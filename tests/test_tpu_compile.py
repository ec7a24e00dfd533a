"""Compile the serving path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel or jitted stage at the shapes that
LUBM(10) over 8 shards sends it (``chip_smoke.py``'s extended workload,
adaptation round and federation accounting) and compiles it with the TPU
compiler, which refuses what the chip cannot run — layouts, unsupported
lowerings, fast-memory overruns — where interpret mode passes. Where a
Pallas kernel is expected, the compiled program must hold its
``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.jaccard import kernel as jaccard_kernel
from repro.kernels.join import kernel, ops

# largest shapes per stage on LUBM(10)/8 (CPU rehearsal of chip_smoke.py)
MAX_ROWS = 735_594            # largest join side (probe rows)
PROBE_BUILD, PROBE_ROWS = 117_490, 36_095    # largest probe under the cap
WIDE_BUILD = 317_079          # largest build side
EXPAND_TOTAL = 1 << 20        # pow2 bucket of the largest expansion
FEDERATION_SEGMENTS, FEDERATION_TOTAL = 54, 1 << 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return fn.lower(*args, **static).compile()


def test_topology_is_v5e(topo):
    assert "v5" in topo.devices[0].device_kind.lower()


@pytest.mark.parametrize("k", [1, 2])
def test_pack_keys_kernel_compiles(one_chip, k):
    c = _compile(kernel.pack_keys_pallas, one_chip,
                 ((MAX_ROWS, k), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("build,probe", [(PROBE_BUILD, PROBE_ROWS),
                                         (WIDE_BUILD, 256)])
def test_probe_sorted_kernel_compiles(one_chip, build, probe):
    c = _compile(kernel.probe_sorted_pallas, one_chip,
                 ((build,), jnp.int32), ((build,), jnp.uint32),
                 ((probe,), jnp.int32), ((probe,), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("segments,total", [
    (203, EXPAND_TOTAL),                         # join: few runs, wide
    (587_583, 4096),                             # join: many short runs
    (FEDERATION_SEGMENTS, FEDERATION_TOTAL),     # federation segment ids
])
def test_expand_pairs_kernel_compiles(one_chip, segments, total):
    c = _compile(kernel.expand_pairs_pallas, one_chip,
                 ((segments,), jnp.int32), ((segments,), jnp.int32),
                 ((segments,), jnp.int32), total=total)
    assert "tpu_custom_call" in c.as_text()


def test_gather_stage_compiles(one_chip):
    """The pipeline's gather stage is XLA's device gather (no Pallas
    kernel: Mosaic lowers only 2-D gathers)."""
    c = _compile(ops._pipe_fns()["pair_gather"], one_chip,
                 ((WIDE_BUILD,), jnp.int32), ((MAX_ROWS,), jnp.int32))
    assert c.memory_analysis().output_size_in_bytes >= MAX_ROWS * 4


@pytest.mark.parametrize("q,w", [(14, 2), (24, 2)])
def test_jaccard_kernel_compiles(one_chip, q, w):
    c = _compile(jaccard_kernel.jaccard_distance_pallas, one_chip,
                 ((q, w), jnp.uint32), ((q, w), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


def test_federation_scatter_add_compiles(one_chip):
    """The executor's one scatter-add per window: per-match shard ids
    counted into (distinct patterns, shards) on the device."""
    def bincount(seg, shard):
        out = jnp.zeros((FEDERATION_SEGMENTS, 8), jnp.int32)
        return out.at[seg, shard].add(1)

    c = _compile(jax.jit(bincount), one_chip,
                 ((FEDERATION_TOTAL,), jnp.int32),
                 ((FEDERATION_TOTAL,), jnp.int32))
    assert c.memory_analysis() is not None


def test_x64_oracle_stages_compile(one_chip):
    """The int64 jnp stages (the oracle tier and the pipeline's per-stage
    work-cap fallbacks) compile for the chip under x64."""
    pack, search = ops._oracle_fns()
    fns = ops._pipe_fns()
    rows = ops._pow2_len(MAX_ROWS)
    with jax.enable_x64(True):
        _compile(pack, one_chip, ((rows, 2), jnp.int64))
        _compile(search, one_chip, ((ops._pow2_len(WIDE_BUILD),), jnp.int64),
                 ((rows,), jnp.int64))
        for dt in (jnp.int64, jnp.int32):       # oracle tier, work-cap stage
            _compile(fns["expand"], one_chip, ((rows,), dt), ((rows,), dt),
                     ((rows,), dt), total=EXPAND_TOTAL)
        c = _compile(fns["join_words"], one_chip, ((MAX_ROWS,), jnp.int32),
                     ((MAX_ROWS,), jnp.uint32))
    assert c.memory_analysis().output_size_in_bytes >= MAX_ROWS * 8
