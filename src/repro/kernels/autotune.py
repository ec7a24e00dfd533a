"""Empirical kernel-vs-fallback dispatch tuning.

The join family's auto dispatch is governed by scaling envelopes — the
quadratic probe-work cap and the expand ownership-test cap
(``join/ops.py``). Their defaults are analytical
guesses; this module replaces guesses with measurements on the backend
that will actually serve: it sweeps each stage's Pallas kernel against the
fallback tier auto dispatch would otherwise pick (host numpy on CPU, the
jitted-jnp oracle on TPU), finds the work size where the kernel stops
winning, and records the crossover as a **dispatch profile** —

```
profile = autotune.tune_join()            # sweep on this backend
profile.save("results/dispatch_profile.json")
profile.install()                         # envelopes now govern dispatch
```

— which ``repro.kernels.dispatch`` resolves per call (env var > installed
profile > default), either installed programmatically or named via the
``REPRO_DISPATCH_PROFILE`` environment variable. The CLI form feeds CI and
the docs' crossover table::

    python -m repro.kernels.autotune --quick --out results/profile.json

On this CPU container the kernels execute in interpret mode (Python
per-op), so a recorded CPU profile legitimately measures "the kernel never
wins" and pins the caps to 0 — exactly the right dispatch decision there;
the TPU profile is the one with nontrivial crossovers.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, List, Sequence

import numpy as np

from repro.kernels import dispatch
from repro.kernels.join import ops

# envelope names, shared with join/ops.py (the single source of the
# defaults below is the ops module's getters — kept in sync by the tests)
PROBE_CAP = "REPRO_JOIN_PROBE_WORK_CAP"
EXPAND_CAP = "REPRO_JOIN_EXPAND_WORK_CAP"

_DEFAULTS = {PROBE_CAP: 1 << 32, EXPAND_CAP: 1 << 32}


@dataclasses.dataclass
class Measurement:
    """One sweep point: the stage's abstract work size (the quantity the
    envelope caps — compare pairs for the probe, ownership tests for the
    expand) and both tiers' wall time."""
    stage: str
    work: int
    kernel_us: float
    fallback_us: float

    @property
    def kernel_wins(self) -> bool:
        return self.kernel_us <= self.fallback_us


def crossover_cap(measurements: Sequence[Measurement], *, default: int,
                  ) -> int:
    """The empirical envelope value from a sweep: the work size past which
    the kernel loses to the fallback.

    * kernel never wins -> 0 (auto dispatch always falls back);
    * kernel still wins at the largest measured work -> ``default`` (no
      crossover observed inside the sweep, keep the analytical cap);
    * otherwise the geometric midpoint between the largest winning work
      and the smallest losing work above it — the sweep brackets the true
      crossover, and work scales multiplicatively.
    """
    ms = sorted(measurements, key=lambda m: m.work)
    wins = [m.work for m in ms if m.kernel_wins]
    if not wins:
        return 0
    last_win = max(wins)
    losses_above = [m.work for m in ms
                    if not m.kernel_wins and m.work > last_win]
    if not losses_above:
        return default
    return int(np.sqrt(float(last_win) * float(min(losses_above))))


@dataclasses.dataclass
class DispatchProfile:
    """A recorded set of dispatch envelopes plus the measurements behind
    them. ``kernels.dispatch.load_profile`` accepts it directly (it quacks
    via ``.envelopes``); :meth:`save`/:meth:`load` round-trip the JSON form
    the ``REPRO_DISPATCH_PROFILE`` env var points at."""
    envelopes: Dict[str, int]
    backend: str = "cpu"
    measurements: List[Measurement] = dataclasses.field(default_factory=list)

    def install(self) -> Dict[str, int]:
        return dispatch.load_profile(self)

    def save(self, path: str) -> None:
        payload = {
            "backend": self.backend,
            "envelopes": {k: int(v) for k, v in self.envelopes.items()},
            "measurements": [dataclasses.asdict(m)
                             for m in self.measurements],
        }
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")

    @classmethod
    def load(cls, path: str) -> "DispatchProfile":
        with open(path) as fh:
            raw = json.load(fh)
        return cls(envelopes={k: int(v)
                              for k, v in raw.get("envelopes", {}).items()},
                   backend=raw.get("backend", "cpu"),
                   measurements=[Measurement(**m)
                                 for m in raw.get("measurements", [])])


def _time_us(fn: Callable[[], object], repeats: int = 3) -> float:
    import jax

    jax.block_until_ready(fn())                   # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / repeats * 1e6


def _join_fixture(rng: np.random.Generator, nl: int, nr: int):
    """Executor-shaped key columns, 50% hit rate (bench_kernels' shape)."""
    lcs = [rng.integers(0, 2**31 - 1, nl).astype(np.int64) for _ in range(2)]
    rcs = [rng.integers(0, 2**31 - 1, nr).astype(np.int64) for _ in range(2)]
    n = min(nl, nr) // 2
    for c in range(2):
        rcs[c][:n] = lcs[c][:n]
    return lcs, rcs


def tune_join(*, quick: bool = False,
              sizes: Sequence[int] | None = None,
              timer: Callable[[Callable[[], object]], float] | None = None,
              rng: np.random.Generator | None = None) -> DispatchProfile:
    """Sweep the join family's kernel stages against the fallback tier auto
    dispatch would pick on this backend, and return the recorded profile.

    ``timer`` is injectable (``fn -> microseconds``) so the crossover logic
    is unit-testable with synthetic clocks; ``sizes`` are per-side row
    counts (work scales quadratically off them for probe/expand).
    """
    import jax

    timer = timer or _time_us
    rng = rng or np.random.default_rng(0)
    if sizes is None:
        sizes = (64, 128) if quick else (256, 1024, 4096)
    on_tpu = dispatch.on_tpu()
    interpret = not on_tpu
    sweeps: Dict[str, List[Measurement]] = {"probe": [], "expand": []}
    for n in sizes:
        lcs, rcs = _join_fixture(rng, n, n)
        _, lo, counts = ops.hash_probe_numpy(lcs, rcs)
        total = int(counts.sum())

        k = timer(lambda: ops.hash_probe(lcs, rcs, use_kernel=True,
                                         interpret=interpret))
        f = timer((lambda: ops.hash_probe_oracle(lcs, rcs)) if on_tpu
                  else (lambda: ops.hash_probe_numpy(lcs, rcs)))
        sweeps["probe"].append(Measurement("probe", n * n, k, f))

        k = timer(lambda: ops.expand_pairs(lo, counts, use_kernel=True,
                                           interpret=interpret))
        f = timer((lambda: ops.expand_pairs(lo, counts, use_kernel=False))
                  if on_tpu else (lambda: ops.expand_pairs_numpy(lo, counts)))
        sweeps["expand"].append(Measurement("expand", total * n, k, f))

    envelopes = {
        PROBE_CAP: crossover_cap(sweeps["probe"],
                                 default=_DEFAULTS[PROBE_CAP]),
        EXPAND_CAP: crossover_cap(sweeps["expand"],
                                  default=_DEFAULTS[EXPAND_CAP]),
    }
    return DispatchProfile(envelopes=envelopes,
                           backend=jax.default_backend(),
                           measurements=[m for ms in sweeps.values()
                                         for m in ms])


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="write the recorded profile JSON here")
    ap.add_argument("--quick", action="store_true",
                    help="tiny sweep (CI smoke)")
    ap.add_argument("--install", action="store_true",
                    help="install the profile into this process's dispatch "
                         "(demonstrates load; mostly useful under a REPL)")
    args = ap.parse_args()
    profile = tune_join(quick=args.quick)
    print("stage,work,kernel_us,fallback_us,kernel_wins")
    for m in profile.measurements:
        print(f"{m.stage},{m.work},{m.kernel_us:.1f},{m.fallback_us:.1f},"
              f"{int(m.kernel_wins)}")
    print("envelope,value")
    for k, v in profile.envelopes.items():
        print(f"{k},{v}")
    if args.install:
        profile.install()
    if args.out:
        profile.save(args.out)
        print(f"wrote {args.out} (backend={profile.backend})")


if __name__ == "__main__":
    main()
