"""Bytes each Pallas join kernel must move, from the shapes of its call.

Each function counts reading every input once and writing every output
once, in the kernel's own dtypes (int32 and uint32 words). A kernel's
roofline share (``share``) is that traffic at the chip's HBM bandwidth
over the device time of its calls.

``recording`` wraps the kernels' entry points for the traced run and notes
each call's bytes; the wrapper calls through unchanged.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

WORD = 4


def pack_keys_bytes(cols_shape) -> int:
    n, k = cols_shape
    return n * k * WORD + 2 * n * WORD          # key columns in, hi+lo out


def probe_sorted_bytes(build_n: int, probe_n: int) -> int:
    # build hi+lo and probe hi+lo in, lo+hi match bounds out
    return 2 * build_n * WORD + 2 * probe_n * WORD + 2 * probe_n * WORD


def expand_pairs_bytes(segments: int, total: int) -> int:
    # starts, counts, lo in; (li, pos) for every output slot out
    return 3 * segments * WORD + 2 * total * WORD


KERNELS = ("pack_keys_pallas", "probe_sorted_pallas", "expand_pairs_pallas")


def _bytes_of(name: str, args, kwargs) -> int:
    if name == "pack_keys_pallas":
        return pack_keys_bytes(tuple(args[0].shape))
    if name == "probe_sorted_pallas":
        return probe_sorted_bytes(args[0].shape[0], args[2].shape[0])
    return expand_pairs_bytes(args[0].shape[0], int(kwargs["total"]))


def share(ctx: dict, kernel: str) -> Optional[float]:
    """Percent of its HBM roofline that ``kernel`` reached in the traced
    window: the bytes its calls must move at the chip's HBM bandwidth, over
    the device time of its calls. ``None`` where the window made no call of
    it. An error where the trace and the recorded calls disagree in number:
    the bytes would then be set against another amount of work's time."""
    tr = ctx.get("trace")
    calls = ctx.get("kernel_bytes", {}).get(kernel, [])
    got = tr["kernels"].get(kernel) if tr else None
    if not calls and (got is None or got["calls"] == 0):
        return None
    if got is None or got["calls"] != len(calls) or got["seconds"] <= 0:
        raise ValueError(f"{kernel}: {len(calls)} calls recorded, the trace "
                         f"holds {got}")
    least = sum(calls) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / got["seconds"]


@contextlib.contextmanager
def recording(calls: Dict[str, List[int]]):
    """Note the bytes of every join-kernel call made inside the block into
    ``calls[kernel]``."""
    from repro.kernels.join import kernel as jk

    saved = {name: getattr(jk, name) for name in KERNELS}

    def wrap(name, fn):
        def call(*args, **kwargs):
            calls.setdefault(name, []).append(_bytes_of(name, args, kwargs))
            return fn(*args, **kwargs)
        return call

    for name, fn in saved.items():
        setattr(jk, name, wrap(name, fn))
    try:
        yield calls
    finally:
        for name, fn in saved.items():
            setattr(jk, name, fn)
