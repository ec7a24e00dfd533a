"""Shared kernel/fallback dispatch policy for ``repro.kernels``.

Every op module under ``src/repro/kernels`` answers the same three-way
question — compiled Pallas kernel, ``interpret=True`` kernel, or jnp
oracle — and until this module existed each ``ops.py`` hard-coded its own
size threshold (``jaccard`` shipped a literal ``>= 256``). The policy now
lives in one place:

* :func:`on_tpu` — are we on a real TPU backend (compiled kernels)?
* :func:`kernel_threshold` — the problem-size floor below which the jnp
  oracle wins (no tiling/pad overhead). Overridable per-process via the
  ``REPRO_KERNEL_THRESHOLD`` environment variable or per-call via the
  ``threshold=`` argument.
* :func:`resolve` — turn a caller's ``use_kernel``/``interpret`` pair
  (``None`` = auto) into concrete booleans.
* :func:`envelope` / :func:`load_profile` — per-op scaling-envelope values
  (the join family's probe-work / expand-work caps).
  Resolution order: process env var > a loaded **dispatch profile** >
  the op's hard-coded default. Profiles are recorded empirically by
  ``repro.kernels.autotune`` (kernel-vs-fallback crossover sweeps) and
  installed either programmatically (:func:`load_profile`) or via the
  ``REPRO_DISPATCH_PROFILE`` environment variable naming a profile JSON —
  so the envelopes reflect measured hardware, not guesses.

Two auto policies exist, selected by ``hot_path``:

* ``hot_path=False`` (analysis ops, e.g. ``jaccard``): the kernel runs for
  any large-enough problem, *including* ``interpret=True`` on CPU — these
  ops fire once per adaptation round, so the interpreter cost is an
  acceptable price for exercising the real kernel everywhere.
* ``hot_path=True`` (serving ops, e.g. ``join``): interpret mode is never
  chosen automatically — on CPU the jnp oracle serves (XLA-compiled, fast),
  and the Pallas kernel runs only on TPU or when explicitly forced
  (``use_kernel=True``, how the equivalence tests pin it).
"""
from __future__ import annotations

import os

import jax

DEFAULT_KERNEL_THRESHOLD = 256
_ENV_VAR = "REPRO_KERNEL_THRESHOLD"
_PROFILE_ENV = "REPRO_DISPATCH_PROFILE"

# the installed dispatch profile: {envelope name -> value}. Explicit
# load_profile() wins; otherwise lazily loaded from $REPRO_DISPATCH_PROFILE
# (re-read when the env var points somewhere new, so tests can monkeypatch).
_profile: "dict[str, int] | None" = None
_profile_src: "str | None" = None


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def load_profile(profile) -> "dict[str, int]":
    """Install a recorded dispatch profile: a path to an autotune JSON, a
    ``repro.kernels.autotune.DispatchProfile``, or a plain mapping of
    envelope names to values. Returns the installed envelope dict."""
    global _profile, _profile_src
    if hasattr(profile, "envelopes"):                  # DispatchProfile
        data, src = dict(profile.envelopes), "<object>"
    elif isinstance(profile, dict):
        data, src = profile.get("envelopes", profile), "<dict>"
    else:                                              # a JSON path
        import json
        with open(profile) as fh:
            raw = json.load(fh)
        data, src = raw.get("envelopes", raw), str(profile)
    _profile = {str(k): int(v) for k, v in data.items()}
    _profile_src = src
    return dict(_profile)


def clear_profile() -> None:
    global _profile, _profile_src
    _profile = None
    _profile_src = None


def _active_profile() -> "dict[str, int] | None":
    env_path = os.environ.get(_PROFILE_ENV)
    if env_path and _profile_src != env_path and _profile_src not in (
            "<object>", "<dict>"):
        load_profile(env_path)
    return _profile


def envelope(name: str, default: int) -> int:
    """Resolve a dispatch envelope: env var > loaded profile > default."""
    env = os.environ.get(name)
    if env is not None:
        return int(env)
    prof = _active_profile()
    if prof is not None and name in prof:
        return prof[name]
    return default


def kernel_threshold(threshold: int | None = None) -> int:
    """The dispatch size floor: explicit argument > env override > loaded
    profile > default."""
    if threshold is not None:
        return threshold
    return envelope(_ENV_VAR, DEFAULT_KERNEL_THRESHOLD)


def resolve(use_kernel: bool | None, interpret: bool | None, size: int, *,
            hot_path: bool = False,
            threshold: int | None = None) -> tuple[bool, bool]:
    """Resolve a ``(use_kernel, interpret)`` pair for a problem of ``size``.

    ``None`` means auto; explicit booleans pass through untouched (tests
    force ``use_kernel=True`` to pin the kernel path bit-exactly on CPU).
    """
    floor = kernel_threshold(threshold)
    if use_kernel is None:
        if hot_path:
            use_kernel = on_tpu() and size >= floor
        else:
            use_kernel = on_tpu() or size >= floor
    if interpret is None:
        interpret = not on_tpu()
    return use_kernel, interpret


def note_tier(op: str, tier: str, reason: str = "") -> None:
    """Record one dispatch decision in the ambient ``repro.obs`` metrics
    registry (the owning ``KGService``'s): counters
    ``kernels.dispatch.<op>.<tier>`` and, when given, a companion
    ``...<tier>.<reason>`` — so tier picks (pallas/oracle/host) and their
    fallback reasons (size floor, work caps, int32 envelopes) are
    attributable per op. No-op when no registry is
    installed; called once per op dispatch, never per row."""
    from repro.obs import metrics as obs_metrics
    m = obs_metrics.ambient()
    if m is None:
        return
    m.counter(f"kernels.dispatch.{op}.{tier}").inc()
    if reason:
        m.counter(f"kernels.dispatch.{op}.{tier}.{reason}").inc()
