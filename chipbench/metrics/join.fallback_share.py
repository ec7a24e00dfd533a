"""Share of the join pipeline's device time spent in its jnp stages, in
percent: the modules ``jit_pack_keys``, ``jit_probe_sorted`` and
``jit_expand_pairs`` (the searchsorted oracle stages, which also take over
the probe and expand of joins above a Pallas kernel's work cap inside a
pipeline counted as a Pallas pick), over those and the Pallas kernels'
modules, read from the traced window. The gather stage runs in modules it
shares with other work and is left out of both."""

FALLBACK = ("jit_pack_keys", "jit_probe_sorted", "jit_expand_pairs")
PALLAS = ("jit_pack_keys_pallas", "jit_probe_sorted_pallas",
          "jit_expand_pairs_pallas")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    modules = tr["modules"]
    slow = sum(modules.get(m, 0.0) for m in FALLBACK)
    total = slow + sum(modules.get(m, 0.0) for m in PALLAS)
    return 100.0 * slow / total if total > 0 else None
