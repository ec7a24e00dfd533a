"""Share of the window's join-pipeline tier picks that went to the Pallas
kernels (``kernels.dispatch.join.pipeline.pallas`` over the pallas, oracle
and host picks), in percent."""

TIERS = ("pallas", "oracle", "host")


def read(ctx):
    c = ctx["counters"]
    picks = {t: c.get(f"kernels.dispatch.join.pipeline.{t}", 0)
             for t in TIERS}
    total = sum(picks.values())
    return 100.0 * picks["pallas"] / total if total else None
