"""Host time of join ordering per query executed, in milliseconds: the
``repro.plan.order`` spans' time over the window (the estimated-size
greedy order of each plan built, the predicate statistics' build
included when one falls in the window) over the ``repro.exec.query``
spans. None when the program has no such span."""
from chipbench import spans

SPAN = "repro.plan.order"


def read(ctx):
    if f"span.{SPAN}.calls" not in ctx["counters"]:
        return None
    return spans.per_call(ctx, SPAN, "repro.exec.query")
