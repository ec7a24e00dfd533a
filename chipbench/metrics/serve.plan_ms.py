"""Host time of query planning per query executed, in milliseconds: the
``repro.serve.plan`` spans' time over the window (``kg.plan`` over a
window's result-cache misses, cached plans included) over the
``repro.exec.query`` spans."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.serve.plan", "repro.exec.query")
