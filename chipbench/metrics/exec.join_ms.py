"""Host time of the program's joins per query executed, in milliseconds:
the ``repro.exec.join`` spans' time over the window (the fused join
pipeline, host waits on the device included, and the binding gather) over
the ``repro.exec.query`` spans, one per result-cache miss."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.exec.join", "repro.exec.query")
