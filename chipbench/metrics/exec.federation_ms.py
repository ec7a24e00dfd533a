"""Host time of the program's federation accounting per query executed, in
milliseconds: the ``repro.exec.federation`` spans' time over the window
(the batch's device scatter-add of serving shards and the per-pattern
accounting after it) over the ``repro.exec.query`` spans."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.exec.federation", "repro.exec.query")
