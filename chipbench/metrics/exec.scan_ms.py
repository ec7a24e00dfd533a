"""Host time of the program's storage scans per query executed, in
milliseconds: the ``repro.exec.scan`` spans' time over the window (the
global store's ``match_indices``, the row gather and the variable
columns of each pattern not yet matched in the batch) over the
``repro.exec.query`` spans, one per result-cache miss."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.exec.scan", "repro.exec.query")
