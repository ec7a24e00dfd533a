"""Host time an adaptation round spends clustering its queries, in
milliseconds: the ``repro.adapt.cluster`` spans' time over the window
(workload bitmaps, the Jaccard distance matrix on the device and its
fetch, HAC and the cut on the host) over the ``repro.adapt.round``
spans."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.adapt.cluster", "repro.adapt.round")
