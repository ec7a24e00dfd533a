"""Host time an adaptation round spends pricing layouts, in milliseconds:
the ``repro.adapt.measure`` spans' time over the window (each
``PartitionedKG.measure_candidate`` call, baseline and candidates, query
profiles built on the way included) over the ``repro.adapt.round``
spans."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.adapt.measure", "repro.adapt.round")
