"""Host time of the join pipeline's build-side sort per query executed, in
milliseconds: the ``repro.join.sort`` spans' time over the window (the
word recombination, the sort key's fetch, which waits on the pack kernels,
the host ``np.argsort``, the order's upload and the build side put in
order) over the ``repro.exec.query`` spans."""
from chipbench import spans


def read(ctx):
    return spans.per_call(ctx, "repro.join.sort", "repro.exec.query")
