"""HBM roofline share of the ``probe_sorted_pallas`` join kernel in the traced
window (``roofline.share``), in percent."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "probe_sorted_pallas")
