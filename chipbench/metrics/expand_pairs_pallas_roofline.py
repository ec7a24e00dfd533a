"""HBM roofline share of the ``expand_pairs_pallas`` join kernel in the traced
window (``roofline.share``), in percent."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "expand_pairs_pallas")
