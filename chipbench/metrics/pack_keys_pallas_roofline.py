"""HBM roofline share of the ``pack_keys_pallas`` join kernel in the traced
window (``roofline.share``), in percent."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "pack_keys_pallas")
