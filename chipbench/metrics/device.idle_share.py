"""Share of the traced window in which no operation ran on the device:
one less the union of the device's op intervals over the window, in
percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
