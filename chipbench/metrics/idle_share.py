"""Share of the traced window in which no operation ran on the device,
percent: 1 - (union of the device's operation intervals) / window.
Layer: device."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
