"""The device's idle share of the traced window: 1 - (union of the
intervals in which an operation ran) / window, averaged over the chips
used. args: none."""


def read(ctx, args):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
