"""Collective time during which no compute operation ran on the chip,
as a share of the traced window (whole steps). args: none. A trace
with no collective in it (one chip) -> None."""


def read(ctx, args):
    tr = ctx.get("trace")
    if not tr or not tr.get("collective_s"):
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
