"""The ratio of two counters' growth over the window, from the
replica's /metrics scraped at both edges.

args: {"numerator": name, "denominator": name, "scale": 1.0}. A
denominator that did not grow -> None."""


def read(ctx, args):
    before, after = ctx.get("before"), ctx.get("after")
    if before is None or after is None:
        return None
    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    den = grew(args["denominator"])
    if den <= 0:
        return None
    return args.get("scale", 1.0) * grew(args["numerator"]) / den
