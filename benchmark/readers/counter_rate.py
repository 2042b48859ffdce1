"""A counter's growth a second of the window, from the replica's
/metrics scraped at both edges and the seconds between the two scrapes
(the window and its drain).

args: {"counter": name, "scale": 1.0}. A counter the replica does not
have, or a run that kept no time between its scrapes -> None."""


def read(ctx, args):
    before, after = ctx.get("before"), ctx.get("after")
    seconds = ctx.get("scrape_seconds")
    if before is None or after is None or not seconds \
            or args["counter"] not in after:
        return None
    grew = after[args["counter"]] - before.get(args["counter"], 0.0)
    return args.get("scale", 1.0) * grew / seconds
