"""A statistic over requests of a sum of per-request times: client-side
fields of a request row (``ttft_s``, ``tpot_s``) and fields of the
engine's flight-recorder ``timing`` block that came back in the
stream's ``done`` event.

args: {"plus": [...], "minus": [...], "stat": "median"|"p95",
       "scale": 1000.0}; a name with a leading ``timing.`` is read from
the timing block. Requests that did not finish are left out (this is a
layer's reading, not the end-to-end tail); nothing to read -> None."""

from benchmark import stats


def _get(row, name):
    if name.startswith("timing."):
        return (row["timing"] or {}).get(name[len("timing."):])
    return row.get(name)


def read(ctx, args):
    values = []
    for row in ctx.get("rows", []):
        if not row["ok"] or not row["timing"]:
            continue
        parts = [_get(row, n) for n in args.get("plus", [])]
        less = [_get(row, n) for n in args.get("minus", [])]
        if any(p is None for p in parts + less):
            continue
        values.append(sum(parts) - sum(less))
    if not values:
        return None
    stat = args.get("stat", "median")
    q = 50.0 if stat == "median" else float(stat.lstrip("p"))
    return args.get("scale", 1.0) * stats.percentile(values, q)
