"""A percentile over every request *sent* of a client-side time
(``ttft_s``: due time to first token; ``tpot_s``: time per output
token), a failed or unfinished request counting as the worst.

args: {"field": "ttft_s"|"tpot_s", "percentile": 50.0, "scale": 1000.0}.
The medians stand here, unbounded, beside the end-to-end tails: with
82 requests a window the median is the statistic the sample supports
(PERF.md section 2)."""

from benchmark import stats


def read(ctx, args):
    rows = ctx.get("rows")
    if not rows:
        return None
    field = args["field"]
    values = [r[field] for r in rows
              if not (field == "tpot_s" and r["ok"] and r["asked"] < 2)]
    return args.get("scale", 1.0) * stats.percentile(
        stats.with_failures(values), args["percentile"])
