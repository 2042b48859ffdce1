"""Median of the worker's synced optimizer steps (host clock), in ms.
args: none."""

from benchmark import stats


def read(ctx, args):
    w = ctx.get("worker")
    if not w or not w.get("step_s"):
        return None
    return 1e3 * stats.median(w["step_s"])
