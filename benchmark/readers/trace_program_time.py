"""Median device time of one compiled program's runs in the trace.

A program is picked among the modules whose name holds ``match``, the
name the program gave it (``jit_run_kfx_decode_chunk``,
``jit_kfx_train_step``). Where several modules hold it they are ranked
by total device time: ``rank`` 0 (the default) is the one that took
most, ``rank`` 1 the next, ``rank`` "rest" pools every other.

args: {"match": "kfx_decode_chunk", "rank": 0 | "rest", "min_ms": 0.0,
       "divide_by": "serving.decode_chunk" (optional, a key of the
       run's context), "scale": 1000.0}"""

from benchmark import stats


def ranked(tr, match, min_ms=0.0):
    mods = [(name, ds) for name, ds in tr["module_durations"].items()
            if match in name and stats.median(ds) * 1e3 >= min_ms]
    return sorted(mods, key=lambda kv: -sum(kv[1]))


def pick(tr, args):
    mods = ranked(tr, args["match"], args.get("min_ms", 0.0))
    rank = args.get("rank", 0)
    if rank == "rest":
        return [d for _, ds in mods[1:] for d in ds]
    return list(mods[rank][1]) if rank < len(mods) else []


def read(ctx, args):
    tr = ctx.get("trace")
    if not tr:
        return None
    durations = pick(tr, args)
    if not durations:
        return None
    value = stats.median(durations)
    if args.get("divide_by"):
        group, key = args["divide_by"].split(".")
        value /= ctx[group][key]
    return args.get("scale", 1.0) * value
