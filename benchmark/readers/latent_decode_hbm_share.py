"""What share of the HBM bandwidth the decode step's device time would
need if it moved only what it must (benchmark/flops_glm_moe_dsa.py):
every held matrix once, the indexer's key of every token cached in the
live rows, and the latent entries of the ``index_topk`` at most that
each row's attention reads; over the step's device time and the chip's
peak (benchmark/peaks.json).

Cached and selected tokens a step: a finished request with a prompt of
p tokens reads p + j cached tokens at its j-th step and min(p + j,
index_topk) latent entries; their sums over the requests, divided by
the decode steps the engine made in the window (chunks x tokens a
chunk). Finds nothing to read (None) on a configuration with no latent
cache.

args: {"program": {...trace_program_time args for the decode chunk}}"""

from benchmark import peaks, stats
from benchmark.readers import trace_program_time


def read(ctx, args):
    tr, before, after = ctx.get("trace"), ctx.get("before"), ctx.get("after")
    cfg = ctx.get("cfg", {})
    if not tr or before is None or "kv_lora_rank" not in cfg:
        return None
    from benchmark import flops_glm_moe_dsa as F

    chunk = ctx["serving"]["decode_chunk"]
    durations = trace_program_time.pick(tr, args["program"])
    chunks = (after.get("kfx_lm_engine_chunks_total", 0)
              - before.get("kfx_lm_engine_chunks_total", 0))
    if not durations or chunks <= 0:
        return None
    k = cfg["index_topk"]
    cached = selected = 0.0
    for r in ctx["rows"]:
        if r["ok"]:
            at = [r["prompt_len"] + j for j in range(len(r["tokens"]))]
            cached += sum(at)
            selected += sum(min(a, k) for a in at)
    steps = chunks * chunk
    itemsize = 2 if cfg["serving"]["param_dtype"] == "bfloat16" else 4
    need = F.decode_step_bytes(cfg, cached / steps, selected / steps,
                               itemsize)
    step_s = stats.median(durations) / chunk
    peak = peaks.peaks(ctx["device"]["kind"])
    return 100.0 * need / (step_s * peak["hbm_bytes_per_s"])
