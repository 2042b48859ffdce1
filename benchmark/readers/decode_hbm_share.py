"""What share of the HBM bandwidth the decode step's device time would
need if it moved only what it must: every matrix once, and the K/V of
the tokens actually cached in the live rows (benchmark/flops.py), over
the step's device time and the chip's peak (benchmark/peaks.json).

Cached tokens a step: over the requests the window finished, a request
with a prompt of p tokens and n generated reads p + j cached tokens at
its j-th step; the sum over all of them, divided by the decode steps
the engine made in the window (chunks dispatched x tokens a chunk).

args: {"program": {...trace_program_time args for the decode chunk}}"""

from benchmark import flops, peaks
from benchmark.readers import trace_program_time


def read(ctx, args):
    tr, before, after = ctx.get("trace"), ctx.get("before"), ctx.get("after")
    if not tr or before is None:
        return None
    chunk = ctx["serving"]["decode_chunk"]
    durations = trace_program_time.pick(tr, args["program"])
    chunks = (after.get("kfx_lm_engine_chunks_total", 0)
              - before.get("kfx_lm_engine_chunks_total", 0))
    if not durations or chunks <= 0:
        return None
    from benchmark import stats

    step_s = stats.median(durations) / chunk
    cached = sum(len(r["tokens"]) * r["prompt_len"]
                 + len(r["tokens"]) * (len(r["tokens"]) - 1) / 2
                 for r in ctx["rows"] if r["ok"]) / (chunks * chunk)
    itemsize = 2 if ctx["cfg"]["serving"]["param_dtype"] == "bfloat16" else 4
    need = flops.decode_step_bytes(ctx["cfg"], cached, itemsize, 2)
    peak = peaks.peaks(ctx["device"]["kind"])
    return 100.0 * need / (step_s * peak["hbm_bytes_per_s"])
