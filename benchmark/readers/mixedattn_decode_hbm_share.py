"""What share of the HBM bandwidth the decode step's device time would
need if it moved only what it must (benchmark/flops_smallthinker.py):
the matrices outside the experts and the head once, the experts that
received rows, and the K/V of the live rows: what a row holds in a full
layer, min(that, sliding_window_size) in a window layer; over the
step's device time and the chip's peak (benchmark/peaks.json).

Bytes and time are the same steps': the traced seconds'. The program
counts, on the device and for its decode chunks alone, the experts a
step hit (kfx_lm_decode_experts_hit_total), what the live rows held and
what of that lay inside the window (kfx_lm_decode_window_cached_ /
_attended_positions_total, a window layer; a full layer reads what the
rows hold) and the steps (kfx_lm_sample_steps_total); their growth is
taken between the two scrapes the traced replica makes of itself at
the trace's edges (benchmark/workers/traced_replica_scraped.py). Device
time: the median decode chunk in the trace. Finds nothing to read
(None) where the trace carries no such scrapes or the counters did not
grow: a program or a configuration without them.

args: {"program": {...trace_program_time args for the decode chunk}}"""

from benchmark import peaks, stats
from benchmark.readers import trace_program_time


def read(ctx, args):
    tr, cfg = ctx.get("trace") or {}, ctx.get("cfg") or {}
    counters = tr.get("counters")
    if not counters or "sliding_window_layout" not in cfg:
        return None
    from benchmark import flops_smallthinker as F

    grew = lambda n: (counters["after"].get(n, 0.0)
                      - counters["before"].get(n, 0.0))
    steps = grew("kfx_lm_sample_steps_total")
    hit = grew("kfx_lm_decode_experts_hit_total")
    durations = trace_program_time.pick(tr, args["program"])
    if steps <= 0 or hit <= 0 or not durations:
        return None
    a_layer = steps * F.layers(cfg)[1]    # the window layers count these
    need = F.decode_step_bytes(
        cfg, hit / steps,
        grew("kfx_lm_decode_window_cached_positions_total") / a_layer,
        grew("kfx_lm_decode_window_attended_positions_total") / a_layer,
        2 if cfg["serving"]["param_dtype"] == "bfloat16" else 4)
    step_s = stats.median(durations) / ctx["serving"]["decode_chunk"]
    peak = peaks.peaks(ctx["device"]["kind"])
    return 100.0 * need / (step_s * peak["hbm_bytes_per_s"])
