"""What share of the HBM bandwidth the decode step's device time would
need if it moved only what it must (benchmark/flops_granitemoehybrid
.py): every matrix once, the recurrent state and the convolution's
window of the rows that were decoding, read and written, and the
grouped K/V of the tokens cached in those rows; over the step's device
time and the chip's peak (benchmark/peaks.json).

Bytes and time are the same steps': the traced seconds'. Live rows a
step: the program's own count between the scrapes the traced replica
makes of itself at the trace's edges
(benchmark/readers/ssm_rows_per_step.py): not the slot count, which a
step also moves whether a slot is taken or not. Cached tokens: at the
middle of the traced seconds, over the requests that were decoding
then by the client's clock, a request's prompt and the tokens it had
been sent. Device time: the median decode chunk in the trace. Finds
nothing to read (None) where the counter did not grow (a program or a
configuration without it).

args: {"program": {...trace_program_time args for the decode chunk}}"""

import bisect

from benchmark import peaks, stats
from benchmark.readers import ssm_rows_per_step, trace_program_time


def cached_tokens(rows, at_s):
    """Tokens cached, at ``at_s`` of the window, in the rows whose
    request had its first token and not yet its last."""
    return sum(r["prompt_len"] + bisect.bisect(r["times_in_window"], at_s)
               for r in rows if r["ok"] and r["times_in_window"]
               and r["times_in_window"][0] <= at_s < r["end_s"])


def read(ctx, args):
    live = ssm_rows_per_step.live_rows(ctx)
    if live is None:
        return None
    from benchmark import flops_granitemoehybrid as F

    tr, cfg = ctx["trace"], ctx["cfg"]
    durations = trace_program_time.pick(tr, args["program"])
    if not durations:
        return None
    middle = (tr["t_start"] + tr["t_stop"]) / 2 - ctx["t0_wall"]
    size = lambda name: 2 if name == "bfloat16" else 4
    need = F.decode_step_bytes(
        cfg, live, cached_tokens(ctx["rows"], middle),
        size(cfg["serving"]["param_dtype"]),
        size(cfg["serving"]["state_dtype"]))
    step_s = stats.median(durations) / ctx["serving"]["decode_chunk"]
    peak = peaks.peaks(ctx["device"]["kind"])
    return 100.0 * need / (step_s * peak["hbm_bytes_per_s"])
