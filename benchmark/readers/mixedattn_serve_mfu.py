"""Model-FLOPs utilisation of a serving window on a configuration with
window layers beside full ones and routed experts in every layer: the
model FLOPs of the prompt and output tokens the replica processed
between the two scrapes of its counters (benchmark/flops_smallthinker
.py: the attention's and the router's matrices a token, an expert a
routed pair, the head an output token, a key and a value product a
head and visible position) over the seconds between the scrapes and
the chip's peak. Read from the program's own counters: routed pairs
kfx_lm_moe_assignments_total (tokens = pairs / experts a token /
layers); the positions the window layers' rows hold and, of them, those
inside the window, kfx_lm_window_cached_positions_total and
kfx_lm_window_attended_positions_total (each summed over the window
layers: a full layer reads what one window layer's rows hold). Finds
nothing to read (None) where those counters did not grow (a program or
a configuration without them). args: none."""

from benchmark import peaks


def read(ctx, args):
    before, after, cfg = ctx.get("before"), ctx.get("after"), ctx.get("cfg")
    seconds = ctx.get("scrape_seconds")
    if before is None or after is None or not seconds \
            or "sliding_window_layout" not in (cfg or {}):
        return None
    from benchmark import flops_smallthinker as F

    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    pairs = grew("kfx_lm_moe_assignments_total")
    held = grew("kfx_lm_window_cached_positions_total")
    if pairs <= 0 or held <= 0:
        return None
    _, window = F.layers(cfg)
    need = F.window_flops(
        cfg, pairs / (cfg["moe_num_active_primary_experts"]
                      * cfg["num_hidden_layers"]),
        grew("kfx_lm_generated_tokens_total"), pairs, held / window,
        grew("kfx_lm_window_attended_positions_total") / window)
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / (seconds * ctx["device"]["count"] * peak)
