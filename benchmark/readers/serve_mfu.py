"""Model-FLOPs utilisation of a serving window: the model FLOPs of the
prompt and output tokens the replica processed between the two scrapes
of its counters (benchmark/flops_glm_moe_dsa.py: the matrices every
token meets, the held experts it was routed to, the indexer over the
cached keys, the main attention over the selected, the head once an
output token) over the seconds between the scrapes and the chip's peak.
Read from the program's own counters: tokens processed =
kfx_lm_moe_assignments_total / (experts a token x expert layers). The
selected positions are the model's, min(cached, index_topk) a token
and layer of every finished request, not what the program read
(kfx_lm_sparse_attended_positions_total counts the whole view it
scores): work it does beyond the model's is no utilisation.
Finds nothing to read (None) where the counters did not grow (a
program or a configuration without them). args: none."""

from benchmark import peaks


def read(ctx, args):
    before, after, cfg = ctx.get("before"), ctx.get("after"), ctx.get("cfg")
    seconds = ctx.get("scrape_seconds")
    if before is None or after is None or not seconds \
            or "kv_lora_rank" not in (cfg or {}):
        return None
    from benchmark import flops_glm_moe_dsa as F

    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    routed = grew("kfx_lm_moe_assignments_total")
    if routed <= 0:
        return None
    _, expert_layers = F.layers(cfg)
    tokens = routed / (cfg["num_experts_per_tok"] * expert_layers)
    k, selected = cfg["index_topk"], 0.0
    for r in ctx.get("rows") or []:
        if r["ok"]:
            n = r["prompt_len"] + len(r["tokens"])
            selected += min(n, k) * (min(n, k) + 1) / 2 + max(0, n - k) * k
    need = F.window_flops(
        cfg, tokens, grew("kfx_lm_generated_tokens_total"),
        grew("kfx_lm_moe_assignments_held_total"),
        grew("kfx_lm_sparse_cached_positions_total"),
        selected * cfg["num_hidden_layers"])
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / (seconds * ctx["device"]["count"] * peak)
