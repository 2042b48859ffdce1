"""Model-FLOPs utilisation of a serving window on a configuration with
state-space layers: the model FLOPs of the prompt and output tokens the
replica processed between the two scrapes of its counters
(benchmark/flops_granitemoehybrid.py: the matrices every token meets,
the recurrence a state number, the attention over the cached positions,
the head once an output token) over the seconds between the scrapes and
the chip's peak. Read from the program's own counters: tokens processed
= (kfx_lm_ssm_prefill_tokens_total + kfx_lm_ssm_row_updates_total) /
state-space layers. The attended positions are the model's: a finished
request of n tokens attends n (n + 1) / 2 positions an attention layer.
Finds nothing to read (None) where the counters did not grow (a program
or a configuration without them). args: none."""

from benchmark import peaks


def read(ctx, args):
    before, after, cfg = ctx.get("before"), ctx.get("after"), ctx.get("cfg")
    seconds = ctx.get("scrape_seconds")
    if before is None or after is None or not seconds \
            or "mamba_n_heads" not in (cfg or {}):
        return None
    from benchmark import flops_granitemoehybrid as F

    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    through = (grew("kfx_lm_ssm_prefill_tokens_total")
               + grew("kfx_lm_ssm_row_updates_total"))
    if through <= 0:
        return None
    mamba, _ = F.layers(cfg)
    attended = sum(n * (n + 1) / 2 for n in (
        r["prompt_len"] + len(r["tokens"])
        for r in ctx.get("rows") or [] if r["ok"]))
    need = F.window_flops(cfg, through / mamba,
                          grew("kfx_lm_generated_tokens_total"), attended)
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / (seconds * ctx["device"]["count"] * peak)
