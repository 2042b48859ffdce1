"""Model-FLOPs utilisation of the window: tokens/s x the FLOPs a token
requires (benchmark/flops.py: forward + backward, attention over the
whole sequence, remat not credited) over chips x peak. args: none."""

from benchmark import flops, peaks


def read(ctx, args):
    if "worker" not in ctx:
        return None
    need = flops.train_flops_per_token(ctx["cfg"],
                                       ctx["mix"]["sequence_tokens"])
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * ctx["e2e"]["train_tokens_per_s"] * need
            / (ctx["device"]["count"] * peak))
