"""Model-FLOPs accounting for MFU reporting.

Convention (PaLM appendix B / scaling-book): count the matmul FLOPs the
model *requires* — 2·m·n·k per matmul, attention scored over the full
sequence (no causal discount), backward = 2x forward, and remat
recomputation NOT counted (MFU penalises remat rather than crediting it).
"""

from __future__ import annotations

from typing import Optional

# Peak dense bf16 FLOP/s per chip, keyed by the ``device_kind`` JAX
# reports (Google Cloud TPU documentation, per-generation spec tables).
# The one table: a device that is not in it has no MFU.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def transformer_fwd_flops_per_token(cfg, seq_len: int) -> float:
    """Forward matmul FLOPs per token for models.transformer.TransformerLM."""
    d, hh = cfg.d_model, cfg.n_heads * cfg.head_dim
    per_layer = (
        2 * d * 3 * hh          # qkv projections
        + 2 * hh * d            # output projection
        + 2 * 2 * seq_len * hh  # scores (q·k) + mixing (probs·v)
    )
    if cfg.n_experts > 0:
        per_layer += 2 * d * cfg.n_experts                    # router gate
        per_layer += cfg.expert_top_k * 6 * d * cfg.d_ff      # SwiGLU experts
    else:
        per_layer += 6 * d * cfg.d_ff                         # SwiGLU wi+wo
    return cfg.n_layers * per_layer + 2 * d * cfg.vocab_size  # + lm head


def transformer_train_flops_per_token(cfg, seq_len: int) -> float:
    """fwd + bwd (2x fwd) matmul FLOPs per trained token."""
    return 3.0 * transformer_fwd_flops_per_token(cfg, seq_len)


def peak_flops_per_chip() -> float:
    """Peak bf16 FLOP/s of the attached chip, so MFU is computed against
    the right roofline. A device without a published peak (the CPU
    included) is an error, never a default: a utilisation against
    someone else's peak is not a measurement."""
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return PEAK_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no published peak FLOP/s for device kind {kind!r}: MFU is "
            f"undefined on it (known: {sorted(PEAK_FLOPS)})") from None


def mfu(tokens_per_s: float, flops_per_token: float,
        n_chips: int = 1, peak: Optional[float] = None) -> float:
    peak = peak or peak_flops_per_chip()
    return tokens_per_s * flops_per_token / (n_chips * peak)
