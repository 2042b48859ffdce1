"""Operations and bytes a ``granitemoehybrid`` step needs, from the
published sizes alone (``benchmark/flops.py`` has the one dense
block's). They count the model's work whatever implements it: a matrix
2 FLOPs a parameter a token; a Mamba-2 head's recurrence, a token, the
decay, the update and the read of its ``mamba_d_head x mamba_d_state``
state; the attention a key and a value dot product a query head and
cached position. ``cfg`` is a configuration file's dictionary.
"""

from __future__ import annotations

from typing import Any, Dict

from . import weights_granitemoehybrid as W


def layers(cfg: Dict[str, Any]):
    """(Mamba layers, attention layers)."""
    mamba = sum(k == "mamba" for k in cfg["layer_types"])
    return mamba, cfg["num_hidden_layers"] - mamba


def mamba_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of one Mamba-2 mixer (the convolution's taps
    in: they are read a step too)."""
    s = W.sizes(cfg)
    return (cfg["hidden_size"] * (s["in_proj"] + s["inner"])
            + s["conv"] * cfg["mamba_d_conv"])


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], W.sizes(cfg)["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def every_token_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters every token goes through, all layers; not the
    head (a position whose logits are asked for) nor the embedding (a
    row a token)."""
    mamba, attention = layers(cfg)
    return (mamba * mamba_params(cfg) + attention * attention_params(cfg)
            + (mamba + attention) * mlp_params(cfg))


def state_numbers(cfg: Dict[str, Any]) -> int:
    """Numbers of one row's recurrent state, one layer."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"])


def window_flops(cfg: Dict[str, Any], tokens: float, head_tokens: float,
                 attended_positions: float) -> float:
    """Model FLOPs of what a window processed: ``tokens`` through every
    layer (the recurrence 6 FLOPs a state number: decay, update and
    read, a multiply and an add each), ``head_tokens`` through the
    head, and the attention over ``attended_positions`` (cached
    positions summed over tokens, one attention layer's)."""
    mamba, attention = layers(cfg)
    per_position = 4 * cfg["num_attention_heads"] * W.sizes(cfg)["head_dim"]
    return (tokens * (2.0 * every_token_params(cfg)
                      + 6.0 * mamba * state_numbers(cfg))
            + 2.0 * head_tokens * cfg["hidden_size"] * cfg["vocab_size"]
            + attention * per_position * attended_positions)


def decode_step_bytes(cfg: Dict[str, Any], live_rows: float, cached: float,
                      itemsize: int = 2, state_itemsize: int = 4) -> float:
    """Bytes one decode step must move: every matrix once (the tied
    embedding once, as the head: the rows it gives the tokens are a
    gather of ``live_rows`` rows, left out), the recurrent state and
    the convolution's window of the ``live_rows`` rows that were
    decoding, read and written, all Mamba layers, and the grouped K/V
    of the ``cached`` tokens (summed over those rows), all attention
    layers."""
    mamba, attention = layers(cfg)
    s = W.sizes(cfg)
    matrices = every_token_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    row = (state_numbers(cfg) * state_itemsize
           + (cfg["mamba_d_conv"] - 1) * s["conv"] * itemsize)
    kv = 2 * cfg["num_key_value_heads"] * s["head_dim"] * itemsize
    return (matrices * itemsize + 2.0 * live_rows * mamba * row
            + cached * attention * kv)
