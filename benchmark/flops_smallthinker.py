"""Operations and bytes a ``smallthinker`` step needs, from the
published sizes alone (``benchmark/flops.py`` has the one dense
block's). They count the model's work whatever implements it: a matrix
2 FLOPs a parameter a token; a token the router's matrix and its
``moe_num_active_primary_experts`` experts; the attention a key and a
value dot product a query head and *visible* position: every earlier
one in a full layer, ``sliding_window_size`` at most in a window layer.
``cfg`` is a configuration file's dictionary.
"""

from __future__ import annotations

from typing import Any, Dict

from . import weights_smallthinker as W


def layers(cfg: Dict[str, Any]):
    """(full layers, window layers) of the layers held here."""
    window = sum(W.is_window_layer(cfg, i)
                 for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - window, window


def attention_params(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["hidden_size"] * cfg["head_dim"] * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def router_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["moe_num_primary_experts"]


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def every_token_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters every token goes through, all layers: the
    attentions and the routers. Not the experts (a token meets those
    it is routed to), the head (a position whose logits are asked for)
    or the embedding (a row a token)."""
    return cfg["num_hidden_layers"] * (attention_params(cfg)
                                       + router_params(cfg))


def held_params(cfg: Dict[str, Any]) -> int:
    """Every matrix parameter held here, head and embedding in."""
    return (every_token_params(cfg)
            + cfg["num_hidden_layers"] * cfg["moe_num_primary_experts"]
            * expert_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """A cached position's keys and values, one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def window_flops(cfg: Dict[str, Any], tokens: float, head_tokens: float,
                 assignments: float, held_positions: float,
                 visible_positions: float) -> float:
    """Model FLOPs of what a window processed: ``tokens`` through every
    layer's attention and router, ``head_tokens`` through the head,
    ``assignments`` (token, expert) pairs through an expert (all
    layers'), and the attention over ``held_positions`` (the positions
    a token's row holds, itself in, summed over tokens: what one full
    layer reads) and ``visible_positions`` (of those, the ones inside
    the window: one window layer's)."""
    full, window = layers(cfg)
    per_position = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * tokens * every_token_params(cfg)
            + 2.0 * head_tokens * cfg["hidden_size"] * cfg["vocab_size"]
            + 2.0 * assignments * expert_params(cfg)
            + per_position * (full * held_positions
                              + window * visible_positions))


def decode_step_bytes(cfg: Dict[str, Any], experts_hit: float,
                      held_positions: float, visible_positions: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move: the matrices outside the
    experts once (the routers are float32; the embedding is a gather of
    a row a token: left out), the head, the ``experts_hit`` experts
    that received rows (summed over layers), and the K/V of the live
    rows: ``held_positions`` (summed over rows) a full layer,
    ``visible_positions`` (each row's min(held, window)) a window
    layer."""
    full, window = layers(cfg)
    n = cfg["num_hidden_layers"]
    return (n * (attention_params(cfg) * itemsize + router_params(cfg) * 4)
            + cfg["hidden_size"] * cfg["vocab_size"] * itemsize
            + experts_hit * expert_params(cfg) * itemsize
            + kv_bytes(cfg, itemsize) * (full * held_positions
                                         + window * visible_positions))
