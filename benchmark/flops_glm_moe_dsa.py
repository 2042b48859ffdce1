"""Operations and bytes a ``glm_moe_dsa`` step needs, from the published
sizes alone (``benchmark/flops.py`` has the one dense block's). They
count the model's work whatever implements it: a matrix 2 FLOPs a
parameter a token; the indexer one dot product of ``index_head_dim`` a
head and cached position; the main attention, in the plain form the
model is published in, a key and a value dot product a head and
*selected* position. ``cfg`` is a configuration file's dictionary.
"""

from __future__ import annotations

from typing import Any, Dict

from . import weights_glm_moe_dsa as W


def attention_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's attention, the indexer's in."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (d * rq + rq * h * (nope + rd) + d * (c + rd)
            + c * h * (nope + vd) + h * vd * d
            + rq * hi * di + d * di + d * hi)


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layers(cfg: Dict[str, Any]):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def every_token_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters every token goes through, all layers: the
    attentions, the dense FFNs, the routers and the shared experts.
    Not the routed experts (a token meets those it is routed to) and
    not the head (a position whose logits are asked for)."""
    d = cfg["hidden_size"]
    dense, expert = layers(cfg)
    return ((dense + expert) * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + expert * (d * W.router_width(cfg)
                        + cfg["n_shared_experts"] * expert_params(cfg)))


def held_params(cfg: Dict[str, Any]) -> int:
    """Every matrix parameter this share holds, head and embedding in."""
    _, expert = layers(cfg)
    return (every_token_params(cfg)
            + expert * cfg["n_routed_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def window_flops(cfg: Dict[str, Any], tokens: float, head_tokens: float,
                 held_assignments: float, cached_positions: float,
                 selected_positions: float) -> float:
    """Model FLOPs of what a window processed: ``tokens`` through every
    layer, ``head_tokens`` through the head, ``held_assignments``
    (token, expert) pairs through an expert here, and the indexer and
    the main attention over ``cached_positions`` and
    ``selected_positions`` (summed over tokens and layers)."""
    per_selected = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2.0 * tokens * every_token_params(cfg)
            + 2.0 * head_tokens * cfg["hidden_size"] * cfg["vocab_size"]
            + 2.0 * held_assignments * expert_params(cfg)
            + 2.0 * cached_positions * cfg["index_n_heads"]
            * cfg["index_head_dim"]
            + 2.0 * selected_positions * per_selected)


def decode_step_bytes(cfg: Dict[str, Any], cached: float, selected: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must move: every held matrix once (the
    embedding is a gather of a row a token: left out), of the live rows
    the indexer's key of every cached token (``cached``, summed over
    rows) and the latent entries of the selected ones (``selected``)."""
    matrices = held_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    return (matrices * itemsize
            + n * itemsize * cached * cfg["index_head_dim"]
            + n * itemsize * selected * (cfg["kv_lora_rank"]
                                         + cfg["qk_rope_head_dim"]))
