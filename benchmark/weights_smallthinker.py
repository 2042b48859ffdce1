"""Seeded random weights of a ``smallthinker`` configuration, under the
published leaf names (``self_attn.q_proj``, ...,
``block_sparse_moe.primary_router``, ``block_sparse_moe.experts.<e>.
gate`` / ``up`` / ``down``), one layer at a time.

As ``benchmark/weights.py`` makes them for the one dense block: from
``--seed``, the leaf's name and its layer alone, 16-bit uniform indices
into a table of normal quantiles; matrices normal(0,
``initializer_range`` 0.02) as [in, out], norm scales 1 + 0.1 * normal,
so a dropped scale shows. The router has no bias.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from .weights import (FILL_ROWS_ELEMENTS, LEVELS, MATRIX_STD, SCALE_STD,
                      _quantiles, _tag)

TOP_LEAVES = ("embed_tokens", "norm", "lm_head")
ATTN_LEAVES = ("input_layernorm", "self_attn.q_proj", "self_attn.k_proj",
               "self_attn.v_proj", "self_attn.o_proj",
               "post_attention_layernorm")
ROUTER = "block_sparse_moe.primary_router"
MLP = ("gate", "up", "down")


def is_window_layer(cfg: Dict[str, Any], layer: int) -> bool:
    return bool(cfg["sliding_window_layout"][layer])


def rotates(cfg: Dict[str, Any], layer: int) -> bool:
    return bool(cfg["rope_layout"][layer])


def expert_leaf(expert: int, matrix: str) -> str:
    return f"block_sparse_moe.experts.{expert}.{matrix}"


def layer_leaves(cfg: Dict[str, Any], layer: int) -> List[str]:
    if layer < 0:
        return list(TOP_LEAVES)
    return (list(ATTN_LEAVES) + [ROUTER]
            + [expert_leaf(e, m) for e in
               range(cfg["moe_num_primary_experts"]) for m in MLP])


def leaves(cfg: Dict[str, Any]) -> Iterator[Tuple[str, int]]:
    for layer in range(-1, cfg["num_hidden_layers"]):
        for name in layer_leaves(cfg, layer):
            yield name, layer


def leaf_shape(cfg: Dict[str, Any], name: str) -> Tuple[int, ...]:
    """Logical shape, matrices as [in, out]."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    fixed = {
        "embed_tokens": (v, d), "norm": (d,), "lm_head": (d, v),
        "input_layernorm": (d,), "post_attention_layernorm": (d,),
        "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
        "self_attn.v_proj": (d, kv), "self_attn.o_proj": (q, d),
        ROUTER: (d, cfg["moe_num_primary_experts"]),
    }
    if name in fixed:
        return fixed[name]
    f = cfg["moe_ffn_hidden_size"]
    return (f, d) if name.endswith(".down") else (d, f)


def is_scale(name: str) -> bool:
    return name.endswith("norm")


def host_fill(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              out: np.ndarray) -> None:
    """Fill ``out`` (the leaf's logical shape, any strides, any dtype)
    in place, a block of rows at a time."""
    shape = leaf_shape(cfg, name)
    if out.shape != shape:
        raise ValueError(f"{name}: out is {out.shape}, the leaf {shape}")
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), _tag(name), layer + 1]))
    table = ((1.0 + SCALE_STD * _quantiles()) if is_scale(name)
             else MATRIX_STD * _quantiles()).astype(out.dtype)
    cols = int(np.prod(shape[1:], dtype=np.int64))
    step = max(1, FILL_ROWS_ELEMENTS // cols)
    if (step * cols) % 2 and shape[0] > step:
        step += 1
    for r in range(0, shape[0], step):
        idx = rng.integers(0, LEVELS, size=(min(step, shape[0] - r),)
                           + shape[1:], dtype=np.uint16)
        out[r:r + step] = table[idx]


def keeps_float32(name: str) -> bool:
    """Leaves served in float32 whatever the parameters' type: the norm
    scales, and the router (its logits are float32 by the model)."""
    return is_scale(name) or name == ROUTER


def host_leaf(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              dtype) -> np.ndarray:
    out = np.empty(leaf_shape(cfg, name),
                   np.float32 if keeps_float32(name) else dtype)
    host_fill(seed, cfg, name, layer, out)
    return out
