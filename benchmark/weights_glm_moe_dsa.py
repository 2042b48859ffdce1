"""Seeded random weights of a ``glm_moe_dsa`` configuration, under the
published leaf names (DeepSeek-V3 / V3.2 naming: ``q_a_proj``,
``kv_a_proj_with_mqa``, ``indexer.wq_b``, ``mlp.gate``,
``mlp.experts.<e>.gate_proj``, ...), one layer at a time.

As ``benchmark/weights.py`` makes them for the one dense block: from
``--seed``, the leaf's name and its layer alone, 16-bit uniform indices
into a table of normal quantiles; matrices normal(0, 0.02) as [in, out],
norm scales 1 + 0.1 * normal, so a dropped scale shows. **The router's
bias is not zero** (0.1 * normal): a program that drops it chooses other
experts. A routed expert is named by its number in the whole model, so
every share of the experts draws the same expert the uncut model has.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from .weights import (FILL_ROWS_ELEMENTS, LEVELS, MATRIX_STD, SCALE_STD,
                      _quantiles, _tag)

BIAS_STD = 0.1
TOP_LEAVES = ("embed_tokens", "norm", "lm_head")
ATTN_LEAVES = ("input_layernorm", "q_a_proj", "q_a_layernorm", "q_b_proj",
               "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
               "o_proj", "indexer.wq_b", "indexer.wk", "indexer.k_norm",
               "indexer.weights_proj", "post_attention_layernorm")
MLP = ("gate_proj", "up_proj", "down_proj")


def router_width(cfg: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the
    file's ``n_routed_experts`` is this share's (``reduced``)."""
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else cfg["n_routed_experts"]


def held_experts(cfg: Dict[str, Any]) -> range:
    """The routed experts this share holds (``n_routed_experts`` of
    them from ``share.first_expert``), by their number in the whole
    model."""
    first = cfg.get("share", {}).get("first_expert", 0)
    return range(first, first + cfg["n_routed_experts"])


def is_expert_layer(cfg: Dict[str, Any], layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def layer_leaves(cfg: Dict[str, Any], layer: int) -> List[str]:
    if layer < 0:
        return list(TOP_LEAVES)
    if not is_expert_layer(cfg, layer):
        return list(ATTN_LEAVES) + [f"mlp.{m}" for m in MLP]
    return (list(ATTN_LEAVES) + ["mlp.gate", "mlp.gate.bias"]
            + [f"mlp.shared_experts.{m}" for m in MLP]
            + [f"mlp.experts.{e}.{m}" for e in held_experts(cfg)
               for m in MLP])


def leaves(cfg: Dict[str, Any]) -> Iterator[Tuple[str, int]]:
    for layer in range(-1, cfg["num_hidden_layers"]):
        for name in layer_leaves(cfg, layer):
            yield name, layer


def leaf_shape(cfg: Dict[str, Any], name: str) -> Tuple[int, ...]:
    """Logical shape, matrices as [in, out]."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, rq, c = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    fixed = {
        "embed_tokens": (v, d), "norm": (d,), "lm_head": (d, v),
        "input_layernorm": (d,), "post_attention_layernorm": (d,),
        "q_a_proj": (d, rq), "q_a_layernorm": (rq,),
        "q_b_proj": (rq, h * (nope + rope)),
        "kv_a_proj_with_mqa": (d, c + rope), "kv_a_layernorm": (c,),
        "kv_b_proj": (c, h * (nope + vd)), "o_proj": (h * vd, d),
        "indexer.wq_b": (rq, hi * di), "indexer.wk": (d, di),
        "indexer.k_norm": (di,), "indexer.weights_proj": (d, hi),
        "mlp.gate": (d, router_width(cfg)),
        "mlp.gate.bias": (router_width(cfg),),
    }
    if name in fixed:
        return fixed[name]
    width = cfg["intermediate_size"]
    if name.startswith("mlp.shared_experts."):
        width = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    elif name.startswith("mlp.experts."):
        width = cfg["moe_intermediate_size"]
    return (width, d) if name.endswith("down_proj") else (d, width)


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
    if is_scale(name):
        table = (1.0 + SCALE_STD * _quantiles()).astype(out.dtype)
    elif name == "mlp.gate.bias":
        table = (BIAS_STD * _quantiles()).astype(out.dtype)
    else:
        table = (MATRIX_STD * _quantiles()).astype(out.dtype)
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
    scales, and the router (its scores are float32 by the model)."""
    return is_scale(name) or name in ("mlp.gate", "mlp.gate.bias")


def host_leaf(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              dtype) -> np.ndarray:
    out = np.empty(leaf_shape(cfg, name),
                   np.float32 if keeps_float32(name) else dtype)
    host_fill(seed, cfg, name, layer, out)
    return out
