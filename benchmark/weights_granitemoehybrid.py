"""Seeded random weights of a ``granitemoehybrid`` configuration under
the published leaf names (``mamba.in_proj``, ``mamba.conv1d.weight``,
``self_attn.q_proj``, ``shared_mlp.input_linear``, ...), one layer at a
time, as ``benchmark/weights.py`` makes the one dense block's: from
``--seed``, the leaf's name and its layer alone.

Matrices normal(0, ``initializer_range``) as [in, out]; norm scales (the mixer's gated
norm too) 1 + 0.1 * normal, so a dropped scale shows. What plain normal
draws would leave dead is drawn as the Mamba-2 family initialises it
(the configuration's ``assumed``): ``A_log`` = log(uniform(1, 16)),
``dt_bias`` the inverse softplus of a log-uniform(1e-3, 1e-1) step,
``D`` ones; the convolution's taps normal(0, 1 / sqrt(taps)) and its
bias normal(0, 0.1), so that the signal reaches the state and a dropped
bias shows. ``mamba.conv1d.weight`` is [channels, taps] (the published
[channels, 1, taps]); ``shared_mlp.input_linear`` [hidden, 2 x width],
the gate's half first. The head is the embedding: there is no
``lm_head``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from .weights import FILL_ROWS_ELEMENTS, LEVELS, SCALE_STD, _quantiles, _tag

CONV_BIAS_STD = 0.1
TOP_LEAVES = ("embed_tokens", "norm")
MAMBA_LEAVES = ("input_layernorm", "mamba.in_proj", "mamba.conv1d.weight",
                "mamba.conv1d.bias", "mamba.dt_bias", "mamba.A_log",
                "mamba.D", "mamba.norm", "mamba.out_proj")
ATTENTION_LEAVES = ("input_layernorm", "self_attn.q_proj",
                    "self_attn.k_proj", "self_attn.v_proj",
                    "self_attn.o_proj")
MLP_LEAVES = ("post_attention_layernorm", "shared_mlp.input_linear",
              "shared_mlp.output_linear")
FLOAT32 = ("mamba.dt_bias", "mamba.A_log", "mamba.D")


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The mixer's widths from the published keys."""
    d = cfg["hidden_size"]
    heads, state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = cfg["mamba_expand"] * d
    if inner != heads * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads "
                         "x mamba_d_head")
    conv = inner + 2 * cfg["mamba_n_groups"] * state
    return {"inner": inner, "conv": conv, "in_proj": inner + conv + heads,
            "head_dim": d // cfg["num_attention_heads"]}


def layer_leaves(cfg: Dict[str, Any], layer: int) -> List[str]:
    if layer < 0:
        return list(TOP_LEAVES)
    kind = cfg["layer_types"][layer]
    return list(MAMBA_LEAVES if kind == "mamba" else ATTENTION_LEAVES) \
        + list(MLP_LEAVES)


def leaves(cfg: Dict[str, Any]) -> Iterator[Tuple[str, int]]:
    for layer in range(-1, cfg["num_hidden_layers"]):
        for name in layer_leaves(cfg, layer):
            yield name, layer


def leaf_shape(cfg: Dict[str, Any], name: str) -> Tuple[int, ...]:
    """Logical shape, matrices as [in, out]."""
    d, v, f = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["shared_intermediate_size"])
    s = sizes(cfg)
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 s["head_dim"])
    heads = cfg["mamba_n_heads"]
    return {
        "embed_tokens": (v, d), "norm": (d,),
        "input_layernorm": (d,), "post_attention_layernorm": (d,),
        "mamba.in_proj": (d, s["in_proj"]),
        "mamba.conv1d.weight": (s["conv"], cfg["mamba_d_conv"]),
        "mamba.conv1d.bias": (s["conv"],),
        "mamba.dt_bias": (heads,), "mamba.A_log": (heads,),
        "mamba.D": (heads,), "mamba.norm": (s["inner"],),
        "mamba.out_proj": (s["inner"], d),
        "self_attn.q_proj": (d, h * hd), "self_attn.k_proj": (d, kv * hd),
        "self_attn.v_proj": (d, kv * hd), "self_attn.o_proj": (h * hd, d),
        "shared_mlp.input_linear": (d, 2 * f),
        "shared_mlp.output_linear": (f, d),
    }[name]


def is_scale(name: str) -> bool:
    return name.endswith("norm")


def keeps_float32(name: str) -> bool:
    """Leaves served in float32 whatever the parameters' type."""
    return is_scale(name) or name in FLOAT32


def host_fill(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              out: np.ndarray) -> None:
    """Fill ``out`` (the leaf's logical shape, any strides, any dtype)
    in place, a block of rows at a time."""
    shape = leaf_shape(cfg, name)
    if out.shape != shape:
        raise ValueError(f"{name}: out is {out.shape}, the leaf {shape}")
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), _tag(name), layer + 1]))
    if name == "mamba.D":
        out[...] = 1.0
        return
    if name == "mamba.A_log":
        out[...] = np.log(rng.uniform(1.0, 16.0, shape))
        return
    if name == "mamba.dt_bias":
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
        out[...] = dt + np.log(-np.expm1(-dt))      # softplus^-1
        return
    if is_scale(name):
        table = 1.0 + SCALE_STD * _quantiles()
    elif name == "mamba.conv1d.weight":
        table = cfg["mamba_d_conv"] ** -0.5 * _quantiles()
    elif name == "mamba.conv1d.bias":
        table = CONV_BIAS_STD * _quantiles()
    else:
        table = cfg["initializer_range"] * _quantiles()
    table = table.astype(out.dtype)
    cols = int(np.prod(shape[1:], dtype=np.int64))
    step = max(1, FILL_ROWS_ELEMENTS // cols)
    if (step * cols) % 2 and shape[0] > step:
        step += 1
    for r in range(0, shape[0], step):
        idx = rng.integers(0, LEVELS, size=(min(step, shape[0] - r),)
                           + shape[1:], dtype=np.uint16)
        out[r:r + step] = table[idx]


def host_leaf(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              dtype) -> np.ndarray:
    out = np.empty(leaf_shape(cfg, name),
                   np.float32 if keeps_float32(name) else dtype)
    host_fill(seed, cfg, name, layer, out)
    return out
