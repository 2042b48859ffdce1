"""Seeded random weights under the published (logical) leaf names.

The benchmark owns the weights: the program is handed them (an export
file, or an initial train state) and the plain reference makes the same
ones again from the seed, one layer at a time, without seeing anything
the program made. Matrices are normal with the published
``initializer_range`` (0.02); norm scales are 1 + 0.1 * normal, so a
dropped scale shows.

Two makers, one per way a cell can receive weights:

* ``host_leaf`` / ``host_fill``: numpy on the host, for a served model (the replica
  owns the chip and reads an export file). 16-bit uniform indices into
  a 65 536-level table of normal quantiles: about a nanosecond a
  parameter a core, and it releases the interpreter lock.
* ``device_leaf``: ``jax.random`` on the device the caller owns, for a
  trained model (the worker makes its whole state in one jitted call,
  each chip only its shard; threefry is partitionable, so the values do
  not depend on the sharding).
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, Iterator, Tuple

import numpy as np

MATRIX_STD = 0.02
SCALE_STD = 0.1
LEVELS = 1 << 16

LAYER_LEAVES = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
                "post_attention_layernorm", "gate_proj", "up_proj",
                "down_proj")
TOP_LEAVES = ("embed_tokens", "norm", "lm_head")


def leaf_shape(cfg: Dict[str, Any], name: str) -> Tuple[int, ...]:
    """Logical shape, matrices as [in, out]."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    return {
        "embed_tokens": (v, d), "norm": (d,), "lm_head": (d, v),
        "input_layernorm": (d,), "post_attention_layernorm": (d,),
        "q_proj": (d, d), "k_proj": (d, d), "v_proj": (d, d),
        "o_proj": (d, d), "gate_proj": (d, f), "up_proj": (d, f),
        "down_proj": (f, d),
    }[name]


def is_scale(name: str) -> bool:
    return name in ("norm", "input_layernorm", "post_attention_layernorm")


def leaves(cfg: Dict[str, Any]) -> Iterator[Tuple[str, int]]:
    """Every (name, layer) of the model; layer is -1 for the top."""
    for name in TOP_LEAVES:
        yield name, -1
    for layer in range(cfg["num_hidden_layers"]):
        for name in LAYER_LEAVES:
            yield name, layer


def _tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


@functools.lru_cache(maxsize=1)
def _quantiles() -> np.ndarray:
    """LEVELS standard-normal quantiles (a fixed sorted sample)."""
    return np.sort(np.random.default_rng(20240607).standard_normal(LEVELS)
                   ).astype(np.float32)


FILL_ROWS_ELEMENTS = 1 << 22   # indices drawn at a time (even)


def host_fill(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              out: np.ndarray) -> None:
    """Fill ``out`` (the leaf's logical shape, any strides, the dtype
    it is served in) in place, a block of rows at a time: no array of
    the leaf's size is made besides ``out`` itself."""
    shape = leaf_shape(cfg, name)
    if out.shape != shape:
        raise ValueError(f"{name}: out is {out.shape}, the leaf {shape}")
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), _tag(name), layer + 1]))
    if is_scale(name):
        table = (1.0 + SCALE_STD * _quantiles()).astype(out.dtype)
    else:
        table = (MATRIX_STD * _quantiles()).astype(out.dtype)
    cols = int(np.prod(shape[1:], dtype=np.int64))
    step = max(1, FILL_ROWS_ELEMENTS // cols)
    if (step * cols) % 2 and shape[0] > step:
        step += 1   # an even count a draw: uint16s come two to a word
    for r in range(0, shape[0], step):
        idx = rng.integers(0, LEVELS, size=(min(step, shape[0] - r),)
                           + shape[1:], dtype=np.uint16)
        out[r:r + step] = table[idx]


def host_leaf(seed: int, cfg: Dict[str, Any], name: str, layer: int,
              dtype) -> np.ndarray:
    out = np.empty(leaf_shape(cfg, name),
                   np.float32 if is_scale(name) else dtype)
    host_fill(seed, cfg, name, layer, out)
    return out


def device_key(seed: int):
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def device_leaf(key, cfg: Dict[str, Any], name: str, layer, dtype):
    """``layer`` may be traced (vmap over the layer index)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(key, _tag(name)), layer + 1)
    x = jax.random.normal(k, leaf_shape(cfg, name), jnp.float32)
    if is_scale(name):
        return 1.0 + SCALE_STD * x
    return (MATRIX_STD * x).astype(dtype)
