"""The plain reference: the published decoder block in ``jax.numpy``.

Baichuan-7B and DeepSeek-LLM-7B publish the same block: pre-norm
RMSNorm (eps ``rms_norm_eps``), multi-head attention with as many KV
heads as query heads, rotary embeddings over the whole head (base
10 000, the half-split ``rotate_half`` form), SwiGLU, no bias, untied
``lm_head``. Everything here is float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
scan, no chunked prefill, no sharding rules. It imports nothing of the
program and is handed nothing the program made: weights come from
``benchmark.weights`` by their published names, one layer at a time.

Also here, because training is compared too: the loss, its gradients
(one ``jax.checkpoint`` per layer, rows in blocks) and AdamW with
global-norm clipping and linear warm-up, written out from their
published descriptions.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta: float):
    """x [B, S, H, D]; positions [B, S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * inv          # [B, S, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decoder_layer(p: Dict[str, Any], x, positions, cfg: Dict[str, Any]):
    """One block. ``p`` holds the layer's published leaves as float32
    [in, out] matrices; x [B, S, D]."""
    B, S, D = x.shape
    H = cfg["num_attention_heads"]
    hd = D // H
    eps, theta = cfg["rms_norm_eps"], cfg.get("rope_theta", 10000.0)
    h = rms_norm(x, p["input_layernorm"], eps)
    q = (h @ p["q_proj"]).reshape(B, S, H, hd)
    k = (h @ p["k_proj"]).reshape(B, S, H, hd)
    v = (h @ p["v_proj"]).reshape(B, S, H, hd)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    mix = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + mix.reshape(B, S, D) @ p["o_proj"]
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    gated = jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])
    return x + gated @ p["down_proj"]


def hidden_states(weights: Callable[[str, int], Any], cfg: Dict[str, Any],
                  tokens):
    """Final-norm hidden states [B, S, D] of ``tokens`` [B, S], pulling
    one layer's weights at a time through ``weights(name, layer)``
    (any dtype: they are widened to float32 here, on the device)."""
    from .weights import LAYER_LEAVES

    wide = lambda p: {k: jnp.asarray(v).astype(F32) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x = jax.jit(lambda e, t: e.astype(F32)[t])(
            weights("embed_tokens", -1), tokens)
        layer = jax.jit(
            lambda p, x, pos: decoder_layer(wide(p), x, pos, cfg))
        for i in range(cfg["num_hidden_layers"]):
            x = layer({n: weights(n, i) for n in LAYER_LEAVES}, x, positions)
        return jax.jit(lambda x, s: rms_norm(x, s.astype(F32),
                                             cfg["rms_norm_eps"]))(
            x, weights("norm", -1))


# -- training ----------------------------------------------------------------

def loss_fn(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            loss_chunk: int = 512):
    """Mean next-token cross-entropy of ``tokens`` [B, S+1]. ``params``
    is {"embed_tokens", "norm", "lm_head", "layers": [ {leaf: w} ]}."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed_tokens"][inputs]
    block = jax.checkpoint(
        lambda p, x: decoder_layer(p, x, positions, cfg))
    for p in params["layers"]:
        x = block(p, x)
    x = rms_norm(x, params["norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def chunk_ce(h, t):
        logp = jax.nn.log_softmax(h @ params["lm_head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[..., None], -1))

    total = 0.0
    for s in range(0, S, loss_chunk):
        total = total + chunk_ce(x[:, s:s + loss_chunk],
                                 targets[:, s:s + loss_chunk])
    return total / (B * S)


def learning_rate(step0: int, peak: float, warmup: int, total: int) -> float:
    """Linear warm-up from 0 over ``warmup`` updates, then cosine to 0
    at ``total``; ``step0`` counts updates already made."""
    if step0 < warmup:
        return peak * step0 / warmup
    frac = min(1.0, (step0 - warmup) / max(1, total - warmup))
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    factor = max_norm / jnp.maximum(norm, max_norm)
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def adamw_update(p, g, m, v, t: int, lr: float, hp: Dict[str, float]):
    """Update ``t`` (1-based) of one leaf; returns (p, m, v)."""
    b1, b2, eps, wd = hp["beta1"], hp["beta2"], hp["eps"], hp["weight_decay"]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    m_hat, v_hat = m / (1.0 - b1 ** t), v / (1.0 - b2 ** t)
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p), m, v


def flat_leaves(params: Dict[str, Any]) -> Dict[str, Any]:
    """{"name" or "name.layer": leaf} over a reference parameter tree."""
    out = {k: v for k, v in params.items() if k != "layers"}
    for i, layer in enumerate(params["layers"]):
        out.update({f"{k}.{i}": v for k, v in layer.items()})
    return out


def leaf_norms(tree: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
            for k, v in flat_leaves(tree).items()}
