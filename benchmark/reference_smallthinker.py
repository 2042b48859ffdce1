"""The plain reference of ``smallthinker`` (SmallThinker-21BA3B-
Instruct, arXiv:2507.20984): ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
scan, no grouped product. It imports nothing of the program and is
handed nothing the program made: weights come from
``benchmark.weights_smallthinker`` by their published names, one layer
at a time.

One sequence at a time, x [S, D]; every norm RMSNorm with eps
``rms_norm_eps``; no bias anywhere. Layer l:

* The router reads the layer's INPUT, un-normed (``assumed`` in the
  configuration's file: "router placed before attention"): logits =
  x W_r in float32, S = the ``moe_num_active_primary_experts`` experts
  of largest logit, w = softmax over those (the softmax over all,
  renormalised over the chosen: ``moe_primary_router_apply_softmax``,
  ``norm_topk_prob``).
* a = norm(x); q, k, v = a W_q, a W_k, a W_v (``num_attention_heads``
  x ``head_dim``, ``num_key_value_heads`` x ``head_dim`` twice). Where
  ``rope_layout[l]`` is 1, q and k rotate (half-split pairs over
  ``head_dim``, base ``rope_theta``); where it is 0 there is no
  position term. Query i sees key j iff j <= i, and, where
  ``sliding_window_layout[l]`` is 1, i - ``sliding_window_size`` < j.
  Softmax in float32 of q k / sqrt(head_dim); a key/value head serves
  ``num_attention_heads / num_key_value_heads`` query heads in a row.
  h = x + [o] W_o.
* m = norm(h); y = sum over e in S of w_e (relu(m W_gate[e]) * (m
  W_up[e])) W_down[e]; x' = h + y. Every expert is computed for every
  token and masked (a loop over the experts).

Controls: ``full_window`` (the window layers see every earlier
position) and ``late_router`` (the router reads m, the FFN's normed
input). Queries go in blocks of ``QUERY_BLOCK`` so that a 13 000-token
request fits one chip beside a layer's float32 weights.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from . import weights_smallthinker as W

F32 = jnp.float32
QUERY_BLOCK = 512


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta: float):
    """x [S, H, D] rotated in half-split pairs; positions [S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * inv               # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def keys_values(p: Dict[str, Any], a, cfg: Dict[str, Any], rotate):
    """a [S, D] normed -> the keys (rotated where ``rotate``) and
    values of its positions, [S, key/value heads, head_dim] each: what
    a cache holds of them."""
    S = a.shape[0]
    G, D = cfg["num_key_value_heads"], cfg["head_dim"]
    k = (a @ p["self_attn.k_proj"]).reshape(S, G, D)
    v = (a @ p["self_attn.v_proj"]).reshape(S, G, D)
    pos = jnp.arange(S, dtype=jnp.int32)
    return jnp.where(rotate, rotary(k, pos, cfg["rope_theta"]), k), v


def attention(p: Dict[str, Any], a, cfg: Dict[str, Any], rotate, window):
    """a [S, D] normed -> [S, H * head_dim]; ``window`` 0 sees all.
    ``rotate`` and ``window`` may be traced (one compiled layer for
    both kinds: ``layer_step``)."""
    S = a.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    pos = jnp.arange(S, dtype=jnp.int32)
    q = (a @ p["self_attn.q_proj"]).reshape(S, H, D)
    q = jnp.where(rotate, rotary(q, pos, cfg["rope_theta"]), q)
    k, v = keys_values(p, a, cfg, rotate)
    k, v = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)
    T = min(S, QUERY_BLOCK)
    if S % T:
        raise ValueError(f"{S} tokens are no whole number of blocks of {T}")

    def mix(t0):
        rows = jax.lax.dynamic_slice_in_dim(q, t0, T, 0)
        at = t0 + jnp.arange(T)
        sees = (pos[None, :] <= at[:, None]) & (
            (window == 0) | (pos[None, :] > at[:, None] - window))
        scores = jnp.einsum("thd,shd->hts", rows, k) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(sees[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, v)

    return jax.lax.map(mix, jnp.arange(0, S, T)).reshape(S, H * D)


def route(p: Dict[str, Any], x, cfg: Dict[str, Any]):
    """(chosen experts [S, K], their weights [S, K])."""
    logits = x @ p[W.ROUTER]
    best, chosen = jax.lax.top_k(logits,
                                 cfg["moe_num_active_primary_experts"])
    return chosen, jax.nn.softmax(best, -1)


def experts(p: Dict[str, Any], m, chosen, w, cfg: Dict[str, Any]):
    """Every expert for every token, weighed by what the router gave it
    (0 where it was not chosen): a loop over the experts."""
    n = cfg["moe_num_primary_experts"]
    gate, up, down = (jnp.stack([p[W.expert_leaf(e, name)]
                                 for e in range(n)]) for name in W.MLP)

    def one(y, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)   # [S]
        out = (jax.nn.relu(m @ gate) * (m @ up)) @ down
        return y + weight[:, None] * out, None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (jnp.arange(n), gate, up, down))[0]


def decoder_layer(p: Dict[str, Any], x, cfg: Dict[str, Any], rotate,
                  window, late_router: bool = False):
    """One block; ``p`` holds the layer's published leaves as float32
    [in, out] matrices; x [S, D]; ``rotate`` and ``window`` are the
    layer's (``layer_kind``)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(p, rms_norm(x, p["input_layernorm"], eps), cfg,
                      rotate, window) @ p["self_attn.o_proj"]
    m = rms_norm(h, p["post_attention_layernorm"], eps)
    chosen, w = route(p, m if late_router else x, cfg)
    return h + experts(p, m, chosen, w, cfg)


def layer_kind(cfg: Dict[str, Any], layer: int, full_window: bool = False):
    """(rotate, window) of ``layer``: ``rope_layout`` and, where
    ``sliding_window_layout`` is 1, ``sliding_window_size`` (0: every
    earlier position; the control ``full_window`` makes it 0
    everywhere)."""
    window = cfg["sliding_window_size"] \
        if W.is_window_layer(cfg, layer) and not full_window else 0
    return W.rotates(cfg, layer), window


def layer_step(cfg: Dict[str, Any], full_window: bool = False,
               late_router: bool = False):
    """``step(layer, p, x)``: one block applied to x [S, D] with that
    layer's published leaves ``p`` (any dtype: widened to float32 here,
    on the device). One compiled block a length, kept between calls:
    the layer's kind goes in as two numbers."""
    wide = lambda p: {k: jnp.asarray(v).astype(F32) for k, v in p.items()}
    compiled = jax.jit(lambda p, x, rotate, window: decoder_layer(
        wide(p), x, cfg, rotate, window, late_router))

    def step(layer: int, p: Dict[str, Any], x):
        rotate, window = layer_kind(cfg, layer, full_window)
        with jax.default_matmul_precision("highest"):
            return compiled(p, x, jnp.bool_(rotate), jnp.int32(window))

    return step


def layer_kv(cfg: Dict[str, Any]):
    """``kv(layer, p, x)``: the keys and values [S, key/value heads x
    head_dim] that ``layer`` caches of its input x [S, D], float32."""
    flat = lambda t: t.reshape(t.shape[0], -1)
    compiled = jax.jit(lambda p, x, rotate: tuple(map(flat, keys_values(
        p, rms_norm(x, p["input_layernorm"].astype(F32),
                    cfg["rms_norm_eps"]), cfg, rotate))))

    def kv(layer: int, p: Dict[str, Any], x):
        names = ("input_layernorm", "self_attn.k_proj", "self_attn.v_proj")
        with jax.default_matmul_precision("highest"):
            return compiled({n: jnp.asarray(p[n]).astype(F32)
                             for n in names}, x,
                            jnp.bool_(W.rotates(cfg, layer)))

    return kv


def forward(cfg: Dict[str, Any], **controls):
    """``hidden_states(weights, tokens)`` of this configuration: the
    final-norm hidden states [S, D] of ``tokens`` [S], pulling one
    layer's weights at a time through ``weights(name, layer)``."""
    step = layer_step(cfg, **controls)

    def hidden_states(weights: Callable[[str, int], Any], tokens):
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(weights("embed_tokens", -1)).astype(F32)[tokens]
            for i in range(cfg["num_hidden_layers"]):
                x = step(i, {n: weights(n, i)
                             for n in W.layer_leaves(cfg, i)}, x)
            return rms_norm(x, jnp.asarray(weights("norm", -1)).astype(F32),
                            cfg["rms_norm_eps"])

    return hidden_states
