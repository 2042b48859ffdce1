"""The plain reference of ``glm_moe_dsa`` (GLM-5; the equations are
DeepSeek-V3's and DeepSeek-V3.2's): ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
scan, no absorbed form, no grouped product. It imports nothing of the
program and is handed nothing the program made: weights come from
``benchmark.weights_glm_moe_dsa`` by their published names, one layer
at a time.

One sequence at a time, x [S, D]; every norm RMSNorm with eps
``rms_norm_eps``; no bias anywhere.

* Attention (MLA). h = norm(x); c^Q = norm(h W^DQ); head i of c^Q W^UQ is
  [q^N_i ; q^R_i] (qk_nope_head_dim + qk_rope_head_dim), q^R rotated.
  [c^KV ; k^R] = h W^DKV; c^KV normed, k^R rotated, one rotary key for
  all heads. k_{s,i} = [c^KV_s W^UK_i ; k^R_s], v_{s,i} = c^KV_s W^UV_i
  (``kv_b_proj`` holds, a head, W^UK_i then W^UV_i). Rotary embedding in
  the half-split form over the qk_rope_head_dim numbers, base
  ``rope_parameters.rope_theta``. Scale 1/sqrt(qk_head_dim).
* Indexer (DSA). q^I_j = head j of c^Q W^IQ, k^I = norm(h W^IK), both
  rotated over their first qk_rope_head_dim numbers; w = h W^IW;
  I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) for s <= t. S_t =
  the ``index_topk`` positions s <= t of largest I[t, s] (ties: the
  earlier position), all of them while t < index_topk.
* o[t, i] = sum over s in S_t of softmax over S_t of (q[t, i] . k[s, i]
  scale) v[s, i]; x' = x + [o[t, 1..H]] W^O.
* FFN. h' = norm(x'). The first ``first_k_dense_replace`` layers: SwiGLU
  of width ``intermediate_size``. The others: s = sigmoid(h' W^G) in
  float32, the ``num_experts_per_tok`` experts of largest s + b chosen
  (b enters the choice only), g = ``routed_scaling_factor`` s / sum of
  the chosen s; y = SwiGLU_shared(h') + sum over the chosen e held here
  of g_e SwiGLU_e(h'). ``n_group`` = ``topk_group`` = 1: no group limit.
* The share: the router has its published width; of the experts only
  ``weights_glm_moe_dsa.held_experts(cfg)`` are here, and what the
  others would add is left out, as in the program.

Queries go in blocks of ``QUERY_BLOCK`` and heads in groups of
``HEAD_GROUP`` so that a 25 000-token request fits one chip; a block
sees every key (the mask of S_t is dense).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from . import weights_glm_moe_dsa as W

F32 = jnp.float32
QUERY_BLOCK = 1024
HEAD_GROUP = 8


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta: float, dim: int):
    """The first ``dim`` numbers of x [S, H, D] rotated; positions [S]."""
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * inv               # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dim:]], -1)


def swiglu(h, p, prefix: str):
    return (jax.nn.silu(h @ p[f"{prefix}gate_proj"])
            * (h @ p[f"{prefix}up_proj"])) @ p[f"{prefix}down_proj"]


def selection(index, k: int, recent: bool = False):
    """The mask [T, S] of the positions each query reads, from its
    causally masked indexer scores ``index`` [T, S] (-inf where s > t).
    ``recent`` is the control: the k most recent positions instead."""
    T, S = index.shape
    live = index > -jnp.inf
    if S <= k:
        return live
    if recent:
        index = jnp.where(live, jnp.arange(S, dtype=F32)[None], -jnp.inf)
    best, at = jax.lax.top_k(index, k)
    chosen = jnp.zeros((T, S), bool).at[
        jnp.arange(T)[:, None], at].set(best > -jnp.inf)
    return chosen & live


def attention(p: Dict[str, Any], h, cfg: Dict[str, Any],
              recent: bool = False, index_dtype=None):
    """h [S, D] normed -> [S, H * v_head_dim]. ``index_dtype`` is a
    diagnostic: the indexer's queries, keys and head weights rounded to
    that precision and nothing else, to show what a selection made in
    it does to the logits (PERF.md section 2)."""
    S = h.shape[0]
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_parameters"]["rope_theta"]
    pos = jnp.arange(S, dtype=jnp.int32)
    cq = rms_norm(h @ p["q_a_proj"], p["q_a_layernorm"], eps)
    kv = h @ p["kv_a_proj_with_mqa"]
    c = rms_norm(kv[:, :C], p["kv_a_layernorm"], eps)
    k_r = rotary(kv[:, None, C:], pos, theta, rd)             # [S, 1, rd]
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    i_q = rotary((cq @ p["indexer.wq_b"]).reshape(S, Hi, Di), pos, theta, rd)
    i_k = rotary(rms_norm(h @ p["indexer.wk"], p["indexer.k_norm"],
                          eps)[:, None], pos, theta, rd)[:, 0]
    i_w = h @ p["indexer.weights_proj"]                       # [S, Hi]
    if index_dtype is not None:
        i_q, i_k, i_w = (a.astype(index_dtype).astype(F32)
                         for a in (i_q, i_k, i_w))
    scale = 1.0 / math.sqrt(nope + rd)
    # Queries in blocks (a sequence longer than one block is a whole
    # number of them): first what each may read, then the heads in
    # groups, each group's keys and values made once.
    T = min(S, QUERY_BLOCK)
    if S % T:
        raise ValueError(f"{S} tokens are no whole number of blocks of {T}")
    blocks = jnp.arange(0, S, T)
    rows = lambda a, t0: jax.lax.dynamic_slice_in_dim(a, t0, T, 0)

    def may_read(t0):
        index = jnp.zeros((T, S), F32)
        for j in range(0, Hi, HEAD_GROUP):
            dots = jnp.einsum("thd,sd->ths",
                              rows(i_q, t0)[:, j:j + HEAD_GROUP], i_k)
            index = index + jnp.einsum("ths,th->ts", jax.nn.relu(dots),
                                       rows(i_w, t0)[:, j:j + HEAD_GROUP])
        causal = pos[None, :] <= rows(pos, t0)[:, None]
        return selection(jnp.where(causal, index, -jnp.inf),
                         cfg["index_topk"], recent)

    reads = jax.lax.map(may_read, blocks)                     # [S/T, T, S]
    q_b = p["q_b_proj"].reshape(-1, H, nope + rd)
    kv_b = p["kv_b_proj"].reshape(C, H, nope + vd)
    mixes = []
    for i in range(0, H, HEAD_GROUP):
        g = slice(i, i + HEAD_GROUP)
        q = jnp.einsum("sr,rhd->shd", cq, q_b[:, g])
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], pos, theta, rd)], -1)
        up = jnp.einsum("sc,chd->shd", c, kv_b[:, g])
        k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
            k_r, (S, up.shape[1], rd))], -1)
        v = up[..., nope:]

        def mix(args):
            t0, reads = args
            scores = jnp.einsum("thd,shd->hts", rows(q, t0), k) * scale
            probs = jax.nn.softmax(
                jnp.where(reads[None], scores, -jnp.inf), -1)
            return jnp.einsum("hts,shd->thd", probs, v)

        mixes.append(jax.lax.map(mix, (blocks, reads)).reshape(S, -1, vd))
    return jnp.concatenate(mixes, 1).reshape(S, H * vd)


def route(p: Dict[str, Any], h, cfg: Dict[str, Any]):
    """(chosen experts [S, K], their weights [S, K]) by number in the
    whole model."""
    scores = jax.nn.sigmoid(h @ p["mlp.gate"])
    _, chosen = jax.lax.top_k(scores + p["mlp.gate.bias"],
                              cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(scores, chosen, -1)
    return chosen, cfg["routed_scaling_factor"] * g / g.sum(-1, keepdims=True)


def routed_part(p: Dict[str, Any], h, cfg: Dict[str, Any]):
    """What the routed experts held here give, without the shared one."""
    chosen, g = route(p, h, cfg)
    y = jnp.zeros_like(h)
    for e in W.held_experts(cfg):
        weight = jnp.sum(jnp.where(chosen == e, g, 0.0), -1)   # [S]
        y = y + weight[:, None] * swiglu(h, p, f"mlp.experts.{e}.")
    return y


def decoder_layer(p: Dict[str, Any], x, cfg: Dict[str, Any], layer: int,
                  recent: bool = False, index_dtype=None):
    """One block; ``p`` holds the layer's published leaves as float32
    [in, out] matrices; x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["input_layernorm"], eps), cfg,
                      recent, index_dtype) @ p["o_proj"]
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    if not W.is_expert_layer(cfg, layer):
        return x + swiglu(h, p, "mlp.")
    return x + swiglu(h, p, "mlp.shared_experts.") + routed_part(p, h, cfg)


def layer_step(cfg: Dict[str, Any], recent: bool = False, index_dtype=None):
    """``step(layer, p, x)``: one block applied to x [S, D] with that
    layer's published leaves ``p`` (any dtype: widened to float32 here,
    on the device). One compiled block a kind and a length, kept
    between calls."""
    wide = lambda p: {k: jnp.asarray(v).astype(F32) for k, v in p.items()}
    compiled = {expert: jax.jit(lambda p, x, layer=layer: decoder_layer(
        wide(p), x, cfg, layer, recent, index_dtype)) for expert, layer in (
            (False, 0), (True, cfg["first_k_dense_replace"]))}

    def step(layer: int, p: Dict[str, Any], x):
        with jax.default_matmul_precision("highest"):
            return compiled[W.is_expert_layer(cfg, layer)](p, x)

    return step


def forward(cfg: Dict[str, Any], recent: bool = False, index_dtype=None):
    """``hidden_states(weights, tokens)`` of this configuration: the
    final-norm hidden states [S, D] of ``tokens`` [S], pulling one
    layer's weights at a time through ``weights(name, layer)``."""
    step = layer_step(cfg, recent, index_dtype)

    def hidden_states(weights: Callable[[str, int], Any], tokens):
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(weights("embed_tokens", -1)).astype(F32)[tokens]
            for i in range(cfg["num_hidden_layers"]):
                x = step(i, {n: weights(n, i)
                             for n in W.layer_leaves(cfg, i)}, x)
            return rms_norm(x, jnp.asarray(weights("norm", -1)).astype(F32),
                            cfg["rms_norm_eps"])

    return hidden_states
