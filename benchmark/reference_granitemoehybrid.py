"""The plain reference of ``granitemoehybrid`` (Granite 4.0-H: Mamba-2
layers beside grouped-query attention layers, one shared SwiGLU a
layer): ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no batching of
requests into slots, no chunked scan. It imports nothing of the program
and is handed nothing the program made: weights come from
``benchmark.weights_granitemoehybrid`` by their published names, one
layer at a time.

x [B, S, D]; every norm RMSNorm with eps ``rms_norm_eps``; r =
``residual_multiplier``.

* Model: h0 = ``embedding_multiplier`` E[token]; the blocks; logits =
  norm(h) E^T / ``logits_scaling`` (the head is the embedding).
* Block, either kind: h = h + r mixer(norm(h)); h = h + r W_out(silu(g)
  * u), [g | u] = W_in norm(h) (``shared_mlp``; no routed experts).
* Attention mixer: ``num_attention_heads`` query heads, each group of
  them on one of ``num_key_value_heads`` key/value heads (repeated
  here), no bias, **no position term**, causal,
  softmax(q . k ``attention_multiplier``).
* Mamba-2 mixer: [z | xBC | dt] = W_in_proj x; xBC = silu(conv(xBC) +
  b), causal, depthwise, ``mamba_d_conv`` taps, the last on the current
  token; xBC = [x (heads x ``mamba_d_head``) | B | C (``mamba_d_state``
  each a group)]; dt = softplus(dt + dt_bias); A = -exp(A_log). A head:
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D x_t:
  **a ``lax.scan`` over the tokens**, the recurrence as written. Then
  RMSNorm(y * silu(z)) w, a group of heads at a time, and W_out_proj.

Departures from the published description (the ``transformers``
``granitemoehybrid`` / Bamba mixer as the writer knows it; the
configuration's ``assumed`` lists them): the gate is applied before the
norm; dt is not clamped (``time_step_limit`` is (0, inf)); B and C are
shared by the heads of a group in order (head i in group i // (heads /
groups)).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from . import weights_granitemoehybrid as W

F32 = jnp.float32


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def attention(p: Dict[str, Any], h, cfg: Dict[str, Any]):
    B, S, _ = h.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = W.sizes(cfg)["head_dim"]
    q = (h @ p["self_attn.q_proj"]).reshape(B, S, H, hd)
    k, v = (jnp.repeat((h @ p[f"self_attn.{n}_proj"]).reshape(B, S, KV, hd),
                       H // KV, axis=2) for n in "kv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    mix = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return mix.reshape(B, S, H * hd) @ p["self_attn.o_proj"]


def ssm_scan(x, dt, A, Bm, Cm, D, lengths):
    """The recurrence, token by token from an empty state. x [B, S, H,
    P]; dt [B, S, H]; A, D [H]; Bm, Cm [B, S, H, N]. A row's state
    stops at its ``lengths`` [B] tokens (what lies behind them is the
    batch's filling). Returns (y, the rows' states [B, H, P, N])."""
    def token(state, t):
        i, x, dt, Bm, Cm = t
        new = jnp.exp(dt * A)[..., None, None] * state \
            + (dt[..., None] * x)[..., None] * Bm[:, :, None, :]
        state = jnp.where((i < lengths)[:, None, None, None], new, state)
        return state, jnp.einsum("bhpn,bhn->bhp", state, Cm) + D[:, None] * x

    B, S, H, P = x.shape
    state, y = jax.lax.scan(
        token, jnp.zeros((B, H, P, Bm.shape[-1]), F32),
        (jnp.arange(S),) + tuple(jnp.moveaxis(a, 1, 0)
                                 for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state


def mamba(p: Dict[str, Any], h, cfg: Dict[str, Any], lengths):
    """Returns (the mixer's output, the rows' states after their
    ``lengths`` tokens)."""
    B, S, _ = h.shape
    s = W.sizes(cfg)
    H, P, N, G = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner, K = s["inner"], cfg["mamba_d_conv"]
    z, xbc, dt = jnp.split(h @ p["mamba.in_proj"],
                           [inner, inner + s["conv"]], -1)
    past = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    taps = p["mamba.conv1d.weight"]                      # [channels, K]
    xbc = jax.nn.silu(sum(past[:, j:j + S] * taps[:, j] for j in range(K))
                      + p["mamba.conv1d.bias"])
    x = xbc[..., :inner].reshape(B, S, H, P)
    Bm, Cm = (jnp.repeat(a.reshape(B, S, G, N), H // G, axis=2)
              for a in (xbc[..., inner:inner + G * N],
                        xbc[..., inner + G * N:]))
    dt = jax.nn.softplus(dt + p["mamba.dt_bias"])
    y, state = ssm_scan(x, dt, -jnp.exp(p["mamba.A_log"]), Bm, Cm,
                        p["mamba.D"], lengths)
    y = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(B, S, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return ((y.reshape(B, S, inner) * p["mamba.norm"]) @ p["mamba.out_proj"],
            state)


def decoder_layer(p: Dict[str, Any], x, cfg: Dict[str, Any], kind: str,
                  lengths):
    """One block; ``p`` holds the layer's published leaves as float32
    [in, out] matrices; x [B, S, D]. Returns (x, the rows' recurrent
    states after their ``lengths`` tokens; None for attention)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, p["input_layernorm"], eps)
    if kind == "mamba":
        mixed, state = mamba(p, h, cfg, lengths)
    else:
        mixed, state = attention(p, h, cfg), None
    x = x + r * mixed
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    gate, up = jnp.split(h @ p["shared_mlp.input_linear"], 2, -1)
    return (x + r * ((jax.nn.silu(gate) * up)
                     @ p["shared_mlp.output_linear"]), state)


def hidden_and_states(weights: Callable[[str, int], Any],
                      cfg: Dict[str, Any], tokens, lengths=None):
    """(Final-norm hidden states [B, S, D] of ``tokens`` [B, S], every
    Mamba layer's states [B, H, P, N] after the rows' ``lengths`` [B]
    tokens, all S where None), pulling one layer's weights at a time
    through ``weights(name, layer)`` (any dtype: widened to float32
    here, on the device). One compiled block a kind."""
    wide = lambda p: {k: jnp.asarray(v).astype(F32) for k, v in p.items()}
    layer = {kind: jax.jit(lambda p, x, n, kind=kind: decoder_layer(
        wide(p), x, cfg, kind, n)) for kind in set(cfg["layer_types"])}
    if lengths is None:
        lengths = [tokens.shape[1]] * tokens.shape[0]
    lengths, states = jnp.asarray(lengths, jnp.int32), []
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: cfg["embedding_multiplier"]
                    * e.astype(F32)[t])(weights("embed_tokens", -1), tokens)
        for i, kind in enumerate(cfg["layer_types"]):
            x, state = layer[kind]({n: weights(n, i)
                                    for n in W.layer_leaves(cfg, i)},
                                   x, lengths)
            if state is not None:
                states.append(state)
        return jax.jit(lambda x, s: rms_norm(x, s.astype(F32),
                                             cfg["rms_norm_eps"]))(
            x, weights("norm", -1)), states


def hidden_states(weights: Callable[[str, int], Any], cfg: Dict[str, Any],
                  tokens):
    """Final-norm hidden states [B, S, D] of ``tokens`` [B, S]."""
    return hidden_and_states(weights, cfg, tokens)[0]


def logits(hidden, embedding, cfg: Dict[str, Any]):
    """hidden [..., D] against the tied head, float32 ``highest``."""
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding.astype(F32).T / cfg["logits_scaling"]
