"""Routed experts, dropless, for the share of an expert-parallel layer
that this program holds.

The router scores a token against all ``n_routed_experts`` in float32
(sigmoid), chooses the ``expert_top_k`` of largest score plus bias (the
bias enters the choice only), and weighs the chosen by their scores,
normalised to one and scaled by ``routed_scaling_factor``. Of those
experts this program holds ``held_experts`` = (first, count): it
computes what its own experts give for the tokens routed to them and
leaves out what the others would add; on one chip the layer runs
without its exchange, and nothing stands in for the absent chips.
``n_shared_experts`` run for every token (0: none).

A second router form, ``cfg.router == "softmax"``: float32 logits with
no bias, the ``expert_top_k`` largest chosen by logit, their weights
the softmax over the chosen (the softmax over all, renormalised over
the chosen, is the same numbers). With ``cfg.early_router`` the router
reads what ``Block`` hands it, the residual stream as it entered the
layer, and the experts the FFN's normalised input as ever.
``cfg.expert_act`` gates an expert ("silu", or "relu": ReGLU).

Dropless: no capacity, no token's result depends on what else is in
the batch. The (token, choice) pairs routed here are sorted by expert
and go through one grouped product a matrix (``jax.lax.ragged_dot``:
each row against its own expert's matrix, experts nobody chose never
read); pairs routed elsewhere sort behind every group and are not
computed. The experts' matrices are handed in whole, every expert
layer's in one stack (``TransformerLM`` owns them, outside the layer
scan), with this layer's index: the grouped product takes the stack
as ``layers x held`` groups of which only this layer's are not empty.
Sliced out by the scan instead, a layer's matrices would be copied
every step, since a kernel's operand has to lie in memory as it is
(1.1 GB a layer at GLM-5's widths: AOT for the v5e, PR 36).

Scopes, under the module's ``moe``: ``route``, ``dispatch``,
``experts``, ``shared``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import DenseFFN, TransformerConfig

# What a call counts beside its result, int32 [4], in this order
# (the engine's kfx_lm_moe_* counters): (token, choice) pairs routed,
# those routed to experts held here, dispatches (one a layer), the
# rows of the fullest held expert, and the held experts that got rows
# (whose matrices a dispatch reads).
COUNTS = ("assignments", "assignments_held", "dispatches", "max_rows",
          "experts_hit")


def route(cfg: TransformerConfig, x, gate, bias=None):
    """(chosen experts [T, K], their weights [T, K] float32) of what
    the router reads, x [T, D]; ``bias`` is the sigmoid form's."""
    with jax.default_matmul_precision("highest"):
        logits = x.astype(jnp.float32) @ gate
    if cfg.router == "softmax":
        best, chosen = jax.lax.top_k(logits, cfg.expert_top_k)
        return chosen, jax.nn.softmax(best, -1)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, cfg.expert_top_k)
    weights = jnp.take_along_axis(scores, chosen, -1)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights * cfg.routed_scaling_factor


class RoutedExperts(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, valid, wi, wo, layer=0, routed_from=None):
        """x [B, S, D]; valid [B, S] marks real tokens (pads are routed
        nowhere and counted nowhere); wi [layers, held, D, 2F] and wo
        [layers, held, F, D] the held experts of every expert layer,
        ``layer`` this one's index; ``routed_from`` [B, S, D] what the
        router reads where that is not x. Returns (y [B, S, D],
        counts)."""
        cfg = self.cfg
        B, S, D = x.shape
        T, K, F = B * S, cfg.expert_top_k, cfg.expert_d_ff
        first, count = cfg.held_experts
        gate = self.param("gate", nn.initializers.lecun_normal(),
                          (D, cfg.n_routed_experts), jnp.float32)
        bias = None
        if cfg.router == "sigmoid":
            bias = self.param("gate_bias", nn.initializers.zeros,
                              (cfg.n_routed_experts,), jnp.float32)
        act = nn.relu if cfg.expert_act == "relu" else nn.silu
        tokens = x.reshape(T, D)
        with jax.named_scope("route"):
            chosen, weights = route(
                cfg, tokens if routed_from is None
                else routed_from.reshape(T, D), gate, bias)
        with jax.named_scope("dispatch"):
            here = ((chosen >= first) & (chosen < first + count)
                    & valid.reshape(T, 1))
            # Pairs routed elsewhere get the group past the last one.
            expert = jnp.where(here, chosen - first, count).reshape(T * K)
            order = jnp.argsort(expert, stable=True)
            sizes = jnp.sum(expert[:, None] == jnp.arange(count), 0,
                            dtype=jnp.int32)
            rows = tokens[order // K]                      # [T*K, D]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((wi.shape[0] * count,), jnp.int32), sizes,
                (layer * count,))
        with jax.named_scope("experts"):
            gated, up = jnp.split(jax.lax.ragged_dot(
                rows, wi.reshape(-1, D, 2 * F).astype(cfg.dtype), groups),
                2, -1)
            out = jax.lax.ragged_dot(
                act(gated) * up,
                wo.reshape(-1, F, D).astype(cfg.dtype), groups)
            # Back to the pairs' order, weighed; a row behind the
            # groups holds nothing this program computed.
            w = jnp.where(here, weights, 0.0).reshape(T * K)[order]
            out = jnp.where((w != 0)[:, None],
                            out.astype(jnp.float32) * w[:, None], 0.0)
            # Unsorted (a gather by the inverse permutation), a token's
            # K pairs lie together again: summed without a scatter-add.
            back = jnp.zeros((T * K,), order.dtype).at[order].set(
                jnp.arange(T * K, dtype=order.dtype))
            y = out[back].reshape(T, K, D).sum(1)
        y = y.astype(cfg.dtype).reshape(B, S, D)
        if cfg.n_shared_experts:
            with jax.named_scope("shared"):
                y = y + DenseFFN(cfg, cfg.n_shared_experts * F,
                                 name="shared")(x)
        counts = jnp.stack([K * jnp.sum(valid, dtype=jnp.int32),
                            jnp.sum(here, dtype=jnp.int32),
                            jnp.int32(1), jnp.max(sizes),
                            jnp.sum(sizes > 0, dtype=jnp.int32)])
        return y, counts
