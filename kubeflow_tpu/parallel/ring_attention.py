"""Ring attention: exact causal attention with the sequence sharded over a
context-parallel mesh axis.

Long-context path (task: long sequences must be first-class). Each device
holds a sequence chunk of Q/K/V; K/V chunks rotate around the ring via
``ppermute`` while every device accumulates its queries' attention with an
online (flash-style) softmax — memory per device stays O(S/cp · S/cp) and
the K/V transfer overlaps with compute on real ICI. Matches dense causal
attention to numerical tolerance (tests/test_parallel.py).

Public forms:
  * ``ring_attention(q, k, v, axis_name)`` — call inside shard_map/manual
    axes, seq dim sharded over ``axis_name``;
  * ``make_ring_attention(mesh, axis_name)`` — shard_map-wrapped callable
    on global [B, S, H, D] arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos):
    """One Q-chunk × KV-chunk pass. Returns (numerator [B,Sq,H,D],
    row max [B,H,Sq], row sumexp [B,H,Sq]) for online-softmax merging.
    q is pre-scaled. Masking uses global positions for causality."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)  # [B,H,Sq,Sk]
    mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # [B,H,Sq]
    # Rows with every key masked: exp(NEG_INF - NEG_INF) would be 1; pin
    # the max to 0 so such rows contribute sumexp ~0 instead.
    m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - m[..., None])
    num = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    denom = jnp.sum(p, axis=-1)
    return num, m, denom


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str) -> jnp.ndarray:
    """Causal attention over a ring. q/k/v: [B, S_local, H, D] (local
    chunks; global seq = concat over the axis, chunk i = axis index i).
    q must already be scaled by 1/sqrt(d)."""
    cp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    q_pos = idx * S + jnp.arange(S)

    def body(step, carry):
        num, mx, den, kc, vc = carry
        src_block = (idx - step) % cp  # whose K/V we hold this step
        k_pos = src_block * S + jnp.arange(S)
        n_new, m_new, d_new = _block_attend(q32, kc, vc, q_pos, k_pos)
        # Online-softmax merge of (num, mx, den) with the new block.
        m_tot = jnp.maximum(mx, m_new)
        alpha = jnp.exp(mx - m_tot)  # [B,H,S]
        beta = jnp.exp(m_new - m_tot)
        alpha_t = alpha.transpose(0, 2, 1)[..., None]  # [B,S,H,1]
        beta_t = beta.transpose(0, 2, 1)[..., None]
        num = num * alpha_t + n_new * beta_t
        den = den * alpha + d_new * beta
        # Rotate K/V around the ring (next step uses the neighbour's chunk).
        perm = [(i, (i + 1) % cp) for i in range(cp)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return num, m_tot, den, kc, vc

    # Initial accumulators must be marked device-varying for shard_map's
    # VMA check (the loop makes them varying): over every manual axis the
    # inputs vary over (e.g. data/ctx/model when called from the model's
    # sharded attention), not just the ring axis.
    vma = tuple(jax.typeof(q).vma) or (axis_name,)
    vary = lambda x: jax.lax.pcast(x, vma, to="varying")
    num0 = vary(jnp.zeros((B, S, H, D), jnp.float32))
    m0 = vary(jnp.full((B, H, S), NEG_INF, jnp.float32))
    den0 = vary(jnp.zeros((B, H, S), jnp.float32))
    num, _, den, _, _ = jax.lax.fori_loop(
        0, cp, body, (num0, m0, den0, k32, v32))
    out = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str,
                        batch_axis: Optional[str] = None):
    """shard_map wrapper: global [B, S, H, D] in/out, S sharded over
    ``axis_name`` (and B over ``batch_axis`` if given)."""
    spec = P(batch_axis, axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)
