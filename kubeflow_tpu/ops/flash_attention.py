"""Causal flash attention as a pallas TPU kernel, with a flash backward.

Design (for the scales this framework trains at: S <= a few thousand,
D in {64, 128}):

* K/V for one (batch, head) fit comfortably in VMEM (S x D bf16 at
  S=2048, D=128 is 512 KB), so the kernels block over the QUERY axis
  only and keep whole K/V rows resident — no K-block pipelining needed,
  the MXU stays fed from VMEM.
* Forward: grid (B, H, S/BQ); online softmax over K blocks in fp32
  accumulators; the O(S^2) score matrix never touches HBM (the XLA
  fallback materialises it). The log-sum-exp per row is saved for the
  backward.
* Backward: the standard two-kernel flash backward — one grid over Q
  blocks producing dQ, one grid over K blocks producing dK/dV — each
  recomputing the probabilities from (Q, K, lse) instead of storing
  them. delta = rowsum(dO * O) is computed outside (a cheap fused
  elementwise-reduce XLA handles well).
* Causality skips whole K blocks above the diagonal (the fori_loop
  upper bound depends on the Q block index), so the work per Q block is
  triangular like the math.

Inputs are [B, S, H, D] (the model's layout); q is expected pre-scaled
(the model multiplies by 1/sqrt(D) already). Compute is fp32 regardless
of input dtype.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Run the kernels in the Pallas interpreter instead of compiling them.
# A constant of the program: only tests on a backend without Mosaic set
# it (monkeypatch) — on a TPU the kernels compile or the run fails.
INTERPRET = False


def _pick_block(s: int, want: int = 256) -> int:
    b = min(want, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the caller's varying-mesh-axes set, so
    the kernels also work inside shard_map (check_vma)."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                block_k: int, seq_len: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, D]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    n_kb = (qi * block_q + block_q + block_k - 1) // block_k

    def body(j, carry):
        acc, m, den = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BQ, BK]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        den = den * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, den

    acc0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    den0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, den = jax.lax.fori_loop(0, n_kb, body, (acc0, m0, den0))
    o_ref[0, 0] = (acc / den[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(den))[:, None]


def _fwd(q, k, v, *, block_q: int, block_k: int
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, H, S, D = q.shape
    grid = (B, H, S // block_q)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            _sds((B, H, S, D), q.dtype, q),
            _sds((B, H, S, 1), jnp.float32, q),
        ],
        interpret=INTERPRET,
        name="kfx_flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q: int, block_k: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]                      # [BQ]
    delta = delta_ref[0, 0, :, 0]                  # [BQ]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    n_kb = (qi * block_q + block_q + block_k - 1) // block_k

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])              # recomputed probs
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(0, n_kb, body, dq0)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q: int, block_k: int,
                seq_len: int):
    ki = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    n_qb = seq_len // block_q
    start_qb = (ki * block_k) // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])              # [BQ, BK]
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    dk, dv = jax.lax.fori_loop(start_qb, n_qb, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(block_q, block_k, res, do):
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)         # [B, H, S, 1]
    grid_q = (B, H, S // block_q)
    grid_k = (B, H, S // block_k)
    full = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0))
    full_v = pl.BlockSpec((1, 1, S, 1), lambda b, h, i: (b, h, 0, 0))
    qb = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))
    qv = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0))
    kb = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k),
        grid=grid_q,
        in_specs=[qb, full, full, qb, qv, qv],
        out_specs=qb,
        out_shape=_sds((B, H, S, D), q.dtype, q),
        interpret=INTERPRET,
        name="kfx_flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S),
        grid=grid_k,
        in_specs=[full, kb, kb, full, full_v, full_v],
        out_specs=[kb, kb],
        out_shape=[_sds((B, H, S, D), k.dtype, q),
                   _sds((B, H, S, D), v.dtype, q)],
        interpret=INTERPRET,
        name="kfx_flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------
#
# The op is split in two so activation-rematerialisation policies can SAVE
# the forward kernel's outputs instead of re-running it in the backward:
#
#   o, lse = flash_attention_fwd(q, k, v)      # raw kernel, no grad path
#   o   = checkpoint_name(o, "flash_o")        # (done by the model)
#   lse = checkpoint_name(lse, "flash_lse")
#   out = flash_attention_apply(q, k, v, o, lse)
#
# flash_attention_apply is numerically the identity on ``o`` but carries
# the custom VJP: its residuals are exactly its own INPUTS, so when a
# remat policy keeps (o, lse) — and (q, k, v) are cheap to recompute from
# saved projections — the backward pass runs ONLY the two flash backward
# kernels, never the forward one. With policies that don't save the names
# the behavior (and cost) is identical to the classic fused custom_vjp:
# the recompute re-runs the forward kernel to rebuild (o, lse).


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_apply(q, k, v, o, lse, block_q, block_k):
    return o


def _flash_apply_fwd(q, k, v, o, lse, block_q, block_k):
    return o, (q, k, v, o, lse)


def _flash_apply_bwd(block_q, block_k, res, do):
    dq, dk, dv = _bwd(block_q, block_k, res, do)
    _, _, _, o, lse = res
    # The (o, lse) inputs are precomputed constants of the differentiated
    # path (stop_gradient'd at the producer); their cotangents are dead.
    return dq, dk, dv, jnp.zeros_like(o), jnp.zeros_like(lse)


_flash_apply.defvjp(_flash_apply_fwd, _flash_apply_bwd)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        block_q: int = 256, block_k: int = 256
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Raw forward kernel: [B, S, H, D] -> (o [B, S, H, D],
    lse [B, S, H, 1] fp32). No gradient flows through this call — pair it
    with flash_attention_apply, which owns the backward."""
    B, S, H, D = q.shape
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    q, k, v = (jax.lax.stop_gradient(x).transpose(0, 2, 1, 3)
               for x in (q, k, v))                  # [B,H,S,D]
    o, lse = _fwd(q, k, v, block_q=bq, block_k=bk)
    return o.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1, 3)


def flash_attention_apply(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          o: jnp.ndarray, lse: jnp.ndarray, *,
                          block_q: int = 256, block_k: int = 256
                          ) -> jnp.ndarray:
    """Attention output given the precomputed (o, lse) of
    flash_attention_fwd. Numerically returns ``o``; gradients to q/k/v
    run the flash backward kernels against the given residuals."""
    B, S, H, D = q.shape
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    qt, kt, vt, ot = (x.transpose(0, 2, 1, 3) for x in (q, k, v, o))
    out = _flash_apply(qt, kt, vt, ot, lse.transpose(0, 2, 1, 3), bq, bk)
    return out.transpose(0, 2, 1, 3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    block_q: int = 256, block_k: int = 256) -> jnp.ndarray:
    """Causal attention, [B, S, H, D] in/out. q must be pre-scaled by
    1/sqrt(D) (matching models/transformer.py's convention)."""
    o, lse = flash_attention_fwd(q, k, v, block_q=block_q, block_k=block_k)
    return flash_attention_apply(q, k, v, o, lse, block_q=block_q,
                                 block_k=block_k)


def supported(seq_len: int, head_dim: int) -> bool:
    """Shapes the kernel handles well: lane-aligned head dim, sublane-
    divisible sequence."""
    return head_dim % 64 == 0 and seq_len % 128 == 0
