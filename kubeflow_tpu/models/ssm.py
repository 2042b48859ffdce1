"""The Mamba-2 mixer (state-space duality) of a "mamba" layer, in the
two forms a served model needs, which must agree.

``[z | xBC | dt] = x W_in``; ``xBC`` goes through a causal depthwise
convolution of ``ssm_conv`` taps and a silu and is split into the
heads' inputs ``x`` [H, P] and one ``B`` and ``C`` [N] a group; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head. A head's state
``S`` [P, N] follows ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``
and gives ``y_t = S_t C_t + D x_t``; then the gated norm
``RMSNorm(y * silu(z)) w`` over all heads and ``W_out``.

* ``prefill``: the chunked form over a run of tokens that starts from
  a given state: within a chunk of ``ssm_chunk`` tokens the
  decay-masked ``C B^T`` product, across chunks the carried state;
  plain XLA products, no kernel.
* ``step``: the recurrence itself, one token a row: what a decode step
  runs, and what moves its bytes: a row's float32 state is read and
  written once a token.

What a row carries from call to call is two leaves of the "cache"
collection, indexed by layer and by *slot*, not by page:
``state`` [layers, slots, H, P, N] (``ssm_state_dtype``, float32 as
served) and ``conv`` [layers, slots, (ssm_conv - 1) x conv width] (the
last inputs of the convolution, side by side: declared [..., 3, 4352]
the chip pads the 3 to a tile of 16 rows). ``slots`` [B] names each row's slot
(None: row i is slot i, the decode chunk's case, where the leaves are
read and written whole). A row whose first token is at position 0
starts from zeros whatever its slot held: a sequence's beginning *is*
the reset, so a slot needs no clearing when a request takes it, or
takes it again after a preemption. **Pad positions (< 0) are inert**:
their ``dt`` is 0 (decay 1, no input) and the window does not move;
they fill a bucket on the right, after the real tokens.

Scopes, under the module's ``ssm``: ``in_proj``, ``conv``, ``scan``
(prefill) or ``update`` (decode), ``gate_norm``, ``out_proj``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import TransformerConfig

# What a call counts beside its result, int32 [3], in this order (the
# engine's kfx_lm_ssm_* and kfx_lm_state_resets_total): rows whose
# state a one-token call advanced, real tokens of a longer call, rows
# that started from zeros.
COUNTS = ("row_updates", "prefill_tokens", "resets")
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def conv_width(cfg: TransformerConfig) -> int:
    return (cfg.ssm_heads * cfg.ssm_head_dim
            + 2 * cfg.ssm_groups * cfg.ssm_state)


def init_state(cfg: TransformerConfig, n: int, rows: int):
    """The slot-indexed leaves of a run of ``n`` mamba layers."""
    return {"state": jnp.zeros((n, rows, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state), cfg.ssm_state_dtype),
            # the window's taps side by side: whole 128-lane tiles
            "conv": jnp.zeros((n, rows, (cfg.ssm_conv - 1) * conv_width(cfg)),
                              cfg.dtype)}


def causal_conv(window, xbc, n_valid, kernel, bias):
    """Depthwise causal convolution of ``xbc`` [B, T, C] behind the
    ``window`` [B, K-1, C] of inputs before it; kernel [K, C], its last
    tap the current token's. Returns (silu(conv) [B, T, C], the window
    after the row's ``n_valid`` [B] real tokens)."""
    T = xbc.shape[1]
    full = jnp.concatenate([window.astype(xbc.dtype), xbc], 1)
    out = sum(full[:, j:j + T].astype(F32) * kernel[j].astype(F32)
              for j in range(kernel.shape[0])) + bias.astype(F32)
    after = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, window.shape[1], 0))(full, n_valid)
    return nn.silu(out).astype(xbc.dtype), after


def _heads(g, heads: int):
    """One B or C a group [..., G, N] -> a head [..., H, N]."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def step(x, dt, A, Bm, Cm, D, state):
    """One token a row. x [B, H, P]; dt [B, H] (0 for a pad); A, D [H];
    Bm, Cm [B, G, N]; state [B, H, P, N]. Returns (y [B, H, P] float32,
    the state after, float32)."""
    H = x.shape[1]
    x = x.astype(F32)
    Bh, Ch = _heads(Bm, H).astype(F32), _heads(Cm, H).astype(F32)
    decay = jnp.exp(dt * A)[..., None, None]
    state = decay * state.astype(F32) \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(state * Ch[:, :, None, :], -1) + D[:, None] * x
    return y, state


def prefill(x, dt, A, Bm, Cm, D, state, chunk: int):
    """A run of T tokens a row, from ``state``. x [B, T, H, P]; dt
    [B, T, H] (0 for a pad); Bm, Cm [B, T, G, N]; state [B, H, P, N].
    Returns (y [B, T, H, P] float32, the state after, float32). The
    products whose operands are activations take them as they come
    (the model's dtype, float32 sums); the decays and both products
    with the state are float32."""
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:   # inert tokens behind: dt 0
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    chunks = lambda a: jnp.moveaxis(
        a.reshape((B, -1, Q) + a.shape[2:]), 1, 0)
    later = jnp.tril(jnp.ones((Q, Q), bool))

    def one(state, c):
        x, dt, Bm, Cm = c
        cum = jnp.cumsum(dt * A, 1)                         # [B, Q, H]
        # decay from after token j to after token i >= j, a head
        seg = cum[:, :, None, :] - cum[:, None, :, :]       # [B, i, j, H]
        seg = jnp.exp(jnp.where(later[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", Cm, Bm,
                        preferred_element_type=F32)
        mix = seg * jnp.repeat(cb, H // G, axis=-1) * dt[:, None]
        y = jnp.einsum("bijh,bjhp->bihp", mix.astype(x.dtype), x,
                       preferred_element_type=F32)
        # what the state before the chunk still gives each token
        Ch, Bh = _heads(Cm, H).astype(F32), _heads(Bm, H).astype(F32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bihn,bhpn->bihp", Ch, state, precision=HIGHEST)
        left = jnp.exp(cum[:, -1:] - cum) * dt              # [B, Q, H]
        state = jnp.exp(cum[:, -1])[..., None, None] * state + jnp.einsum(
            "bjhp,bjhn->bhpn", left[..., None] * x.astype(F32), Bh,
            precision=HIGHEST)
        return state, y

    state = state.astype(F32)
    if T + pad == Q:
        state, y = one(state, (x, dt, Bm, Cm))
    else:
        state, y = jax.lax.scan(one, state, tuple(
            chunks(a) for a in (x, dt, Bm, Cm)))
        y = jnp.moveaxis(y, 0, 1).reshape(B, T + pad, H, P)
    return y[:, :T] + D[:, None] * x[:, :T].astype(F32), state


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=F32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1


class Mamba2(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, slots=None, layer=0):
        """x [B, S, D]; positions [B, S] (< 0: a pad); ``layer`` this
        layer's index in its run's leaves. Returns (y [B, S, D],
        counts int32 [3])."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
        inner, width = H * P, conv_width(cfg)
        dense = lambda name, feats: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        kernel = self.param("conv_kernel", nn.initializers.normal(
            cfg.ssm_conv ** -0.5), (cfg.ssm_conv, width), cfg.param_dtype)
        bias = self.param("conv_bias", nn.initializers.zeros, (width,),
                          cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), F32)
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,), F32))
        D = self.param("D", nn.initializers.ones, (H,), F32)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                           F32)
        valid = positions >= 0
        fresh = positions[:, 0] == 0

        if cfg.decode:
            # A carried collection cannot grow inside the scan.
            if not self.has_variable("cache", "state"):
                raise ValueError("decode needs the cache made by "
                                 "init_cache(): no ssm 'state' leaf")
            leaves = (self.variable("cache", "state"),
                      self.variable("cache", "conv"))
            at = (layer,) if slots is None else (layer, slots)
            state, window = (jnp.where(
                fresh.reshape((B,) + (1,) * (v.value.ndim - 2)), 0,
                v.value[at]) for v in leaves)
        else:
            rows = init_state(cfg, 1, B)
            state, window = rows["state"][0], rows["conv"][0]
        window = window.reshape(B, cfg.ssm_conv - 1, width)

        with jax.named_scope("in_proj"):
            # The published W_in_proj in two kernels, [z | xBC] and dt:
            # whole (2 x 4096 + 256 + 64 = 8512 columns at Granite's
            # widths) its rows are no whole number of 128-lane tiles,
            # and the chip's compiler copies the stacked kernels into
            # a padded layout at the top of every dispatch (1.25 GB:
            # AOT for the v5e, PR 40).
            z, xbc = jnp.split(dense("in_proj", inner + width)(x),
                               [inner], -1)
            dt = dense("dt_proj", H)(x)
        with jax.named_scope("conv"):
            xbc, window = causal_conv(window, xbc, valid.sum(1), kernel,
                                      bias)
        xs = xbc[..., :inner].reshape(B, S, H, P)
        Bm = xbc[..., inner:inner + G * N].reshape(B, S, G, N)
        Cm = xbc[..., inner + G * N:].reshape(B, S, G, N)
        dt = jnp.where(valid[..., None],
                       jax.nn.softplus(dt.astype(F32) + dt_bias), 0.0)
        if S == 1:
            with jax.named_scope("update"):
                y, state = step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                D, state)
                y = y[:, None]
        else:
            with jax.named_scope("scan"):
                y, state = prefill(xs, dt, A, Bm, Cm, D, state,
                                   cfg.ssm_chunk)
        if cfg.decode:
            for v, new in zip(leaves, (state, window.reshape(B, -1))):
                v.value = v.value.at[at].set(new.astype(v.value.dtype))
        with jax.named_scope("gate_norm"):
            # the gate first, then the norm, a group of heads at a time
            y = (y.reshape(B, S, inner) * nn.silu(z.astype(F32))
                 ).reshape(B, S, G, inner // G)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + cfg.norm_eps)
            y = y.reshape(B, S, inner) * scale
        with jax.named_scope("out_proj"):
            out = dense("out_proj", x.shape[-1])(y.astype(cfg.dtype))
        real = jnp.sum(valid, dtype=jnp.int32)
        zero = jnp.int32(0)
        counts = jnp.stack([real if S == 1 else zero,
                            zero if S == 1 else real,
                            jnp.sum(fresh, dtype=jnp.int32)])
        return out, counts
