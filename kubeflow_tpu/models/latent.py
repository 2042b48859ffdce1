"""Latent attention (MLA) with a learned sparse selection (DSA), served
from a paged cache.

What a token leaves in the cache is not keys and values a head: it is
one latent vector ``c`` (``kv_lora_rank`` numbers, normed) and one
rotary key ``k_r`` (``qk_rope_head_dim`` numbers, shared by all heads),
stored side by side in the leaf ``cached_latent`` (padded to whole
128-lane tiles: ``transformer.latent_entry_width``), and beside them the
indexer's key (``index_head_dim`` numbers, leaf ``cached_index_key``).
Head ``i``'s key at position ``s`` is ``[c_s W^UK_i ; k_r_s]`` and its
value ``c_s W^UV_i``; neither is ever built. The queries are carried
into the latent space instead (``q_i W^UK_i^T``, the absorbed form), the
scores are taken against ``c`` and ``k_r`` as cached, the mix of the
cached latents goes through ``W^UV`` once a query, not once a key.

With ``index_topk`` set, a query attends only the ``index_topk`` cached
positions its indexer scores highest (``sum_j w_j relu(q^I_j . k^I_s)``
over the indexer's heads), all of them while there are no more. The
scores are taken over the row's logical view, gathered page by page
through its block table; the selection is a mask, made by counting
(``_selected``: no sort), and the main attention runs over the same
view with everything but the selected masked out (why not over a
gather of the selected: ``_attend``). The view is as wide as the
longest row of the call needs, by a ``lax.switch`` over doubling
widths from ``index_topk`` to ``max_seq_len``: a call whose rows all
hold no more than ``index_topk`` tokens scores nothing and selects
nothing.

One form for every program: a prefill chunk (one row, many queries)
and a decode step (many rows, one query) run the same code; the token's
own entry is written before it attends, so it reads itself from the
cache. Served only: there is no cache-free forward pass here (the plain
reference of the benchmark has one).

Scopes, under the module's ``attn``: ``latent_write``, ``indexer``,
``select``, ``latent_attend``.
"""

from __future__ import annotations

import functools
from typing import List

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .transformer import (RMSNorm, TransformerConfig, latent_entry_width,
                          rope)

# Bytes of float32 scores made at a time (rows x queries x heads x
# view): the indexer's heads go in groups and a prefill chunk's queries
# in blocks that keep to it.
_SCORE_BLOCK_BYTES = 1 << 28


# Bits of a key one counting pass of the selection settles: three
# thresholds a pass. Timed on the chip (PR 37, a mask from float32
# scores): at [1024, 32768] a pass reads the 134 MB of keys at the
# memory's speed whatever it counts, and one bit a pass takes 10.0 ms,
# two 5.7, four (fifteen thresholds: the vector unit's time) 8.3; a
# decode step's [16, 32768] 64-282, 60 and 104 us (the sort: 35.5 ms
# and 373 us). Narrower views keep the order but for one bit a pass,
# the best by a half at [1024, 8192] and the worst in decode.
_PASS_BITS = 2


def _ordered(scores):
    """uint32 keys of the same order as float32 ``scores``: ``-0.0`` as
    ``+0.0``, ``-inf`` below every number."""
    sign = jnp.uint32(1 << 31)
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    bits = jnp.where(bits == sign, jnp.uint32(0), bits)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def _kth_largest(keys, k, bits: int):
    """The ``k``-th largest (``k`` an int or int32 [...], 1 <= k <= W)
    of the uint32 ``keys`` [..., W] along the last axis, all of them
    under ``2 ** bits``: the largest ``t`` that ``k`` keys reach.

    By counting, from the top bits down: a pass counts the keys at or
    above the three thresholds that extend the bits settled so far by
    two more, one read of ``keys``, and keeps the largest that ``k``
    keys still reach. ``bits / 2`` passes and no sort."""
    steps = -(-bits // _PASS_BITS)
    digits = jnp.arange(1, 1 << _PASS_BITS, dtype=jnp.uint32)
    k = jnp.asarray(k, jnp.int32)[..., None]

    def settle(state):
        shift, top = state
        by = shift.astype(jnp.uint32)
        thresholds = top[..., None] | (digits << by)           # [..., 3]
        reach = jnp.sum(keys[..., None, :] >= thresholds[..., None], -1,
                        dtype=jnp.int32)
        digit = jnp.sum(reach >= k, -1, dtype=jnp.uint32)
        return shift - _PASS_BITS, top | (digit << by)

    return jax.lax.while_loop(
        lambda state: state[0] >= 0, settle,
        (jnp.int32((steps - 1) * _PASS_BITS),
         jnp.zeros(keys.shape[:-1], jnp.uint32)))[1]


# A jit of its own, for the set-up's sake: a program holds the selection
# eight times (four view widths in each run of layers) and flax's scan
# traces a layer twice, so written out it added a quarter to the
# operations every pass over a program walks, and 1.5 s to the 3.6 s a
# program takes to lower on the chip's host, with 13 programs to a
# replica (my chip run, PR 37). As one call it is traced once a width
# and lowered to one function a width. (Bound as the module loads: a
# test that swaps ``jax.jit`` imports this module first.)
@functools.partial(jax.jit, static_argnums=1)
def _selected(scores, K: int):
    """The ``K`` best of float32 ``scores`` [..., W] (``0 < K < W``)
    along the last axis, as a mask: what a stable ``top_k`` takes, bit
    for bit. Above the K-th score, and of those that tie with it the
    earliest. Only the K-th score is wanted, so it is counted out, not
    sorted out; the last tie taken the same way, by how far from the
    view's end the ties lie."""
    W = scores.shape[-1]
    keys = _ordered(scores)
    kth = _kth_largest(keys, K, 32)[..., None]
    above = keys > kth
    left = K - jnp.sum(above, -1, dtype=jnp.int32)              # >= 1
    back = jnp.where(keys == kth,
                     jnp.uint32(W) - jnp.arange(W, dtype=jnp.uint32), 0)
    last_tie = _kth_largest(back, left, W.bit_length())[..., None]
    return above | (back >= last_tie)


def rope_head(x, positions, dim: int, base: float):
    """Rotary embedding of the first ``dim`` numbers of the last axis;
    the rest pass. x [B, S, H, D]."""
    if dim == x.shape[-1]:
        return rope(x, positions, base)
    return jnp.concatenate(
        [rope(x[..., :dim], positions, base), x[..., dim:]], -1)


def view_widths(cfg: TransformerConfig) -> List[int]:
    """The widths of a row's logical view a call can take: doubling
    from ``index_topk`` (whole pages) up to ``max_seq_len``. Without a
    selection there is one, the whole row."""
    L, P = cfg.max_seq_len, cfg.kv_page_size
    if cfg.index_topk < 1:
        return [L]
    out, w = [], -(-cfg.index_topk // P) * P
    while w < L:
        out.append(w)
        w *= 2
    return out + [L]


# What a call of a layer with a selection counts beside its result,
# int32 [2], in this order (the engine's kfx_lm_sparse_* counters),
# summed over the call's real query tokens: the cached positions a
# token could attend (those up to its own), and the locations the main
# attention scored and mixed for it (the width of the view it took).
COUNTS = ("cached_positions", "attended_positions")


class LatentAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, block_tables=None,
                 write_locations=None, layer=0):
        cfg = self.cfg
        if not cfg.decode or cfg.kv_page_size < 1:
            raise NotImplementedError(
                "latent attention is served from the paged cache only "
                "(decode=True, kv_page_size > 0): there is no training "
                "or cache-free forward pass")
        if block_tables is None:
            raise ValueError("paged decode requires block_tables")
        B, S, _ = x.shape
        H, C, R = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        P, N = cfg.kv_page_size, cfg.kv_pages
        dense = lambda name, feats: nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        norm = lambda name: RMSNorm(cfg.dtype, cfg.norm_eps, name=name)
        pos0 = jnp.maximum(positions, 0)

        cq = norm("q_norm")(dense("q_a", cfg.q_lora_rank)(x))
        q = dense("q_b", (H, nope + R))(cq)
        q_n = q[..., :nope]
        q_r = rope(q[..., nope:], pos0, cfg.rope_base)
        kv = dense("kv_a", C + R)(x)
        c = norm("kv_norm")(kv[..., :C])
        k_r = rope(kv[..., None, C:], pos0, cfg.rope_base)[:, :, 0]
        k_up = self.param("k_up", nn.initializers.lecun_normal(),
                          (C, H, nope), cfg.param_dtype)
        v_up = self.param("v_up", nn.initializers.lecun_normal(),
                          (C, H, vd), cfg.param_dtype)
        # Absorbed: the query's part of a score against the latent.
        q_c = jnp.einsum("bshn,chn->bshc", q_n, k_up.astype(cfg.dtype))
        scale = 1.0 / np.sqrt(nope + R)
        q_c, q_r = q_c * scale, q_r * scale

        def leaf(name):
            if not self.has_variable("cache", name):
                raise ValueError(
                    f"decode needs the cache made by init_cache(): no "
                    f"{name!r} in the 'cache' collection")
            return self.variable("cache", name)

        clat, cpos = leaf("cached_latent"), leaf("cached_pos")
        loc = positions if write_locations is None else write_locations
        ok = (positions >= 0) & (loc >= 0)
        blk = jnp.where(ok, loc // P, 0)
        page = jnp.take_along_axis(block_tables, blk, axis=1)
        # A pad, a negative location or a block not yet allocated ->
        # an out-of-range page, and mode="drop" discards the update.
        page = jnp.where(ok & (page >= 0), page, N)
        slot = jnp.where(ok, loc % P, 0)

        def write(var, rows):
            var.value = var.value.at[layer, page, slot].set(
                rows, mode="drop")

        sparse = cfg.index_topk > 0
        pool = {}
        with jax.named_scope("latent_write"):
            entry = jnp.concatenate([c, k_r], -1)
            entry = jnp.pad(entry, ((0, 0), (0, 0), (
                0, latent_entry_width(cfg) - entry.shape[-1])))
            if cfg.kv_quant == "int8":
                # One float32 scale a cached token beside the pages,
                # from the token's own entry (max |x| / 127), as the
                # K/V pools do it (transformer.Attention).
                csc = leaf("latent_scale")
                wide = entry.astype(jnp.float32)
                s8 = jnp.max(jnp.abs(wide), -1) / 127.0
                write(clat, jnp.clip(jnp.round(
                    wide / jnp.maximum(s8, 1e-30)[..., None]),
                    -127, 127).astype(jnp.int8))
                write(csc, s8)
                pool["scale"] = csc.value
            else:
                write(clat, entry.astype(cfg.dtype))
            if sparse:
                Hi, Di = cfg.index_n_heads, cfg.index_head_dim
                cik = leaf("cached_index_key")
                i_k = norm("index_k_norm")(dense("index_k", Di)(x))
                i_k = rope_head(i_k[:, :, None], pos0, R,
                                cfg.rope_base)[:, :, 0]
                write(cik, i_k.astype(cfg.dtype))
            write(cpos, positions)
        pool.update(lat=clat.value, pos=cpos.value, layer=layer)
        query = {"c": q_c, "r": q_r, "positions": positions}
        if sparse:
            pool["index_key"] = cik.value
            query["index"] = rope_head(dense("index_q", (Hi, Di))(cq),
                                       pos0, R, cfg.rope_base)
            query["index_w"] = dense("index_w", Hi)(x).astype(jnp.float32)
        widths = view_widths(cfg)
        # The view has to hold the last location any row of this call
        # has written (its own tokens included).
        need = jnp.max(jnp.where(ok, loc, -1)) + 1
        which = jnp.sum(jnp.asarray(widths[:-1], jnp.int32) < need)
        mix, seen = jax.lax.switch(
            which, [lambda *a, w=w: self._attend(w, *a) for w in widths],
            query, block_tables, pool)                   # [B, S, H, C]
        out = jnp.einsum("bshc,chv->bshv", mix, v_up.astype(cfg.dtype))
        out = nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              name="out")(out)
        return out, (seen if sparse else None)

    def _attend(self, W, query, block_tables, pool):
        """The mix of cached latents [B, S, H, C] over a logical view of
        ``W`` locations a row, gathered page by page through the row's
        block table. ``pool`` holds the stacked leaves (``lat`` [n, N,
        P, E], ``pos`` [n, N, P], ``index_key`` [n, N, P, Di], ``scale``
        [n, N, P] under int8) and this ``layer``'s index; ``query`` the
        scaled queries against latent and rotary key (``c``, ``r``),
        their ``positions``, and the indexer's ``index`` [B, S, Hi, Di]
        and ``index_w`` [B, S, Hi]. Beside the mix, COUNTS of this call
        and layer (int32 [2]).

        The selection enters the attention as a mask over the view, not
        as a gather of the selected entries: a page is 80 KB and comes
        at the memory's speed, while 2048 scattered rows a query come at
        some 60 ns a row (2.1 ms a layer for a decode step of 16 rows,
        41 ms for a prefill chunk of 1024 queries: chip run, PR 36),
        more than scoring the whole view costs."""
        cfg = self.cfg
        q_c, q_r, positions = query["c"], query["r"], query["positions"]
        B, S, H, C = q_c.shape
        P, N, K, R = (cfg.kv_page_size, cfg.kv_pages, cfg.index_topk,
                      cfg.qk_rope_head_dim)
        layer = pool["layer"]
        tables = block_tables[:, :W // P]
        pages = jnp.clip(tables, 0, N - 1)                    # [B, W/P]
        view = lambda leaf: leaf[layer, pages].reshape(
            B, W, *leaf.shape[3:])
        live = jnp.repeat(tables >= 0, P, axis=1)
        vpos = jnp.where(live, view(pool["pos"]), -1)         # [B, W]
        reads = ((vpos >= 0)[:, None, :]
                 & (vpos[:, None, :] <= positions[:, :, None]))  # [B,S,W]
        if 0 < K < W:
            with jax.named_scope("indexer"):
                i_q, i_w = query["index"], query["index_w"]
                keys = view(pool["index_key"])                 # [B, W, Di]
                Hi = i_q.shape[2]
                group = int(max(1, min(Hi, _SCORE_BLOCK_BYTES
                                       // (4 * B * S * W))))
                while Hi % group:
                    group -= 1

                def some_heads(total, j):
                    qs = jax.lax.dynamic_slice_in_dim(i_q, j * group,
                                                      group, 2)
                    ws = jax.lax.dynamic_slice_in_dim(i_w, j * group,
                                                      group, 2)
                    dots = jnp.einsum("bshd,bwd->bshw", qs, keys,
                                      preferred_element_type=jnp.float32)
                    return total + jnp.einsum("bshw,bsh->bsw",
                                              jax.nn.relu(dots), ws), None

                index, _ = jax.lax.scan(
                    some_heads, jnp.zeros((B, S, W), jnp.float32),
                    jnp.arange(Hi // group))
                index = jnp.where(reads, index, -jnp.inf)
            with jax.named_scope("select"):
                reads = reads & _selected(index, K)
        with jax.named_scope("latent_attend"):
            rows = view(pool["lat"])                           # [B, W, E]
            if "scale" in pool:
                rows = (rows.astype(jnp.float32)
                        * view(pool["scale"])[..., None]).astype(cfg.dtype)

            keys = rows[..., :C + R]

            def attend(q_c, q_r, reads):
                """Queries and heads as the rows of one matrix product
                a batch row: every head reads the same keys."""
                s = q_c.shape[1]
                q = jnp.concatenate([q_c, q_r], -1).reshape(B, s * H, C + R)
                scores = jnp.einsum("bmk,bwk->bmw", q, keys,
                                    preferred_element_type=jnp.float32)
                scores = jnp.where(
                    jnp.repeat(reads, H, axis=1), scores,
                    jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(scores, -1).astype(cfg.dtype)
                return jnp.einsum("bmw,bwc->bmc", probs,
                                  rows[..., :C]).reshape(B, s, H, C)

            # Queries in blocks that keep the float32 scores of one
            # (rows x queries x heads x view) to _SCORE_BLOCK_BYTES.
            block = 1
            while block < S and 2 * block * B * H * W * 4 \
                    <= _SCORE_BLOCK_BYTES:
                block *= 2
            # What attend() scores and mixes is the whole view, W
            # locations a query, whatever the mask leaves of it.
            real = positions >= 0
            seen = jnp.stack([
                jnp.sum(jnp.where(real, positions + 1, 0), dtype=jnp.int32),
                W * jnp.sum(real, dtype=jnp.int32)])
            if block >= S or S % block:
                return attend(q_c, q_r, reads), seen
            blocks = lambda a: jnp.moveaxis(
                a.reshape(B, S // block, block, *a.shape[2:]), 1, 0)
            mix = jax.lax.map(lambda a: attend(*a),
                              (blocks(q_c), blocks(q_r), blocks(reads)))
            return jnp.moveaxis(mix, 0, 1).reshape(B, S, H, C), seen
