"""The two forms of paged decode attention (models/transformer.py
``Attention._decode_attend``) agree: scoring the pool in place under
the page-membership mask, and gathering each row's logical view through
its block table. One constructed pool per trap the in-place form could
fall into; the gathered form (what every prefill still runs) is the
oracle. float32 to 1e-5, bfloat16 to one ulp at the size of the
output, and no further than the oracle from the float32 numbers: the
in-place form accumulates its scores in float32 and sums in the pool's
order, so it is not bitwise the gathered form."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import transformer
from kubeflow_tpu.models.transformer import (Attention, TransformerConfig,
                                             attends_pool_in_place)

B, H, D, P, L, N = 4, 2, 16, 4, 16, 12       # B*L = 64 >= N*P = 48


class _Attend(Attention):
    """``_decode_attend`` alone: it declares the cache variables, so it
    runs under ``nn.compact``."""

    @nn.compact
    def __call__(self, *args):
        return self._decode_attend(*args)


def _row(table, cached, q):
    """One batch row: its block table, the position id cached at each
    logical location so far (-1 = a pad or a gap), and the position of
    its first query token (None = an inactive slot). Queries are written
    at the locations after the cached ones."""
    return {"table": table + [-1] * (L // P - len(table)),
            "cached": cached, "q": q}


# Rows 0 and 1 of every pool; the trap adds to them.
_LONG = _row([5, 0, 9], list(range(10)), 10)      # last page: 2 of 4
_SHORT = _row([3], [0, 1], 2)

TRAPS = {
    # page 2 is block 0 of two rows (a prefix-cache hit)
    "shared_page": dict(rows=[_row([2, 4], list(range(6)), 6),
                              _row([2, 6, 7], list(range(9)), 9),
                              _LONG, _SHORT]),
    # tables end in -1; row 1's window (S=3) also runs off its last
    # allocated block, where writes are dropped
    "unallocated_blocks": dict(rows=[_LONG, _row([3], [0, 1, 2], 3),
                                     _row([7, 8], list(range(5)), 5),
                                     _SHORT]),
    # a bucketed prompt: pads at locations 2..4 carry position -1
    "pad_gap": dict(rows=[_row([1, 8], [0, 1, -1, -1, -1, 2, 3], 4),
                          _LONG, _SHORT,
                          _row([7, 6], [-1, -1, 0, 1, 2], 3)]),
    "partial_last_page": dict(rows=[_row([1, 8], list(range(5)), 5),
                                    _LONG, _SHORT,
                                    _row([7], [0, 1, 2], 3)]),
    # released pages keep their ids until they are recycled
    "stale_free_pages": dict(rows=[_LONG, _SHORT,
                                   _row([7, 8], list(range(5)), 5),
                                   _row([6], [0], 1)],
                             stale={1: [0, 1, 2, 3], 2: [4, 5, 6, 7],
                                    10: [0, 1, 0, 1], 11: [8, 9, 10, 11]}),
    # row 1 is at position 2 while row 0's pages hold live 0..9
    "foreign_live_pages": dict(rows=[_LONG, _SHORT,
                                     _row([7, 8, 1, 2], list(range(14)), 14),
                                     _row([6], [0], 1)]),
    # a retired slot: position -1, its table still names pages
    "inactive_row": dict(rows=[_LONG, _row([3, 4], list(range(6)), None),
                               _SHORT, _row([], [], None)]),
}
_ALL = dict(rows=[_row([2, 4], [0, 1, 2, 3, 4, -1, -1], 5),
                  _row([2, 6, 7], list(range(9)), 9),
                  _row([3, 8], list(range(6)), None),
                  _row([5], [0, 1, 2], 3)],
            stale={1: [0, 1, 2, 3], 10: [4, 5, 6, 7], 11: [0, 0, 0, 0]})
TRAPS["window_s3"] = dict(_ALL, S=3)
TRAPS["int8_kv"] = dict(_ALL, kv_quant="int8")
TRAPS["int8_kv_window_s3"] = dict(_ALL, S=3, kv_quant="int8")


def _pool(trap, dtype):
    """(cache, q, k, v, positions, tables, write_locations, active) of
    one trap: every page of the pool holds random K/V, so whatever a
    row must not see would move its output."""
    rng = np.random.default_rng(7)
    S, int8 = trap.get("S", 1), trap.get("kv_quant") == "int8"
    cpos = np.full((N, P), -1, np.int32)
    for page, ids in trap.get("stale", {}).items():
        cpos[page] = ids
    tables = np.array([r["table"] for r in trap["rows"]], np.int32)
    pos = np.full((B, S), -1, np.int32)
    loc = np.full((B, S), -1, np.int32)
    for b, r in enumerate(trap["rows"]):
        for at, p in enumerate(r["cached"]):
            cpos[r["table"][at // P], at % P] = p
        if r["q"] is not None:
            pos[b] = r["q"] + np.arange(S)
            loc[b] = len(r["cached"]) + np.arange(S)
    kv = lambda: rng.standard_normal((N, P, H, D), np.float32)
    if int8:
        cache = {"cached_key": np.round(kv() * 40).astype(np.int8),
                 "cached_value": np.round(kv() * 40).astype(np.int8),
                 "key_scale": rng.uniform(.01, .03, (N, P)).astype(
                     np.float32),
                 "value_scale": rng.uniform(.01, .03, (N, P)).astype(
                     np.float32)}
    else:
        cache = {"cached_key": jnp.asarray(kv(), dtype),
                 "cached_value": jnp.asarray(kv(), dtype)}
    cache["cached_pos"] = cpos
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D), np.float32),
                           dtype) for _ in range(3))
    return (cache, q / np.sqrt(D).astype(dtype), k, v, pos, tables, loc,
            pos[:, 0] >= 0)


def _attend(trap, dtype, args):
    cfg = TransformerConfig(
        vocab_size=8, d_model=H * D, n_heads=H, head_dim=D, n_layers=1,
        d_ff=8, max_seq_len=L, dtype=dtype, decode=True, kv_page_size=P,
        kv_pages=N, kv_quant=trap.get("kv_quant", ""))
    cache, *rest = args
    out, vars_ = jax.jit(lambda c, *a: _Attend(cfg).apply(
        {"cache": c}, *a, mutable=["cache"]))(cache, *rest)
    return np.asarray(out, np.float32), vars_["cache"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("trap", TRAPS)
def test_in_place_agrees_with_the_gathered_form(trap, dtype, monkeypatch):
    spec = TRAPS[trap]
    *args, active = _pool(spec, dtype)
    assert attends_pool_in_place(B, L, N, P)
    in_place, cache_a = _attend(spec, dtype, args)
    monkeypatch.setattr(transformer, "attends_pool_in_place",
                        lambda *shape: False)
    gathered, cache_b = _attend(spec, dtype, args)
    # The write is the same code: the pools leave both forms equal.
    for name, leaf in cache_a.items():
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(cache_b[name], np.float32))
    assert active.any() and np.isfinite(in_place).all()
    assert np.isfinite(gathered).all()
    a, b = in_place[active], gathered[active]
    if dtype == jnp.float32:
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
        return
    # bfloat16: within one unit in the last place (8 bits of mantissa)
    # at the size of the output's largest element (an element near 0
    # is a sum of terms that are not). And since the gathered form
    # rounds its scores to bfloat16 where this one keeps them in
    # float32, in place is on average no further than it from the numbers
    # computed in float32 out of the same bfloat16-valued pool.
    ulp = 2.0 ** (np.floor(np.log2(np.abs(b).max())) - 7)
    assert np.abs(a - b).max() <= ulp
    if spec.get("kv_quant"):
        return      # (dequantised values round to bfloat16 in both forms)
    exact, _ = _attend(spec, jnp.float32, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == dtype else x, args))
    assert np.abs(a - exact[active]).mean() \
        <= np.abs(b - exact[active]).mean() + 1e-6


@pytest.mark.parametrize("trap", ["stale_free_pages", "foreign_live_pages",
                                  "shared_page"])
def test_the_trap_is_one_without_membership(trap):
    """Position ids alone do not say whose a slot is: with every page
    in every row's table the same pool gives other numbers, so the
    parity above is the membership test's doing."""
    cache, q, k, v, pos, tables, loc, active = _pool(TRAPS[trap],
                                                     jnp.float32)
    with_member, cache = _attend(TRAPS[trap], jnp.float32,
                                 (cache, q, k, v, pos, tables, loc))
    every_page = np.broadcast_to(np.arange(N, dtype=np.int32), (B, N))
    without = Attention(TransformerConfig(dtype=jnp.float32)).apply(
        {}, q, pos, every_page, cache["cached_key"].reshape(N * P, H, D),
        cache["cached_value"].reshape(N * P, H, D), cache["cached_pos"],
        method=Attention._attend_pool)
    assert np.abs(with_member[active]
                  - np.asarray(without)[active]).max() > 1e-2


@pytest.mark.parametrize("batch, max_seq_len, kv_pages, page, in_place", [
    (16, 1536, 288, 32, True),      # the serving cell's decode chunk
    (1, 1536, 288, 32, False),      # and its prefill programs
    (4, 64, 16, 16, True),          # an engine's default pool: equality
    (1, 64, 4, 16, True),           # one slot, one row's worth of pages
    (4, 64, 17, 16, False),         # a pool larger than the logical view
], ids=["cell_decode", "cell_prefill", "default_pool", "one_row",
        "oversized_pool"])
def test_the_form_is_read_off_the_shapes(batch, max_seq_len, kv_pages, page,
                                         in_place):
    assert attends_pool_in_place(batch, max_seq_len, kv_pages,
                                 page) is in_place
