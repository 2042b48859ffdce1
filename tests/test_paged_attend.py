"""The paged decode cache of models/transformer.py. First, the two forms
of ``Attention._decode_attend`` agree: scoring the pool in place under
the page-membership mask, and gathering each row's logical view through
its block table. One constructed pool per trap the in-place form could
fall into; the gathered form (what every prefill still runs) is the
oracle. float32 to 1e-5, bfloat16 to one ulp at the size of the
output, and no further than the oracle from the float32 numbers: the
in-place form accumulates its scores in float32 and sums in the pool's
order, so it is not bitwise the gathered form. Second, the layer scan
carries the cache (every leaf a stack of layers, written in place):
logits and every leaf equal, bit for bit in float32, a loop over layers
in Python that keeps one pool a layer in a list, and ``init_cache``
makes the tree the engine's programs are compiled for."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import transformer
from kubeflow_tpu.models.transformer import (Attention, Block, RMSNorm,
                                             TransformerConfig,
                                             TransformerLM,
                                             attends_pool_in_place,
                                             init_cache, score_bytes)

B, H, D, P, L, N = 4, 2, 16, 4, 16, 12       # B*L = 64 >= N*P = 48


def _score(dtype, window=1, kv_quant=""):
    """``score_bytes`` of this file's heads (what a cached position's
    scores cost a row, over its keys and values)."""
    return score_bytes(TransformerConfig(
        vocab_size=8, d_model=H * D, n_heads=H, head_dim=D, n_layers=1,
        d_ff=8, dtype=dtype, kv_page_size=P, kv_pages=N,
        kv_quant=kv_quant), window)


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
    # Every leaf is a stack of layers: the trap's pool is layer 1 of
    # two, under a layer 0 of other numbers that nothing may touch.
    stack = {name: np.stack([np.roll(leaf, 1, axis=0), leaf])
             for name, leaf in cache.items()}
    out, vars_ = jax.jit(lambda c, *a: _Attend(cfg).apply(
        {"cache": c}, *a, 1, mutable=["cache"]))(stack, *rest)
    for name, leaf in vars_["cache"].items():
        np.testing.assert_array_equal(np.asarray(leaf[0], np.float32),
                                      np.asarray(stack[name][0], np.float32))
    return (np.asarray(out, np.float32),
            {name: leaf[1] for name, leaf in vars_["cache"].items()})


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("trap", TRAPS)
def test_in_place_agrees_with_the_gathered_form(trap, dtype, monkeypatch):
    spec = TRAPS[trap]
    *args, active = _pool(spec, dtype)
    assert attends_pool_in_place(B, L, N, P, _score(
        dtype, spec.get("S", 1), spec.get("kv_quant", "")))
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


@pytest.mark.parametrize(
    "batch, max_seq_len, kv_pages, page, window, in_place", [
        (16, 1536, 288, 32, 1, True),    # the serving cell's decode chunk
        (1, 1536, 288, 32, 256, False),  # and its prefill programs
        (4, 64, 16, 16, 1, True),        # an engine's default pool: equality
        (1, 64, 4, 16, 1, True),         # one slot, one row's worth of pages
        (4, 64, 17, 16, 1, True),        # a pool somewhat over the view: a
                                         # gather moves a position three times
        (4, 64, 52, 16, 1, False),       # a pool over three times the view
    ], ids=["cell_decode", "cell_prefill", "default_pool", "one_row",
            "larger_pool", "oversized_pool"])
def test_the_form_is_read_off_the_shapes(batch, max_seq_len, kv_pages, page,
                                         window, in_place):
    """With the serving cell's heads (32 x 128 a token, bfloat16)."""
    score = score_bytes(TransformerConfig(
        vocab_size=8, d_model=4096, n_heads=32, head_dim=128, n_layers=1,
        d_ff=8), window)
    assert attends_pool_in_place(batch, max_seq_len, kv_pages, page,
                                 score) is in_place


# -- the carried cache against one pool a layer ------------------------------

LAYERS, VOCAB = 3, 64


def _lm(rows, kv_quant=""):
    """A float32 decode model over the pool of the traps above (12
    pages of 4, ``max_seq_len`` 16) and its parameters: ``rows`` = 4
    attends the pool in place, 1 gathers (a prefill program)."""
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=H * D, n_heads=H, head_dim=D,
        n_layers=LAYERS, d_ff=48, max_seq_len=L, dtype=jnp.float32,
        attn_impl="xla", decode=True, kv_page_size=P, kv_pages=N,
        kv_quant=kv_quant)
    assert attends_pool_in_place(
        rows, L, N, P, score_bytes(cfg, 1)) is (rows == B)
    params = TransformerLM(dataclasses.replace(cfg, decode=False)).init(
        jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


def _per_layer(cfg, params, pools, tokens, positions, tables, loc):
    """``TransformerLM``'s forward with no scan and no stack: the
    blocks run one after another in Python, block ``i`` on its own
    parameters and on ``pools[i]``, a cache of that one layer. Returns
    (logits, the pools after the call)."""
    one = dataclasses.replace(cfg, n_layers=1)
    x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype).apply(
        {"params": params["embed"]}, tokens)
    after = []
    for i, pool in enumerate(pools):
        mine = jax.tree_util.tree_map(lambda w: w[i], params["layers"])
        (x, _), vars_ = Block(one).apply(
            {"params": mine, "cache": pool}, x, positions, tables, loc,
            None, None, 0, mutable=["cache"])
        after.append(vars_["cache"])
    x = RMSNorm(cfg.dtype).apply({"params": params["ln_f"]}, x)
    logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype).apply(
        {"params": params["lm_head"]}, x)
    return logits.astype(jnp.float32), after


def _window(rows, first, width):
    """Positions (= write locations) of a ``width``-token window that
    starts at ``first[b]`` in row ``b`` (None = an inactive row)."""
    pos = np.full((rows, width), -1, np.int32)
    for b, at in enumerate(first):
        if at is not None:
            pos[b] = at + np.arange(width)
    return pos


def _tables(*rows):
    return np.array([r + [-1] * (L // P - len(r)) for r in rows], np.int32)


_FOUR = _tables([5, 0, 9], [3], [7, 8], [])
# Each case: the calls made one after another on one cache, as
# (block tables, positions of the window); tokens are drawn per call.
CARRIED = {
    # four prompts of 5, 2 and 6 tokens and an idle row, then three
    # single-token steps of the three live rows
    "decode_3_steps": dict(calls=[
        (_FOUR, _window(4, [0, 0, 0, None], 6)),
        (_FOUR, _window(4, [6, 6, 6, None], 1)),
        (_FOUR, _window(4, [7, 7, 7, None], 1)),
        (_FOUR, _window(4, [8, 8, 8, None], 1))]),
    # a speculative verify window: the pending token and 3 proposals
    "verify_window_4": dict(calls=[
        (_FOUR, _window(4, [0, 0, 0, None], 5)),
        (_FOUR, _window(4, [5, 5, 5, None], 4))]),
    # a one-row prefill, then another whose first two blocks are the
    # first one's pages (a prefix-cache hit: its tail starts at 8)
    "prefill_prefix_hit": dict(calls=[
        (_tables([2, 6, 7]), _window(1, [0], 10)),
        (_tables([2, 6, 10, 11]), _window(1, [8], 6))]),
    "int8_kv": dict(kv_quant="int8", calls=[
        (_FOUR, _window(4, [0, 0, 0, None], 6)),
        (_FOUR, _window(4, [6, 6, 6, None], 1)),
        (_tables([5, 0, 9]), _window(1, [7], 3))]),
}


@pytest.mark.parametrize("case", CARRIED)
def test_carried_cache_equals_one_pool_a_layer(case):
    spec = CARRIED[case]
    rng = np.random.default_rng(11)
    cfg, params = _lm(B, spec.get("kv_quant", ""))
    model = TransformerLM(cfg)
    cache = init_cache(cfg)
    pools = [jax.tree_util.tree_map(lambda leaf: leaf[i:i + 1],
                                    cache["layers"]) for i in range(LAYERS)]
    carried = jax.jit(lambda c, *a: model.apply(
        {"params": params, "cache": c}, a[0], positions=a[1],
        block_tables=a[2], write_locations=a[1], mutable=["cache"]))
    plain = jax.jit(lambda pools, *a: _per_layer(
        cfg, params, pools, a[0], a[1], a[2], a[1]))
    for tables, pos in spec["calls"]:
        tokens = rng.integers(0, VOCAB, pos.shape).astype(np.int32)
        logits, vars_ = carried(cache, tokens, pos, tables)
        cache = vars_["cache"]
        want, pools = plain(pools, tokens, pos, tables)
        live = pos >= 0
        assert live.any() and np.isfinite(np.asarray(logits)[live]).all()
        np.testing.assert_array_equal(np.asarray(logits)[live],
                                      np.asarray(want)[live])
        leaves = cache["layers"]["attn"]
        assert set(leaves) == set(pools[0]["attn"])
        for name, stack in leaves.items():
            for i, pool in enumerate(pools):
                np.testing.assert_array_equal(
                    np.asarray(stack[i]), np.asarray(pool["attn"][name][0]),
                    err_msg=f"{name}, layer {i}")
    # every layer wrote, each its own numbers
    keys = np.asarray(cache["layers"]["attn"]["cached_key"], np.float32)
    assert all(np.abs(keys[i]).sum() > 0 for i in range(LAYERS))
    assert not np.array_equal(keys[0], keys[1])


@pytest.fixture(scope="module")
def spec_engine():
    """A tiny engine with a one-layer draft on a smaller pool."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=2,
                            head_dim=16, n_layers=3, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=2,
                       name="lm-carried", kv_page_size=16, kv_pages=6,
                       draft_layers=1, draft_kv_pages=4, kv_quant="int8")
    yield eng
    eng.close()


@pytest.mark.parametrize("draft, layers, pages", [(False, 3, 6),
                                                  (True, 1, 4)],
                         ids=["target", "draft"])
def test_init_cache_makes_the_tree_the_programs_take(spec_engine, draft,
                                                     layers, pages):
    """Paths, shapes and dtypes of ``init_cache``'s tree are the ones
    ``DecodeEngine._cache_specs`` compiles every program for, in the
    target's pool and the draft's; every position id starts at -1."""
    cfg = spec_engine.draft_cfg if draft else spec_engine.cfg
    made = init_cache(cfg)
    rows, heads = (layers, pages, 16), (2, 16)
    want = {"cached_key": (rows + heads, jnp.int8),
            "cached_value": (rows + heads, jnp.int8),
            "key_scale": (rows, jnp.float32),
            "value_scale": (rows, jnp.float32),
            "cached_pos": (rows, jnp.int32)}
    assert set(made) == {"layers"} and set(made["layers"]) == {"attn"}
    assert {name: (leaf.shape, leaf.dtype)
            for name, leaf in made["layers"]["attn"].items()} == want
    specs = spec_engine._cache_specs(draft)
    assert jax.tree_util.tree_structure(specs) \
        == jax.tree_util.tree_structure(made)
    assert jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), specs) \
        == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), made)
    for name, leaf in made["layers"]["attn"].items():
        assert (np.asarray(leaf) == (-1 if name == "cached_pos" else 0)).all()


def test_init_cache_of_the_dense_layout_is_per_row():
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=2,
                            head_dim=16, n_layers=2, d_ff=64,
                            max_seq_len=8, dtype=jnp.float32, decode=True)
    attn = init_cache(cfg, 3)["layers"]["attn"]
    assert {name: leaf.shape for name, leaf in attn.items()} == {
        "cached_key": (2, 3, 8, 2, 16), "cached_value": (2, 3, 8, 2, 16),
        "cached_pos": (2, 3, 8), "cache_index": (2, 3)}
    assert (np.asarray(attn["cached_pos"]) == -1).all()
    with pytest.raises(ValueError, match="per batch row"):
        init_cache(cfg)
    # ... and is not made by applying the model without one
    tokens = jnp.zeros((3, 1), jnp.int32)
    params = TransformerLM(dataclasses.replace(cfg, decode=False)).init(
        jax.random.PRNGKey(0), tokens)["params"]
    with pytest.raises(ValueError, match="init_cache"):
        TransformerLM(cfg).apply({"params": params}, tokens,
                                 positions=tokens, mutable=["cache"])


def test_a_build_reports_the_programs_temporary_bytes(spec_engine):
    """``kfx_lm_program_temp_bytes{model,program}`` is set from the
    compiled executable when a model program is built."""
    spec_engine._decode()
    spec_engine._prefill_for(8)
    gauge = spec_engine._reg().gauge("kfx_lm_program_temp_bytes", "")
    programs = {lab["program"]: value for lab, value in gauge.samples()
                if lab["model"] == "lm-carried"}
    assert {"decode_chunk", "prefill_8"} <= set(programs)
    assert all(value >= 0 for value in programs.values())
    assert programs["decode_chunk"] \
        == spec_engine._decode().memory_analysis().temp_size_in_bytes
