"""Window layers with a page pool of their own beside full layers with
no position term, and softmax-routed ReGLU experts whose router reads
the layer's input (models/transformer.py, models/experts.py,
serving/engine.py), through the model and through ``DecodeEngine``,
against the benchmark's plain reference (benchmark/
reference_smallthinker.py) at a tiny size with every mechanism present:
two periods of (full, window x 3), window 16. float32, seeded weights;
logits, not tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_smallthinker as R
from benchmark import weights_smallthinker as W
from benchmark.tests import tiny_smallthinker as tiny
from kubeflow_tpu.models import experts
from kubeflow_tpu.models.transformer import (TransformerConfig,
                                             TransformerLM, attention_path,
                                             init_cache)

SEED = 5
WINDOW = tiny.TINY["sliding_window_size"]


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, 128, size=n)


# -- (1) the model in one shot ------------------------------------------------

def test_one_shot_forward_matches_the_reference_past_the_window():
    cfg = tiny.config()
    tcfg, params = tiny.program(cfg, SEED, attn_impl="naive")
    assert tcfg.layer_pattern == (("full", 1), ("window", 3)) * 2
    assert params["expert_wi"].shape == (8, 8, 64, 96)
    tokens = tokens_of(0, 70)           # four windows and a bit
    got = TransformerLM(tcfg).apply({"params": params},
                                    jnp.asarray(tokens)[None])[0]
    want = tiny.reference_logits(cfg, SEED, tokens)
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the window and the router's input are in those numbers
    for control in ("full_window", "late_router"):
        other = tiny.reference_logits(cfg, SEED, tokens, **{control: True})
        assert np.abs(other - want).max() > 0.01, control


def test_the_flash_kernels_refuse_a_window_layer_by_name():
    cfg = TransformerConfig(
        vocab_size=32, d_model=128, n_heads=2, head_dim=64, n_layers=2,
        d_ff=32, max_seq_len=2048, attn_impl="flash", window=512,
        layer_pattern=(("full", 1), ("window", 1)))
    assert attention_path(cfg, 1024) == "flash"
    with pytest.raises(ValueError, match="a 'window' layer"):
        attention_path(cfg, 1024, cfg.window)


# -- through the paged cache, both attention forms ----------------------------

def served_logits(tcfg, params, tokens, pieces, table, window_pages=None):
    """Logits [S, V] of ``tokens`` fed through the paged cache in
    ``pieces`` (chunk lengths; 1 = a decode step), and the last call's
    counts. The row's window table slides as the engine's does: before
    a piece it gets pages for the piece's positions from
    ``window_pages`` (recycled ones invalidated), after it the pages
    behind the next query's window go back."""
    model = TransformerLM(tcfg)
    apply = jax.jit(lambda p, c, t, pos, wt: model.apply(
        {"params": p, "cache": c}, t, positions=pos,
        block_tables=jnp.asarray(table), window_tables=wt,
        mutable=["cache", "counts"]))
    P, W = tcfg.kv_page_size, tcfg.window
    free = list(window_pages if window_pages is not None
                else range(tcfg.window_pages))
    wtable = np.full((1, table.shape[1]), -1, np.int32)
    cache, out, at = init_cache(tcfg), [], 0
    for n in pieces:
        for b in range(at // P, (at + n - 1) // P + 1):
            if wtable[0, b] < 0:
                page = wtable[0, b] = free.pop(0)
                for run in cache:
                    if run.startswith("window"):
                        pos = cache[run]["attn"]["cached_pos"]
                        cache[run]["attn"]["cached_pos"] = \
                            pos.at[:, page].set(-1)
        logits, vars_ = apply(
            params, cache, jnp.asarray(tokens[at:at + n])[None],
            jnp.arange(at, at + n, dtype=jnp.int32)[None],
            jnp.asarray(wtable))
        cache = jax.tree_util.tree_map(lambda x: x, vars_["cache"])
        out.append(np.asarray(logits[0]))
        at += n
        behind = max(0, at - W + 1) // P
        free += [int(p) for p in wtable[0, :behind] if p >= 0]
        wtable[0, :behind] = -1
    return np.concatenate(out, 0), vars_["counts"]


@pytest.mark.parametrize("window_pages, in_place", [(40, False), (7, True)])
def test_chunked_prefill_then_decode_match_the_reference_logits(
        window_pages, in_place):
    """The pages behind the window go back and are taken again by the
    same row; a decode step's view of a window layer is 3 blocks
    whatever the row holds, gathered, or (a pool of 7 pages) the pool
    scored in place under the same mask."""
    from kubeflow_tpu.models.transformer import (attends_pool_in_place,
                                                 score_bytes)

    cfg = tiny.config()
    tcfg, params = tiny.program(cfg, SEED, decode=True, kv_page_size=8,
                                kv_pages=40, window_pages=window_pages,
                                max_seq_len=128)
    assert attends_pool_in_place(1, 3 * 8, window_pages, 8,
                                 score_bytes(tcfg, 1)) == in_place
    tokens = tokens_of(1, 70)
    table = np.full((1, 16), -1, np.int32)
    table[0, :9] = np.random.default_rng(2).permutation(40)[:9]
    got, counts = served_logits(
        tcfg, params, tokens, (16, 16, 8) + (1,) * 30, table,
        np.random.default_rng(3).permutation(window_pages).tolist())
    want = tiny.reference_logits(cfg, SEED, tokens)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the last decode step: a query at position 69 in 6 window layers,
    # which scored its view of 3 blocks, or the pool
    assert np.asarray(counts["window"][0]).tolist() == [
        [70, WINDOW, 8 * (window_pages if in_place else 3)]] * 6
    moe = dict(zip(experts.COUNTS, np.asarray(counts["moe"][0])))
    assert moe["assignments"] == moe["assignments_held"] == 8 * 3
    assert moe["dispatches"] == 8 and 3 <= moe["experts_hit"] <= 8 * 3


# -- (6) the router -----------------------------------------------------------

def test_softmax_over_the_chosen_is_softmax_over_all_renormalised():
    tcfg = tiny.program(tiny.config(), SEED)[0]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    chosen, weights = experts.route(tcfg, x, gate)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ gate, -1)
    best, at = jax.lax.top_k(probs, 3)
    assert (np.asarray(chosen) == np.asarray(at)).all()
    np.testing.assert_allclose(weights, best / best.sum(-1, keepdims=True),
                               atol=1e-6)
    assert chosen.shape == (40, 3)


def test_the_program_routes_from_the_layers_input_and_not_from_ln2():
    """A program told to route late computes the reference's control,
    and told nothing the reference."""
    cfg = tiny.config()
    tokens = tokens_of(5, 40)
    early = tiny.reference_logits(cfg, SEED, tokens)
    late = tiny.reference_logits(cfg, SEED, tokens, late_router=True)
    tcfg, params = tiny.program(cfg, SEED, attn_impl="naive")
    assert tcfg.early_router
    for flag, want, other in ((True, early, late), (False, late, early)):
        got = TransformerLM(dataclasses.replace(
            tcfg, early_router=flag)).apply(
                {"params": params}, jnp.asarray(tokens)[None])[0]
        np.testing.assert_allclose(got, want, atol=2e-6)
        assert np.abs(np.asarray(got) - other).max() > 0.01


# -- (7) the shares of an expert-parallel layer ------------------------------

def test_the_shares_of_the_softmax_routed_experts_add_up_to_the_layer():
    """Four shares of 2 of the 8 experts give what the reference gives
    for the whole layer (no shared expert to count once)."""
    cfg = tiny.config()
    layer = 1
    p = {n: jnp.asarray(W.host_leaf(SEED, cfg, n, layer, np.float32))
         for n in W.layer_leaves(cfg, layer)}
    rng = np.random.default_rng(6)
    m, x = (jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want = R.experts(p, m, *R.route(p, x, cfg), cfg)
    tcfg, params = tiny.program(cfg, SEED)
    total, held, hit = 0.0, 0, 0
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(tcfg, held_experts=(first, 2))
        y, counts = experts.RoutedExperts(share).apply(
            {"params": {"gate": params["window_layers"]["moe"]["gate"][0]}},
            m[None], jnp.ones((1, 24), bool),
            jnp.asarray(params["expert_wi"][:, first:first + 2]),
            jnp.asarray(params["expert_wo"][:, first:first + 2]), layer,
            x[None])
        total, held, hit = total + y[0], held + int(counts[1]), \
            hit + int(counts[4])
    assert held == 24 * 3       # every routed pair is held by one share
    assert 3 <= hit <= 8
    assert np.abs(want).max() > 0.005
    np.testing.assert_allclose(total, want, atol=1e-7)


# -- the configuration's checks ----------------------------------------------

BASE = dict(vocab_size=32, d_model=16, n_heads=2, head_dim=8, n_layers=2,
            d_ff=16, max_seq_len=32)


@pytest.mark.parametrize("changes, said", [
    (dict(layer_pattern=(("dense", 1), ("expert", 1)), n_routed_experts=4,
          expert_d_ff=8, held_experts=(2, 3)), "held_experts"),
    (dict(layer_pattern=(("dense", 1), ("expert", 1)), n_routed_experts=4,
          expert_d_ff=8, held_experts=(0, 4), expert_top_k=5),
     "expert_top_k"),
    (dict(layer_pattern=(("full", 1), ("window", 1)), n_routed_experts=4,
          expert_d_ff=8, held_experts=(0, 0), window=4), "held_experts"),
    (dict(layer_pattern=(("full", 1), ("window", 1)), n_routed_experts=4,
          expert_d_ff=8, held_experts=(0, 4), window=4, router="tanh"),
     "router 'tanh'"),
    (dict(layer_pattern=(("full", 1), ("window", 1))), "window >= 1"),
    (dict(layer_pattern=(("full", 1), ("attention", 1)), window=4),
     "stand beside each other alone"),
    (dict(layer_pattern=(("full", 1), ("window", 1)), window=4,
          kv_page_size=8, kv_pages=4), "window_pages"),
])
def test_a_bad_configuration_is_refused(changes, said):
    with pytest.raises(ValueError, match=said):
        TransformerConfig(**dict(BASE, **changes))


def test_window_layers_take_a_dense_ffn_where_no_expert_is_stated():
    cfg = TransformerConfig(**dict(
        BASE, layer_pattern=(("full", 1), ("window", 1)), window=4,
        attn_impl="naive", dtype=jnp.float32))
    assert cfg.expert_layers == 0 and cfg.has_window_pages
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert "mlp" in params["window_layers"] and "expert_wi" not in params


# -- through the engine -------------------------------------------------------

def served_gaps(cfg, prompts, outs):
    """The reference's best logit less its logit of the served token, at
    every generated position of every request."""
    gaps = []
    for prompt, out in zip(prompts, outs):
        logits = tiny.reference_logits(cfg, SEED, list(prompt) + out)
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gaps += list(rows.max(-1) - rows[np.arange(len(out)), out])
    return np.asarray(gaps)


@pytest.fixture(scope="module")
def parts():
    cfg = tiny.config()
    tcfg, params = tiny.program(cfg, SEED)
    return cfg, tcfg, params


@pytest.fixture(scope="module")
def engine(parts):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = parts
    eng = DecodeEngine(tcfg, params, n_slots=3, chunk_tokens=4, name="st",
                       kv_page_size=8, kv_pages=40,
                       prefill_chunk_tokens=16)
    yield eng
    eng.close()


def counter(eng, name):
    return eng._reg().counter(name).value(model=eng.name)


def test_engine_serves_the_reference_past_three_windows(parts, engine):
    """(2), (3), (5): chunked prefill, then decode past three windows
    with rows of unequal length in one batch; seven requests over three
    slots, so slots and the window class's pages are taken again."""
    cfg = parts[0]
    # a row: 2 window blocks, one that straddles, 2 of a chunk, and one
    assert engine.window_pages == 3 * (2 + 2 + 2)
    assert engine._prefix is None       # off by default: a second class
    assert engine.kv_bytes_per_token_by_class == {
        "full": 2 * 2 * 2 * 16 * 4, "window": 6 * 2 * 2 * 16 * 4}
    taken = []
    alloc = engine._wmgr.alloc
    engine._wmgr.alloc = lambda n: taken.extend(alloc(n)) or taken[-n:]
    rng = np.random.default_rng(7)
    lengths = [8, 12, 40, 70, 23, 49, 5]
    prompts = [rng.integers(0, 128, size=n).tolist() for n in lengths]
    try:
        outs = engine.generate(prompts, max_new_tokens=3 * WINDOW + 9)
    finally:
        engine._wmgr.alloc = alloc
    assert np.abs(served_gaps(cfg, prompts, outs)).max() < 2e-5
    # every row outgrew its window: pages came back and were taken again
    freed = counter(engine, "kfx_lm_window_pages_freed_total")
    assert freed > 7 * 3 and len(taken) > 4 * engine.window_pages
    assert max(np.bincount(taken)) > 4
    assert engine._wmgr.n_free == engine.window_pages
    assert (engine._wtables == -1).all() and not engine._wmgr.ref.any()
    # a query at position p holds p + 1 and reads min(p + 1, 16), in
    # each of the 6 window layers; every token of a request is one
    # (a preempted row's tokens are counted again as it is recomputed)
    held = lambda n: np.arange(1, n + 1)
    total = [n + 3 * WINDOW + 9 for n in lengths]
    assert counter(engine, "kfx_lm_window_cached_positions_total") \
        >= 6 * sum(held(n).sum() for n in total)
    assert counter(engine, "kfx_lm_window_attended_positions_total") \
        >= 6 * sum(np.minimum(held(n), WINDOW).sum() for n in total)
    assert 0 < counter(engine, "kfx_lm_moe_experts_hit_total") \
        <= 8 * counter(engine, "kfx_lm_moe_dispatches_total")
    gauge = lambda n, **kw: engine._reg().gauge(n).value(model="st", **kw)
    assert gauge("kfx_lm_kv_pages", **{"class": "window"}) == 18
    assert gauge("kfx_lm_kv_pages_free", **{"class": "full"}) == 40
    assert gauge("kfx_lm_kv_pool_bytes", **{"class": "window"}) \
        == 18 * 8 * 6 * 2 * 2 * 16 * 4
    hbm = engine.hbm_bytes()
    assert 0 < hbm["kv_pool_window"] < hbm["kv_pool"]


def test_a_dispatch_is_handed_copies_of_both_tables(engine):
    """The rows of both tables change as soon as a program is enqueued
    (pages behind the window go back, the next prompt chunk gets its
    pages), and the CPU's backend reads a host array in place after the
    call has returned: a lone row's prompt chunks, enqueued one behind
    the other, read a later chunk's tables (wrong logits in one run in
    four under load, until the first class's table was copied too)."""
    for slot in (None, 1):
        for handed, own in zip(engine._tables_arg(slot),
                               (engine._tables, engine._wtables)):
            assert not np.shares_memory(handed, own)
            assert (handed == (own if slot is None else own[slot])).all()


def test_a_row_never_holds_more_than_its_windows_pages(parts):
    """The window class is sized for every slot's worst case, and a
    long row stays inside it: a prompt of 100 in chunks of 16, then 60
    tokens, never more than 2 + 1 + 2 + 1 pages."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = parts
    eng = DecodeEngine(tcfg, params, n_slots=1, chunk_tokens=4, name="one",
                       kv_page_size=8, prefill_chunk_tokens=16)
    low = [eng.window_pages]
    alloc = eng._wmgr.alloc

    def watched(n):
        pages = alloc(n)
        low.append(eng._wmgr.n_free)
        return pages

    eng._wmgr.alloc = watched
    try:
        long = tokens_of(8, 100).tolist()
        short = tokens_of(9, 6).tolist()
        outs = [eng.generate([p], max_new_tokens=60)[0]
                for p in (long, short)]      # (5): the slot, again
    finally:
        eng.close()
    assert eng.window_pages == 6 and min(low) >= 0
    assert np.abs(served_gaps(cfg, [long, short], outs)).max() < 2e-5
    # every token a request holds went through the model once, the
    # last one served too (its step runs; nothing reads its logits)
    held = lambda n: np.arange(1, n + 1)
    assert counter(eng, "kfx_lm_window_cached_positions_total") \
        == 6 * (held(160).sum() + held(66).sum())
    assert counter(eng, "kfx_lm_window_attended_positions_total") \
        == 6 * sum(np.minimum(held(n), WINDOW).sum() for n in (160, 66))
    # the decode chunks' own: 60 steps a request, each a query whose
    # view is 3 blocks wide whatever the row holds, and 3 of 8 experts
    # hit a layer
    decode = lambda what: counter(eng, f"kfx_lm_decode_{what}_total")
    assert decode("window_cached_positions") \
        == 6 * (held(160)[100:].sum() + held(66)[6:].sum())
    assert decode("window_attended_positions") == 6 * (
        60 * WINDOW + np.minimum(held(66)[6:], WINDOW).sum())
    from kubeflow_tpu.models.transformer import paged_view

    # (one row: its 3-block view is no smaller than the pool of 6
    # pages, which is scored in place)
    assert paged_view(eng.cfg, 1, 1, WINDOW, eng.n_blocks) == (3, True)
    assert decode("window_gathered_positions") == 6 * 120 * 6 * 8
    assert decode("experts_hit") == 8 * 120 * 3
    assert counter(eng, "kfx_lm_window_gathered_positions_total") \
        > decode("window_gathered_positions")     # the prompt chunks'
    assert counter(eng, "kfx_lm_moe_experts_hit_total") \
        > decode("experts_hit")
    assert counter(eng, "kfx_lm_sample_steps_total") >= 120


def reference_kv(cfg, tokens, layer):
    """The reference's keys and values of ``tokens`` in ``layer``, each
    [S, key/value heads x head_dim]."""
    weights = lambda i: {n: W.host_leaf(SEED, cfg, n, i, np.float32)
                         for n in W.layer_leaves(cfg, i)}
    x = jnp.asarray(W.host_leaf(SEED, cfg, "embed_tokens", -1,
                                np.float32))[jnp.asarray(tokens)]
    step = R.layer_step(cfg)
    for i in range(layer):
        x = step(i, weights(i), x)
    return [np.asarray(t) for t in R.layer_kv(cfg)(layer, weights(layer), x)]


def live_row_kv(eng, prompt, new, layers):
    """What ``row_kv`` gives of a request while it decodes, the layers
    asked for, and the request's tokens once it has ended."""
    import time

    req = eng.submit(prompt, max_new_tokens=new)
    while not req.tokens:
        time.sleep(0.001)
    held = {l: eng.row_kv(req.slot, l) for l in layers}
    return held, list(prompt) + req.result(60)


@pytest.mark.parametrize("kv_quant, least, most",
                         [("", 0.0, 2e-6), ("int8", 1e-3, 2e-2)])
def test_a_live_rows_pages_hold_the_references_keys_and_values(
        parts, kv_quant, least, most):
    """``row_kv``: a full layer's pages hold every position of the row,
    a window layer's the positions it has not given back, and both the
    reference's numbers; held in int8 they are off by a scale's step
    and more (in the first layer by that step alone: ``most``), which
    is what the benchmark's ``kv_gap`` tells apart."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = parts
    eng = DecodeEngine(tcfg, params, n_slots=2, chunk_tokens=4, name="kv",
                       kv_page_size=8, prefill_chunk_tokens=16,
                       kv_quant=kv_quant)
    try:
        held, tokens = live_row_kv(eng, tokens_of(21, 50).tolist(), 150,
                                   (0, 1, 4, 7))
        with pytest.raises(ValueError, match="no request is live"):
            eng.row_kv(1, 0)
        with pytest.raises(ValueError, match="layer 8 of 8"):
            eng.row_kv(0, 8)
    finally:
        eng.close()
    for layer, got in held.items():
        at = got["positions"]
        n = at[-1] + 1
        assert 50 <= n <= 200 and got["key"].shape == (len(at), 2 * 16)
        if layer % 4 == 0:      # full: everything the row holds
            assert at.tolist() == list(range(n))
        else:                   # window: whole pages from the window on
            assert at.tolist() == list(range(at[0], n))
            assert at[0] % 8 == 0 and n - WINDOW - 8 - 4 < at[0] \
                <= max(0, n - WINDOW)
        for name, want in zip(("key", "value"),
                              reference_kv(cfg, tokens[:n], layer)):
            gap = np.linalg.norm(got[name] - want[at], axis=-1) \
                / np.linalg.norm(want[at], axis=-1)
            assert least <= np.median(gap), (layer, name)
            if layer == 0 or not kv_quant:
                assert np.median(gap) <= most and gap.max() <= most * 10, \
                    (layer, name)


def test_row_kv_is_refused_by_name_where_a_layer_holds_none():
    from kubeflow_tpu.serving.engine import DecodeEngine

    from benchmark.tests import tiny_granite

    tcfg, params = tiny_granite.program(tiny_granite.config(), SEED)
    eng = DecodeEngine(tcfg, params, n_slots=1, name="hybrid",
                       kv_page_size=8)
    try:
        with pytest.raises(ValueError, match="holds no paged keys"):
            eng.row_kv(0, 0)
    finally:
        eng.close()


def test_a_preempted_row_whose_window_pages_were_freed_resumes(parts,
                                                               engine):
    """(4): three rows that outgrow the first class's 40 pages; the
    youngest is preempted long after it gave back the pages behind its
    window, and completes by recompute from position 0."""
    cfg = parts[0]
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 128, size=60).tolist() for _ in range(3)]
    before = counter(engine, "kfx_lm_kv_preemptions_total")
    outs = engine.generate(prompts, max_new_tokens=60)
    assert counter(engine, "kfx_lm_kv_preemptions_total") > before
    assert np.abs(served_gaps(cfg, prompts, outs)).max() < 2e-5
    assert engine._wmgr.n_free == engine.window_pages
    assert engine.generate(prompts[-1:], max_new_tokens=60) == outs[-1:]


# -- (8) what the second page class refuses, by name -------------------------

@pytest.mark.parametrize("asked, named", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(draft_layers=1), "speculative decoding"),
    (dict(role="prefill"), "KV offload, migration and transfer"),
    (dict(kv_peer_send=lambda raw: "peer"),
     "KV offload, migration and transfer"),
    (dict(kv_offload_pages=4), "KV offload, migration and transfer"),
])
def test_a_second_page_class_refuses_by_name(parts, asked, named):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = parts
    with pytest.raises(ValueError, match=f"{named} cannot take a "
                       "configuration with a second page class"):
        DecodeEngine(tcfg, params, n_slots=2, name="refused", **asked)


@pytest.mark.parametrize("asked, named", [
    (dict(adapters={"a": "file:///nowhere"}), "LoRA adapters"),
    (dict(models={"m": "file:///nowhere"}, model_default="m"),
     "the weight pool"),
])
def test_runs_of_layers_refuse_by_name(parts, asked, named):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = parts
    with pytest.raises(ValueError, match=f"{named} cannot take this "
                       "configuration"):
        DecodeEngine(tcfg, params, n_slots=2, name="refused", **asked)


def test_migration_and_import_refuse_a_second_page_class(engine):
    from kubeflow_tpu.serving import kvtransfer

    with pytest.raises(ValueError, match="second page class"):
        engine.migrate_out(send=lambda raw: "peer")
    with pytest.raises(kvtransfer.TransferError, match="second page class"):
        engine.kv_import(b"")


def test_the_configuration_round_trips_through_an_export(parts, tmp_path):
    from kubeflow_tpu.serving.lm_server import export_lm, load_lm

    _, tcfg, params = parts
    export_lm(str(tmp_path), tcfg, params)
    cfg, loaded = load_lm(str(tmp_path))
    assert cfg == tcfg and cfg.router == "softmax" and cfg.early_router
    assert cfg.window == WINDOW and cfg.expert_act == "relu"
    np.testing.assert_array_equal(loaded["expert_wo"], params["expert_wo"])
