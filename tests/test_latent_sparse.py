"""The latent (MLA) paged cache, the learned sparse selection and the
routed experts of models/latent.py and models/experts.py, through the
model and through ``DecodeEngine``, against the benchmark's plain
reference (benchmark/reference_glm_moe_dsa.py) at a tiny size with
every mechanism present. float32, seeded weights; logits, not tokens."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_glm_moe_dsa as R
from benchmark import weights_glm_moe_dsa as W
from benchmark.tests import tiny_glm
from kubeflow_tpu.models import experts
from kubeflow_tpu.models.transformer import (TransformerConfig,
                                             TransformerLM, init_cache)

SEED = 5
SERVE = dict(decode=True, kv_page_size=8, kv_pages=40)


def served_logits(tcfg, params, tokens, pieces, table, cache=None):
    """Logits [S, V] of ``tokens`` fed through the paged cache in
    ``pieces`` (chunk lengths; 1 = a decode step)."""
    model = TransformerLM(tcfg)
    cache = init_cache(tcfg) if cache is None else cache
    apply = jax.jit(lambda p, c, t, pos: model.apply(
        {"params": p, "cache": c}, t, positions=pos,
        block_tables=jnp.asarray(table), mutable=["cache", "counts"]))
    out, at = [], 0
    for n in pieces:
        logits, vars_ = apply(
            params, cache, jnp.asarray(tokens[at:at + n])[None],
            jnp.arange(at, at + n, dtype=jnp.int32)[None])
        cache = vars_["cache"]
        out.append(np.asarray(logits[0]))
        at += n
    return np.concatenate(out, 0), vars_


def one_row_table(pages, blocks=16):
    table = np.full((1, blocks), -1, np.int32)
    table[0, :len(pages)] = pages
    return table


PAGES = [3, 7, 1, 30, 12, 5, 9, 22, 17, 2]


def test_chunked_prefill_then_decode_match_the_reference_logits():
    cfg = tiny_glm.config()
    tcfg, params = tiny_glm.program(cfg, SEED, **SERVE)
    tokens = np.random.default_rng(0).integers(0, 128, size=70)
    got, vars_ = served_logits(tcfg, params, tokens,
                               (16, 16, 8) + (1,) * 30, one_row_table(PAGES))
    want = tiny_glm.reference_logits(cfg, SEED, tokens)
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6)
    # one decode step: 1 token x top-4 in each of 2 expert layers
    counts = dict(zip(experts.COUNTS, np.asarray(vars_["counts"]["moe"][0])))
    assert counts["assignments"] == 8 and counts["dispatches"] == 2
    assert 0 <= counts["max_rows"] <= counts["assignments_held"] <= 8


def test_below_index_topk_the_sparse_layer_is_dense_latent_attention():
    """While a row holds no more than ``index_topk`` tokens the
    selection is everything: the same logits as a model with no
    indexer, and the indexer's weights change nothing."""
    cfg = tiny_glm.config()
    tcfg, params = tiny_glm.program(cfg, SEED, **SERVE)
    tokens = np.random.default_rng(1).integers(0, 128, size=16)
    pieces = (8, 4) + (1,) * 4
    sparse, _ = served_logits(tcfg, params, tokens, pieces,
                              one_row_table(PAGES))
    dense, _ = served_logits(dataclasses.replace(tcfg, index_topk=0),
                             params, tokens, pieces, one_row_table(PAGES))
    np.testing.assert_allclose(sparse, dense, atol=1e-6)
    # ... and past it they part
    tokens = np.random.default_rng(1).integers(0, 128, size=40)
    sparse, _ = served_logits(tcfg, params, tokens, (40,),
                              one_row_table(PAGES))
    dense, _ = served_logits(dataclasses.replace(tcfg, index_topk=0),
                             params, tokens, (40,), one_row_table(PAGES))
    assert np.abs(sparse[:16] - dense[:16]).max() < 2e-6
    assert np.abs(sparse[20:] - dense[20:]).max() > 1e-4


@pytest.mark.parametrize("case", ["ties", "stale_pages", "pads"])
def test_the_selection_never_reads_what_is_not_the_rows(case):
    """Ties go to the earlier position, as in the reference; entries of
    pages that are not in the row's table (live ones of other rows,
    stale ones of freed pages), unallocated blocks and pads are never
    selected, however high they would score."""
    cfg = tiny_glm.config()
    tcfg, params = tiny_glm.program(cfg, SEED, **SERVE)
    tokens = np.random.default_rng(2).integers(0, 128, size=50)
    table, cache, pieces = one_row_table(PAGES), init_cache(tcfg), (24, 26)
    if case == "ties":
        # An indexer that scores every position 0: all tie.
        for run in ("dense_layers", "expert_layers"):
            k = params[run]["attn"]["index_w"]["kernel"]
            params[run]["attn"]["index_w"]["kernel"] = np.zeros_like(k)
        weights = lambda n, l: (
            np.zeros(W.leaf_shape(cfg, n), np.float32)
            if n == "indexer.weights_proj"
            else W.host_leaf(SEED, cfg, n, l, np.float32))
        hidden = R.forward(cfg)(weights, jnp.asarray(tokens))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(hidden @ weights("lm_head", -1))
    else:
        want = tiny_glm.reference_logits(cfg, SEED, tokens)
    if case == "stale_pages":
        # Every page outside the table holds loud entries at live
        # positions with an indexer key that outscores all.
        rng = np.random.default_rng(3)
        outside = np.setdiff1d(np.arange(40), PAGES)

        def loud(leaf, value):
            leaf = np.array(leaf)
            leaf[:, outside] = value(leaf[:, outside].shape)
            return jnp.asarray(leaf)

        for run in cache:
            a = cache[run]["attn"]
            a["cached_latent"] = loud(
                a["cached_latent"], lambda s: 50 * rng.standard_normal(s))
            a["cached_index_key"] = loud(
                a["cached_index_key"], lambda s: 50 * rng.standard_normal(s))
            a["cached_pos"] = loud(
                a["cached_pos"], lambda s: rng.integers(0, 40, size=s))
    if case == "pads":
        # A bucketed prompt: 24 real tokens right-padded to 32 (pads
        # carry position -1), then a second chunk.
        model = TransformerLM(tcfg)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :24] = tokens[:24]
        pos = np.where(np.arange(32) < 24, np.arange(32), -1)[None]
        first, vars_ = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(padded),
            positions=jnp.asarray(pos, jnp.int32),
            block_tables=jnp.asarray(table), mutable=["cache", "counts"])
        counts = np.asarray(vars_["counts"]["moe"][0])
        assert counts[0] == 24 * 4 * 2   # the pads are routed nowhere
        second, _ = model.apply(
            {"params": params, "cache": vars_["cache"]},
            jnp.asarray(tokens[24:])[None],
            positions=jnp.arange(24, 50, dtype=jnp.int32)[None],
            block_tables=jnp.asarray(table), mutable=["cache", "counts"])
        got = np.concatenate([np.asarray(first[0, :24]),
                              np.asarray(second[0])], 0)
    else:
        got, _ = served_logits(tcfg, params, tokens, pieces, table, cache)
    np.testing.assert_allclose(got, want, atol=2e-6)


def top_k_mask(index, K):
    """The selection as it was made until PR 37, from a stable
    ``jax.lax.top_k``: the reference of the counting form."""
    best, at = jax.lax.top_k(index, K)
    least = best[..., -1:]
    last_tie = jnp.max(jnp.where(best == least, at, -1), -1, keepdims=True)
    where = jnp.arange(index.shape[-1], dtype=at.dtype)
    return (index > least) | ((index == least) & (where <= last_tie))


SCORES = {
    "random": lambda rng, shape: rng.standard_normal(shape),
    # ties across the K-th place (+ 0.0: no -0.0, which a sort puts
    # below +0.0 and a comparison does not)
    "eight_values": lambda rng, shape: rng.integers(-3, 5, size=shape) + 0.0,
    "all_zero": lambda rng, shape: np.zeros(shape),
    # every exponent of the normal numbers, both signs (no subnormals:
    # a comparison flushes them to zero, a sort keeps them apart)
    "every_exponent": lambda rng, shape: (
        rng.choice([-1.0, 1.0], size=shape)
        * 10.0 ** rng.uniform(-37, 38, size=shape)),
}


@pytest.mark.parametrize("shape", [(16, 1, 200), (1, 64, 256)],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("readable", [59, 64, 101],
                         ids=["fewer", "exactly_k", "more"])
@pytest.mark.parametrize("kind", sorted(SCORES))
def test_the_counted_selection_is_top_ks_mask_bit_for_bit(kind, readable,
                                                          shape):
    """``latent._selected`` against the mask a stable ``top_k`` gives:
    ties across the K-th place, ``-inf`` tails that leave fewer than K,
    exactly K and more than K positions to read, a decode step's shape
    and a prefill chunk's (a query further on reads a position more)."""
    from kubeflow_tpu.models.latent import _selected

    K, (_, S, W) = 64, shape
    rng = np.random.default_rng(sum(map(ord, kind)) + readable + S)
    scores = SCORES[kind](rng, shape).astype(np.float32)
    reads = np.broadcast_to(
        np.arange(W) < readable + np.arange(S)[:, None], shape)
    index = jnp.asarray(np.where(reads, scores, -np.inf))
    got = jax.jit(_selected, static_argnums=1)(index, K)
    np.testing.assert_array_equal(got, top_k_mask(index, K))
    np.testing.assert_array_equal((np.asarray(got) & reads).sum(-1),
                                  np.minimum(K, reads.sum(-1)))


def test_no_sort_is_left_in_the_select_scope_of_the_lowered_prefill(
        tiny_engine_parts):
    """``kfx_prefill_16`` of the tiny sparse configuration, as the
    engine lowers it: every view width that selects (32, 64, 128; 16 is
    ``index_topk``, where all is selected) counts, in either run of
    layers: the counting loops, and neither a sort nor a top_k."""
    import re

    from kubeflow_tpu.models.latent import view_widths
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = tiny_engine_parts
    texts, real_jit = {}, jax.jit

    class Recording:
        def __init__(self, fn, **kw):
            self.fn, self.jitted = fn, real_jit(fn, **kw)

        def lower(self, *specs):
            lowered = self.jitted.lower(*specs)
            texts[self.fn.__name__] = lowered.as_text(debug_info=True)
            return lowered

    eng = DecodeEngine(tcfg, params, n_slots=2, chunk_tokens=4, name="low",
                       kv_page_size=8, kv_pages=30, prefix_cache=False,
                       prefill_chunk_tokens=16)
    try:
        assert view_widths(eng.cfg) == [16, 32, 64, 128]
        jax.jit = lambda fn, **kw: Recording(fn, **kw)
        try:
            eng._build_prefill(16)
        finally:
            jax.jit = real_jit
    finally:
        eng.close()
    text = texts["run_kfx_prefill_16"]
    # one function a width that selects, called from either run of
    # layers, the two counting loops in it and no sort
    selections = [f for f in text.split("func.func private @")
                  if f.startswith("_selected")]
    assert len(selections) == 3
    assert len(re.findall(r"func\.call @_selected", text)) == 2 * 3
    for body in selections:
        assert body.count("stablehlo.while") == 2
        assert "sort" not in body and "top_k" not in body
    # what the operations are called, scope / primitive: none under
    # ``attn`` is a sort (the names do show one where there is one: the
    # router's top_k and the dispatch's argsort)
    names = re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M)
    sorts = [n for n in names if "top_k" in n or "sort" in n]
    assert sorts and not [n for n in sorts if "attn" in n]


def test_int8_latent_pool_is_close_and_not_equal():
    cfg = tiny_glm.config()
    tcfg, params = tiny_glm.program(cfg, SEED, kv_quant="int8", **SERVE)
    tokens = np.random.default_rng(4).integers(0, 128, size=40)
    got, _ = served_logits(tcfg, params, tokens, (16, 16, 8),
                           one_row_table(PAGES))
    err = np.abs(got - tiny_glm.reference_logits(cfg, SEED, tokens)).max()
    assert 1e-5 < err < 0.1
    assert init_cache(tcfg)["dense_layers"]["attn"][
        "cached_latent"].dtype == jnp.int8


def test_the_router_bias_moves_the_choice_and_not_the_weight():
    tcfg, params = tiny_glm.program(tiny_glm.config(), SEED, **SERVE)
    moe = jax.tree_util.tree_map(lambda x: x[0], params["expert_layers"]["moe"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((64, 64)),
                    jnp.float32)
    assert np.abs(moe["gate_bias"]).max() > 0.05
    chosen, weights = experts.route(tcfg, x, moe["gate"], moe["gate_bias"])
    plain, _ = experts.route(tcfg, x, moe["gate"], 0 * moe["gate_bias"])
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ moe["gate"])
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four shares of 8 of the 32 experts, the shared expert counted
    once, give what the reference gives for the whole layer."""
    whole = tiny_glm.config(n_routed_experts=32, share={"first_expert": 0})
    whole.pop("reduced")
    layer = whole["first_k_dense_replace"]
    p = {n: jnp.asarray(W.host_leaf(SEED, whole, n, layer, np.float32))
         for n in W.layer_leaves(whole, layer)}
    h = jnp.asarray(np.random.default_rng(6).standard_normal((24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = R.swiglu(h, p, "mlp.shared_experts.") + R.routed_part(
            p, h, whole)
        total = R.swiglu(h, p, "mlp.shared_experts.")
    held = 0
    for first in (0, 8, 16, 24):
        share = tiny_glm.config(share={"first_expert": first})
        tcfg, params = tiny_glm.program(share, SEED, n_shared_experts=0,
                                        **SERVE)
        assert tcfg.held_experts == (first, 8)
        moe = jax.tree_util.tree_map(lambda x: x[0],
                                     params["expert_layers"]["moe"])
        moe.pop("shared")
        y, counts = experts.RoutedExperts(tcfg).apply(
            {"params": moe}, h[None], jnp.ones((1, 24), bool),
            jnp.asarray(params["expert_wi"]),
            jnp.asarray(params["expert_wo"]), 0)
        total, held = total + y[0], held + int(counts[1])
    assert held == 24 * 4    # every routed pair is held by one share
    assert np.abs(want).max() > 0.005
    np.testing.assert_allclose(total, want, atol=1e-7)


# -- through the engine -------------------------------------------------------

def served_gaps(cfg, prompts, outs):
    """The reference's best logit less its logit of the served token, at
    every generated position of every request."""
    gaps = []
    for prompt, out in zip(prompts, outs):
        logits = tiny_glm.reference_logits(cfg, SEED, list(prompt) + out)
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gaps += list(rows.max(-1) - rows[np.arange(len(out)), out])
    return np.asarray(gaps)


@pytest.fixture(scope="module")
def tiny_engine_parts():
    cfg = tiny_glm.config()
    tcfg, params = tiny_glm.program(cfg, SEED, max_seq_len=128)
    return cfg, tcfg, params


def test_engine_serves_the_reference_with_preemption_and_shared_pages(
        tiny_engine_parts):
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = tiny_engine_parts
    rng = np.random.default_rng(7)
    eng = DecodeEngine(tcfg, params, n_slots=4, chunk_tokens=4, name="glm",
                       kv_page_size=8, kv_pages=30, prefix_cache=True,
                       prefill_chunk_tokens=16)
    try:
        # latent 32 + rotary 8 in one 128-lane tile, the indexer key 16
        assert eng.kv_bytes_per_token == 3 * (128 + 16) * 4
        # contexts 8-96: some rows select, some never do
        lengths = [8, 12, 40, 70, 23, 90]
        prompts = [rng.integers(0, 128, size=n).tolist() for n in lengths]
        outs = eng.generate(prompts, max_new_tokens=6)
        assert np.abs(served_gaps(cfg, prompts, outs)).max() < 1e-5
        # two rows that share pages: a common prefix of three pages
        stem = rng.integers(0, 128, size=24).tolist()
        pair = [stem + rng.integers(0, 128, size=n).tolist()
                for n in (5, 30)]
        first = eng.generate(pair[:1], max_new_tokens=4)
        both = eng.generate(pair, max_new_tokens=8)
        assert both[0][:4] == first[0]
        assert eng.prefix_stats()["tokens_reused"] >= 24
        assert np.abs(served_gaps(cfg, pair, both)).max() < 1e-5
        # four rows that outgrow 30 pages: the youngest is preempted
        # and completes by recompute
        prompts = [rng.integers(0, 128, size=40).tolist() for _ in range(4)]
        outs = eng.generate(prompts, max_new_tokens=40)
        reg = eng._reg()
        assert reg.counter("kfx_lm_kv_preemptions_total").value(
            model="glm") >= 1
        assert np.abs(served_gaps(cfg, prompts, outs)).max() < 1e-5
        # the counters of what this PR added
        value = lambda n: reg.counter(n).value(model="glm")
        cached = value("kfx_lm_sparse_cached_positions_total")
        attended = value("kfx_lm_sparse_attended_positions_total")
        # the main attention scores the whole view under the
        # selection's mask: it reads no less than is cached
        assert 0 < cached <= attended
        routed = value("kfx_lm_moe_assignments_total")
        held = value("kfx_lm_moe_assignments_held_total")
        assert 0 < held < routed and routed % 4 == 0
        assert value("kfx_lm_moe_dispatches_total") > 0
        assert value("kfx_lm_moe_max_rows_total") > 0
    finally:
        eng.close()


def test_sparse_counters_count_what_the_program_reads(tiny_engine_parts):
    """The layers count on the device: a query token's cached positions
    (those up to its own) and the locations its main attention scored,
    which is the width of the view the call took, not index_topk. One
    prompt of 40 tokens in chunks of 16, then 6 tokens decoded from
    the prompt's bucket (64) on: views of 16, 32, 64 and 128."""
    from kubeflow_tpu.models.latent import view_widths
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = tiny_engine_parts
    eng = DecodeEngine(tcfg, params, n_slots=2, chunk_tokens=4, name="one",
                       kv_page_size=8, kv_pages=30, prefix_cache=False,
                       prefill_chunk_tokens=16)
    try:
        assert view_widths(eng.cfg) == [16, 32, 64, 128]
        prompt = np.random.default_rng(3).integers(0, 128, size=40).tolist()
        eng.generate([prompt], max_new_tokens=6)
        value = lambda n: eng._reg().counter(n).value(model="one")
        layers = cfg["num_hidden_layers"]
        assert value("kfx_lm_sparse_cached_positions_total") == \
            layers * sum(range(1, 40 + 6 + 1))
        assert value("kfx_lm_sparse_attended_positions_total") == \
            layers * (16 * 16 + 16 * 32 + 8 * 64 + 6 * 128)
    finally:
        eng.close()


def test_pages_move_between_engines_leaf_by_leaf(tiny_engine_parts):
    """KV transfer takes the latent leaves as they are: a prompt
    prefilled on one engine and decoded on another reads like one
    served whole."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = tiny_engine_parts
    kw = dict(n_slots=2, chunk_tokens=4, kv_page_size=8, kv_pages=30,
              prefix_cache=False, prefill_chunk_tokens=16)
    a = DecodeEngine(tcfg, params, name="a", **kw)
    b = DecodeEngine(tcfg, params, name="b", **kw)
    try:
        assert ([d["path"] for d in a._leaf_descriptors()]
                == [d["path"] for d in b._leaf_descriptors()])
        assert any("cached_latent" in d["path"]
                   for d in a._leaf_descriptors())
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("feature, kw", [
    ("speculative decoding", dict(draft_layers=1)),
    ("LoRA adapters", dict(adapters={"a": "file:///nowhere"})),
    ("the weight pool", dict(models={"m": "/nowhere"}, model_default="m")),
])
def test_features_that_cannot_take_the_configuration_refuse_by_name(
        tiny_engine_parts, feature, kw):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = tiny_engine_parts
    with pytest.raises(ValueError, match=feature):
        DecodeEngine(tcfg, params, n_slots=2, kv_page_size=8, **kw)


def test_int8_weights_refuse_and_the_dense_cache_refuses():
    tcfg, _ = tiny_glm.program(tiny_glm.config(), SEED)
    with pytest.raises(ValueError, match="int8 weights"):
        dataclasses.replace(tcfg, quant="int8")
    with pytest.raises(ValueError, match="cached in pages"):
        init_cache(dataclasses.replace(tcfg, decode=True), batch=2)


def test_the_configuration_round_trips_through_an_export(tmp_path):
    from kubeflow_tpu.serving.lm_server import export_lm, load_lm

    tcfg, params = tiny_glm.program(tiny_glm.config(), SEED)
    export_lm(str(tmp_path), tcfg, params)
    with open(tmp_path / "lm_config.json") as f:
        stored = json.load(f)["config"]
    assert stored["layer_pattern"] == [["dense", 1], ["expert", 2]]
    assert stored["held_experts"] == [8, 8] and stored["norm_eps"] == 1e-5
    back, tree = load_lm(str(tmp_path))
    assert back == tcfg and back.layer_runs == (
        ("dense_layers", "dense", 1), ("expert_layers", "expert", 2))
    np.testing.assert_array_equal(
        tree["expert_layers"]["moe"]["gate_bias"],
        params["expert_layers"]["moe"]["gate_bias"])
    assert tree["expert_wi"].shape == (2, 8, 64, 96)


def test_dense_configurations_state_what_was_literal():
    cfg = TransformerConfig()
    assert (cfg.norm_eps, cfg.rope_base, cfg.layer_pattern) == (
        1e-6, 10_000.0, ())
    assert cfg.layer_runs == (("layers", "", cfg.n_layers),)
    with pytest.raises(ValueError, match="layer_pattern"):
        TransformerConfig(n_layers=4, layer_pattern=(("dense", 1),))
