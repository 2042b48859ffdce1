"""Recurrent state beside pages: the Mamba-2 mixer of models/ssm.py in
its two forms, grouped key/value heads, runs of two kinds of layer
that recur, the four multipliers and the tied head, through the model and
through ``DecodeEngine``, against the benchmark's plain reference
(benchmark/reference_granitemoehybrid.py) at a tiny size with every
mechanism present (benchmark/tests/tiny_granite.py). float32, seeded
weights; logits, not tokens."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granitemoehybrid as R
from benchmark.tests import tiny_glm, tiny_granite
from kubeflow_tpu.models import ssm
from kubeflow_tpu.models.transformer import (Attention, TransformerConfig,
                                             TransformerLM,
                                             attends_pool_in_place,
                                             attention_path, init_cache,
                                             score_bytes)

SEED = 5
SERVE = dict(decode=True, kv_page_size=8, kv_pages=16, state_slots=3)


# -- (a) the two forms of the mixer's recurrence, and the reference's ------

def scan_inputs(T, seed=0, B=2, H=4, P=8, G=2, N=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    dt = jax.nn.softplus(f(B, T, H) - 2.0)
    A = -jnp.exp(f(H) * 0.5)
    return f(B, T, H, P), dt, A, f(B, T, G, N), f(B, T, G, N), f(H)


@pytest.mark.parametrize("length", [5, 8, 13, 21])
def test_prefill_is_step_by_step_is_the_references_scan(length):
    """Chunks of 8 tokens: lengths that are no multiple of the chunk,
    under one chunk, and of several."""
    x, dt, A, Bm, Cm, D = scan_inputs(length)
    B, _, H, P = x.shape
    empty = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    y, state = ssm.prefill(x, dt, A, Bm, Cm, D, empty, chunk=8)
    s, ys = empty, []
    for t in range(length):
        yt, s = ssm.step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, s)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=2e-5)
    np.testing.assert_allclose(state, s, atol=2e-5)
    heads = lambda g: jnp.repeat(g, H // g.shape[2], axis=2)
    cut = length // 3 + 1   # where the reference's second row stops
    ref_y, ref_state = R.ssm_scan(x, dt, A, heads(Bm), heads(Cm), D,
                                  jnp.asarray([length, cut]))
    np.testing.assert_allclose(y[0], ref_y[0], atol=2e-5)
    np.testing.assert_allclose(y[1, :cut], ref_y[1, :cut], atol=2e-5)
    np.testing.assert_allclose(state[0], ref_state[0], atol=2e-5)
    # from a state that is not empty, in two runs of unequal length
    y1, s1 = ssm.prefill(x[:, :cut], dt[:, :cut], A, Bm[:, :cut],
                         Cm[:, :cut], D, empty, chunk=8)
    y2, s2 = ssm.prefill(x[:, cut:], dt[:, cut:], A, Bm[:, cut:],
                         Cm[:, cut:], D, s1, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-5)
    np.testing.assert_allclose(s2, state, atol=2e-5)
    np.testing.assert_allclose(s1[1], ref_state[1], atol=2e-5)


def test_pad_positions_are_inert_in_both_forms():
    """dt 0: the state keeps its bits and the window does not move."""
    x, dt, A, Bm, Cm, D = scan_inputs(6)
    state = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 4, 8, 16)), jnp.float32)
    _, after = ssm.step(x[:, 0], 0 * dt[:, 0], A, Bm[:, 0], Cm[:, 0], D,
                        state)
    np.testing.assert_array_equal(after, state)
    _, after = ssm.prefill(x, 0 * dt, A, Bm, Cm, D, state, chunk=4)
    np.testing.assert_array_equal(after, state)
    window = jnp.ones((2, 3, 5))
    kernel, bias = jnp.ones((4, 5)), jnp.zeros((5,))
    _, kept = ssm.causal_conv(window, 7 * jnp.ones((2, 6, 5)),
                              jnp.asarray([0, 2]), kernel, bias)
    np.testing.assert_array_equal(kept[0], window[0])
    np.testing.assert_array_equal(kept[1], [[1] * 5, [7] * 5, [7] * 5])


# -- the model through its cache -------------------------------------------

def served_logits(tcfg, params, tokens, pieces, table, slot=2, cache=None):
    """Logits [S, V] of ``tokens`` fed through the cache in ``pieces``
    of (real tokens, bucket): the bucket's rest is pad (position -1)."""
    model = TransformerLM(tcfg)
    cache = init_cache(tcfg) if cache is None else cache
    apply = jax.jit(lambda p, c, t, pos: model.apply(
        {"params": p, "cache": c}, t, positions=pos,
        block_tables=jnp.asarray(table), slots=jnp.asarray([slot]),
        mutable=["cache", "counts"]))
    out, at = [], 0
    for n, bucket in pieces:
        t = np.zeros((1, bucket), np.int32)
        pos = np.full((1, bucket), -1, np.int32)
        t[0, :n], pos[0, :n] = tokens[at:at + n], np.arange(at, at + n)
        logits, vars_ = apply(params, cache, jnp.asarray(t),
                              jnp.asarray(pos))
        cache = vars_["cache"]
        out.append(np.asarray(logits[0, :n]))
        at += n
    return np.concatenate(out, 0), cache


TABLE = np.array([[3, 7, 1, 12, 5, 9, 14, 2] + [-1] * 8], np.int32)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_granite.config()
    tcfg, params = tiny_granite.program(cfg, SEED, max_seq_len=128)
    tokens = np.random.default_rng(0).integers(0, 128, size=60)
    return cfg, tcfg, params, tokens, \
        tiny_granite.reference_logits(cfg, SEED, tokens)


def test_the_tiny_configuration_has_every_mechanism(tiny):
    _, tcfg, params, _, _ = tiny
    assert tcfg.layer_pattern == (
        ("mamba", 2), ("attention", 1), ("mamba", 3), ("attention", 1),
        ("mamba", 1)) and tcfg.has_slot_state
    assert tcfg.layer_runs == (("mamba_layers", "mamba", 2),
                               ("attention_layers", "attention", 1),
                               ("mamba_layers2", "mamba", 3),
                               ("attention_layers2", "attention", 1),
                               ("mamba_layers3", "mamba", 1))
    assert (tcfg.kv_heads, tcfg.n_heads) == (2, 4)
    assert not tcfg.rope and tcfg.tie_embeddings and "lm_head" not in params
    assert 1.0 not in (tcfg.embedding_multiplier, tcfg.residual_multiplier,
                       tcfg.logits_scaling, tcfg.attention_multiplier)
    # a run's layers stacked; the published in_proj in two kernels
    run = params["mamba_layers2"]
    assert run["ssm"]["in_proj"]["kernel"].shape == (3, 32, 64 + 96)
    assert run["ssm"]["dt_proj"]["kernel"].shape == (3, 32, 4)
    assert params["attention_layers2"]["attn"]["key"][
        "kernel"].shape == (1, 32, 2, 8)


def test_the_whole_forward_pass_is_the_references(tiny):
    _, tcfg, params, tokens, want = tiny
    got = jax.jit(TransformerLM(tcfg).apply)(
        {"params": params}, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("pieces", [
    ((60, 64),),                                   # whole
    ((5, 8), (11, 16), (3, 8), (32, 32), (9, 16)),  # unequal, pad-filled
    ((16, 16), (16, 16), (8, 8)) + ((1, 1),) * 20,  # prefill, then decode
], ids=["whole", "unequal-chunks", "prefill-then-decode"])
def test_chunks_with_pad_filling_are_the_prompt_whole(tiny, pieces):
    _, tcfg, params, tokens, want = tiny
    tcfg = dataclasses.replace(tcfg, **SERVE)
    n = sum(p[0] for p in pieces)
    got, cache = served_logits(tcfg, params, tokens, pieces, TABLE)
    np.testing.assert_allclose(got, want[:n], atol=2e-5)
    # the state lies in the row's slot and nowhere else
    for run in ("mamba_layers", "mamba_layers2", "mamba_layers3"):
        state = np.asarray(cache[run]["ssm"]["state"])
        assert np.abs(state[:, 2]).max() > 0 and not state[:, :2].any()


def test_the_state_is_held_in_the_type_the_configuration_states(tiny):
    _, tcfg, params, tokens, want = tiny
    err = {}
    for name in ("float32", "bfloat16"):
        cfg = dataclasses.replace(tcfg, ssm_state_dtype=name, **SERVE)
        leaves = init_cache(cfg)["mamba_layers2"]["ssm"]
        assert leaves["state"].dtype == jnp.dtype(name)
        assert leaves["state"].shape == (3, 3, 4, 16, 16)
        assert leaves["conv"].shape == (3, 3, 3 * (64 + 32))
        got, _ = served_logits(cfg, params, tokens,
                               ((16, 16),) + ((1, 1),) * 30, TABLE)
        err[name] = np.abs(got - want[:46]).max()
    assert err["float32"] < 2e-5 and err["bfloat16"] > 50 * err["float32"]


# -- (f) grouped heads against repeated-head attention ---------------------

@pytest.mark.parametrize("form", ["in-place", "gathered"])
def test_grouped_heads_are_repeated_head_attention(form):
    """One layer's paged attention with 2 key/value heads under 4 query
    heads, against the same layer with each key/value head's kernel
    repeated for its group (plain multi-head attention): the decode
    chunk's form over the pool in place and the prefill's gathered
    view."""
    B, S = (4, 1) if form == "in-place" else (1, 8)
    base = dict(vocab_size=64, d_model=32, n_heads=4, head_dim=8,
                n_layers=1, d_ff=32, max_seq_len=32, dtype=jnp.float32,
                param_dtype=jnp.float32, decode=True, kv_page_size=8,
                kv_pages=16, rope=False, attention_multiplier=0.3)
    grouped = TransformerConfig(n_kv_heads=2, **base)
    plain = TransformerConfig(**base)
    for cfg in (grouped, plain):
        assert attends_pool_in_place(
            B, 32, 16, 8, score_bytes(cfg, S)) is (form == "in-place")
    rng = np.random.default_rng(3)
    kernels = {"query": rng.standard_normal((32, 4, 8)),
               "key": rng.standard_normal((32, 2, 8)),
               "value": rng.standard_normal((32, 2, 8)),
               "out": rng.standard_normal((4, 8, 32))}
    tree = lambda rep: {n: {"kernel": jnp.asarray(
        np.repeat(k, rep, 1) if n in ("key", "value") else k, jnp.float32)}
        for n, k in kernels.items()}
    tables = np.stack([np.arange(4) + 4 * b for b in range(B)]).astype(
        np.int32)
    xs = [jnp.asarray(rng.standard_normal((B, S, 32)), jnp.float32)
          for _ in range(3)]
    outs = []
    for cfg, rep in ((grouped, 1), (plain, 2)):
        cache, got = init_cache(cfg)["layers"]["attn"], []
        for step, x in enumerate(xs):   # the cache fills, then is read
            pos = jnp.broadcast_to(jnp.arange(step * S, (step + 1) * S),
                                   (B, S))
            y, vars_ = Attention(cfg, name="attn").apply(
                {"params": tree(rep), "cache": cache}, x, pos,
                jnp.asarray(tables), pos, mutable=["cache"])
            cache = vars_["cache"]
            got.append(np.asarray(y))
        outs.append(got)
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # a token's key/value heads lie side by side in the grouped leaves
    assert init_cache(grouped)["layers"]["attn"]["cached_key"].shape == (
        1, 16, 8, 16)
    assert init_cache(plain)["layers"]["attn"]["cached_key"].shape == (
        1, 16, 8, 4, 8)


def test_the_flash_kernels_refuse_grouped_heads_by_name():
    cfg = TransformerConfig(n_heads=8, n_kv_heads=2, head_dim=64,
                            attn_impl="flash")
    with pytest.raises(ValueError, match="n_kv_heads 2 of n_heads 8"):
        attention_path(cfg, 2048)
    assert attention_path(dataclasses.replace(cfg, attn_impl="naive"),
                          2048) == "dense"


# -- (c) (d) (e) through DecodeEngine --------------------------------------

def served_gaps(cfg, prompts, outs):
    """The reference's best logit less its logit of the served token, at
    every generated position of every request."""
    gaps = []
    for prompt, out in zip(prompts, outs):
        logits = tiny_granite.reference_logits(cfg, SEED,
                                               list(prompt) + out)
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gaps += list(rows.max(-1) - rows[np.arange(len(out)), out])
    return np.asarray(gaps)


@pytest.fixture(scope="module")
def engine(tiny):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params, _, _ = tiny
    eng = DecodeEngine(tcfg, params, n_slots=3, chunk_tokens=4,
                       name="hybrid", kv_page_size=8, kv_pages=24,
                       prefill_chunk_tokens=16)
    yield eng
    eng.close()


def counter(eng, name):
    return eng._reg().counter(name).value(model="hybrid")


def test_engine_serves_the_references_logits(tiny, engine):
    cfg = tiny[0]
    rng = np.random.default_rng(7)
    # 4 attention layers' worth of nothing: 2 layers x K and V x 2 x 8
    assert engine.kv_bytes_per_token == 2 * 2 * 2 * 8 * 4
    assert engine.state_bytes_per_slot == 6 * (4 * 16 * 16 + 3 * 96) * 4
    assert engine._prefix is None          # off by default: slot state
    # prompts in one bucket, in chunks, and with a remainder chunk
    lengths = [8, 12, 40, 70, 23, 49, 5]
    prompts = [rng.integers(0, 128, size=n).tolist() for n in lengths]
    outs = engine.generate(prompts, max_new_tokens=9)
    assert np.abs(served_gaps(cfg, prompts, outs)).max() < 2e-5
    # the counters of what this PR added: 7 requests over 3 slots
    assert counter(engine, "kfx_lm_state_resets_total") == 6 * 7
    assert counter(engine, "kfx_lm_ssm_prefill_tokens_total") \
        == 6 * sum(lengths)
    assert counter(engine, "kfx_lm_ssm_row_updates_total") == 6 * 9 * 7
    gauge = lambda n: engine._reg().gauge(n).value(model="hybrid")
    assert gauge("kfx_lm_state_bytes_per_slot") == engine.state_bytes_per_slot
    assert gauge("kfx_lm_kv_bytes_per_token") == engine.kv_bytes_per_token
    assert gauge("kfx_lm_state_slots_in_use") == 0


def test_a_slot_taken_again_serves_what_the_request_gets_alone(tiny, engine):
    """Six requests through three slots, then each of them alone on an
    engine nothing has used: the same tokens, and the reference's."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, tcfg, params = tiny[:3]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, size=n).tolist()
               for n in (30, 9, 17, 44, 21, 12)]
    together = engine.generate(prompts, max_new_tokens=12)
    alone = DecodeEngine(tcfg, params, n_slots=1, chunk_tokens=4,
                         name="alone", kv_page_size=8,
                         prefill_chunk_tokens=16)
    try:
        for prompt, out in zip(prompts, together):
            assert alone.generate([prompt], max_new_tokens=12) == [out]
    finally:
        alone.close()
    assert np.abs(served_gaps(cfg, prompts, together)).max() < 2e-5


@pytest.mark.parametrize("prompt_len, new", [(9, 5), (37, 11)])
def test_a_slot_keeps_the_state_its_request_left(tiny, engine, prompt_len,
                                                 new):
    """``slot_state``: the leaves at the request's slot are the
    reference's states after the prompt and every served token, all
    six Mamba layers in the model's order (three runs), until another
    request takes the slot."""
    from benchmark import reference_granitemoehybrid as R
    from benchmark import weights_granitemoehybrid as W

    cfg = tiny[0]
    prompt = np.random.default_rng(prompt_len).integers(
        0, 128, size=prompt_len).tolist()
    req = engine.submit(prompt, max_new_tokens=new)
    out = req.result(60)
    assert 0 <= req.slot < 3 and engine.flight.timing(req)["slot"] == req.slot
    held = engine.slot_state(req.slot)
    assert held["state"].shape == (6, 4, 16, 16)
    assert held["conv"].shape == (6, 3 * 96)
    _, states = R.hidden_and_states(
        lambda n, l: W.host_leaf(SEED, cfg, n, l, np.float32), cfg,
        jnp.asarray(prompt + out + [0, 0, 0])[None],
        [prompt_len + new])    # (the three behind are a batch's filling)
    np.testing.assert_allclose(
        held["state"], np.stack([s[0] for s in states]), atol=2e-5)


def test_slot_state_is_refused_by_name_without_such_leaves():
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            head_dim=8, n_layers=1, d_ff=16, max_seq_len=32,
                            dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = DecodeEngine(cfg, params, n_slots=1, name="dense")
    try:
        with pytest.raises(ValueError, match="holds no slot state"):
            eng.slot_state(0)
    finally:
        eng.close()


def test_a_preempted_request_resumes_to_the_same_tokens(tiny, engine):
    """Three rows that outgrow 24 pages: the youngest is preempted, its
    pages freed, and it completes by recompute from position 0, where
    its slot's state starts from zeros again."""
    cfg = tiny[0]
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 128, size=40).tolist() for _ in range(3)]
    before = counter(engine, "kfx_lm_kv_preemptions_total")
    outs = engine.generate(prompts, max_new_tokens=40)
    assert counter(engine, "kfx_lm_kv_preemptions_total") > before
    assert np.abs(served_gaps(cfg, prompts, outs)).max() < 2e-5
    again = engine.generate(prompts[-1:], max_new_tokens=40)
    assert again == outs[-1:]


# -- (g) what a configuration with slot state refuses, by name -------------

@pytest.mark.parametrize("asked, named", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(draft_layers=1), "speculative decoding"),
    (dict(adapters={"a": "file:///nowhere"}), "LoRA adapters"),
    (dict(models={"m": "file:///nowhere"}, model_default="m"),
     "the weight pool"),
    (dict(kv_offload_pages=4), "KV offload, migration and transfer"),
    (dict(role="prefill"), "KV offload, migration and transfer"),
    (dict(kv_peer_send=lambda raw: "peer"),
     "KV offload, migration and transfer"),
])
def test_slot_state_refuses_by_name(tiny, asked, named):
    from kubeflow_tpu.serving.engine import DecodeEngine

    _, tcfg, params = tiny[:3]
    with pytest.raises(ValueError, match=f"{named} cannot take a "
                       "configuration with slot state"):
        DecodeEngine(tcfg, params, n_slots=2, name="refused", **asked)


def test_migration_and_import_refuse_slot_state_by_name(engine):
    from kubeflow_tpu.serving import kvtransfer

    with pytest.raises(ValueError, match="holds slot state"):
        engine.migrate_out(send=lambda raw: "peer")
    with pytest.raises(kvtransfer.TransferError, match="holds slot state"):
        engine.kv_import(b"")


def test_the_configuration_refuses_what_the_layers_cannot_take():
    base = dict(n_layers=4, ssm_heads=4, ssm_head_dim=8, ssm_state=16)
    with pytest.raises(ValueError, match="never twice in a row"):
        TransformerConfig(layer_pattern=(("mamba", 1), ("mamba", 3)), **base)
    with pytest.raises(ValueError, match="each kind once"):
        TransformerConfig(n_layers=3, layer_pattern=(
            ("dense", 1), ("attention", 1), ("dense", 1)))
    with pytest.raises(ValueError, match="needs ssm_heads"):
        TransformerConfig(n_layers=2, layer_pattern=(("mamba", 2),))
    with pytest.raises(ValueError, match="must divide n_heads"):
        TransformerConfig(n_heads=8, n_kv_heads=3)
    with pytest.raises(ValueError, match="served by the plain attention"):
        TransformerConfig(n_heads=8, n_kv_heads=2, lora_rank=4)
    with pytest.raises(ValueError, match="tie_embeddings has no lm_head"):
        TransformerConfig(tie_embeddings=True, loss_chunk=64)
    cfg = TransformerConfig(layer_pattern=(("mamba", 3), ("attention", 1)),
                            **base)
    assert cfg.layer_runs == (("mamba_layers", "mamba", 3),
                              ("attention_layers", "attention", 1))


def test_export_round_trips_the_new_keys(tmp_path, tiny):
    from kubeflow_tpu.serving.lm_server import export_lm, load_lm

    _, tcfg, params = tiny[:3]
    tcfg = dataclasses.replace(tcfg, ssm_state_dtype="bfloat16")
    export_lm(str(tmp_path), tcfg, params)
    with open(tmp_path / "lm_config.json") as f:
        stored = json.load(f)["config"]
    assert stored["ssm_state_dtype"] == "bfloat16"
    assert stored["layer_pattern"] == [
        ["mamba", 2], ["attention", 1], ["mamba", 3], ["attention", 1],
        ["mamba", 1]] and stored["n_kv_heads"] == 2
    back, loaded = load_lm(str(tmp_path))
    assert back == tcfg
    assert jax.tree_util.tree_structure(loaded) \
        == jax.tree_util.tree_structure(params)


# -- (h) the configurations the benchmark had lower to the parent's text ---

GLM_PARENT = {
    "decode_chunk":
        "7af0b9a8a02b18ff4b0bfe4246c6eaabe2edfe40b575d81d75ad9e215a0b2885",
    "prefill_16":
        "5d3dcd8c6a2627940da2d5e6966baf94d8db2d6fc746b59b06327efe6725c1fa",
}


@pytest.fixture(scope="module")
def glm_programs():
    """{program: sha256 of its StableHLO} of the tiny ``glm_moe_dsa``
    engine (2 slots, 30 pages of 8, chunked prefill 16): made on the
    parent commit (81be731) with this function; ``decode_chunk``'s was
    made again in PR 45, on 15b9f16 with that PR's sampler (one choice
    of its form a step, and the counts of it). Both were made again in
    PR 46, whose routed experts hand back a fifth count (the held
    experts that got rows, models/experts.py COUNTS): with that one
    count taken out again, PR 46's tree lowered both to the hashes
    before it (7207114e..., f951ede5...), so nothing else of these
    programs moved. The dense block's
    programs, serving and training, are held by
    tests/test_dense_program_guard.py."""
    from kubeflow_tpu.serving import engine as E

    tcfg, params = tiny_glm.program(tiny_glm.config(), SEED,
                                    max_seq_len=128)
    texts, real_jit = {}, jax.jit

    class Recording:
        def __init__(self, fn, **kw):
            self.fn, self.jitted = fn, real_jit(fn, **kw)

        def __call__(self, *args, **kw):   # the model's own jitted parts
            return self.jitted(*args, **kw)

        def lower(self, *specs):
            lowered = self.jitted.lower(*specs)
            texts[self.fn.__name__] = lowered.as_text()
            return lowered

    eng = E.DecodeEngine(tcfg, params, n_slots=2, chunk_tokens=4,
                         name="guard", kv_page_size=8, kv_pages=30,
                         prefix_cache=False, prefill_chunk_tokens=16)
    try:
        jax.jit = lambda fn, **kw: Recording(fn, **kw)
        try:
            eng._build_decode()
            eng._build_prefill(16)
        finally:
            jax.jit = real_jit
    finally:
        eng.close()
    return {what: hashlib.sha256(
        texts[f"run_kfx_{what}"].encode()).hexdigest()
        for what in GLM_PARENT}


@pytest.mark.parametrize("program", sorted(GLM_PARENT))
def test_latent_program_lowers_to_the_parents_text(glm_programs, program):
    assert glm_programs[program] == GLM_PARENT[program]
