"""The batch sampler (models/generate.py ``sample_rows``): a decode
step picks, once for its batch and on the device, between the argmax
alone, the draw without a top-k and the sort, mask and draw; every live
row gets the token the full one-row formula gives it under ``vmap``, to
the last bit. And the engine's side of it: it serves the parent's
tokens request by request and counts the steps that drew and that
sorted (that its decode chunk holds the sort inside a conditional's
branch is read off the lowered text in tests/test_dense_program_guard.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

B, V = 8, 997


def full_formula(logits, rng, temperature, top_k):
    """``_sample`` as it stood before the choice was made a batch (PR
    45's parent, 15b9f16): every part for every row."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    srt = jnp.sort(scaled, axis=-1)
    kth = jax.lax.dynamic_slice_in_dim(
        srt, jnp.maximum(logits.shape[-1] - top_k, 0), 1, axis=-1)
    masked = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(rng, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def random_logits():
    return 3.0 * jax.random.normal(jax.random.PRNGKey(0), (B, V))


def tied_logits():
    """Every row's maximum stands at several places, the first of them
    at another column a row."""
    base = np.asarray(random_logits()).copy()
    top = base.max() + 1.0
    for row in range(B):
        base[row, [5 + row, 400 + row, 900]] = top
    return jnp.asarray(base)


GREEDY, LIVE = [0.0] * B, [True] * B
# name: (logits, temperature, top_k, live, draws, sorts)
CASES = {
    "all_greedy": (random_logits, GREEDY, [0] * B, LIVE, False, False),
    "all_greedy_with_top_k": (random_logits, GREEDY, [40] * B, LIVE,
                              False, False),
    "mixed_with_top_k": (random_logits,
                         [0.0, 0.8, 0.0, 1.0, 0.0, 0.5, 0.0, 0.0],
                         [0, 40, 0, 0, 5, 0, 1, 0], LIVE, True, True),
    "mixed_without_top_k": (random_logits,
                            [0.0, 0.8, 0.0, 1.0, 0.0, 0.5, 0.0, 0.0],
                            [0, 0, 0, -1, 7, 0, 0, 0], LIVE, True, False),
    "all_sampled_without_top_k": (random_logits, [0.7] * B, [0] * B, LIVE,
                                  True, False),
    "all_sampled_with_top_k": (random_logits, [1.3] * B, [3] * B, LIVE,
                               True, True),
    "stale_knobs_in_an_inactive_row": (
        random_logits, [0.0, 0.0, 0.8, 0.0, 0.0, 0.0, 0.0, 0.0],
        [0, 0, 40, 0, 0, 0, 0, 0],
        [True, True, False, True, True, False, True, True], False, False),
    "an_inactive_row_sorts_and_a_live_one_draws": (
        random_logits, [0.0, 0.9, 0.8, 0.0, 0.0, 0.0, 0.0, 0.0],
        [0, 0, 40, 0, 0, 0, 0, 0],
        [True, True, False, True, True, True, True, True], True, False),
    "ties_greedy": (tied_logits, GREEDY, [0] * B, LIVE, False, False),
    "ties_mixed": (tied_logits, [0.0, 0.6] * (B // 2), [0, 2] * (B // 2),
                   LIVE, True, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sample_rows_gives_every_live_row_the_full_formulas_token(case):
    from kubeflow_tpu.models.generate import sample_needs, sample_rows

    make, temperature, top_k, live, draws, sorts = CASES[case]
    logits = make()
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B) + 11)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    live = np.asarray(live)
    want = jax.jit(jax.vmap(
        lambda l, kk, t, tk: full_formula(l[None], kk, t, tk)[0]))(
            logits, keys, temperature, top_k)
    got, needs = jax.jit(lambda *a: (sample_rows(*a), sample_needs(*a[2:])))(
        logits, keys, temperature, top_k, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(got)[live],
                                  np.asarray(want)[live])
    # the counts' predicates: which form the step took
    assert (bool(needs[0]), bool(needs[1])) == (draws, sorts)
    greedy = np.asarray(temperature) <= 0
    np.testing.assert_array_equal(
        np.asarray(got)[greedy & live],
        np.argmax(np.asarray(logits), -1)[greedy & live])
    if not draws:
        # the argmax for every row, the stale ones too; first maximum
        np.testing.assert_array_equal(got, np.argmax(np.asarray(logits), -1))


# -- the engine: the parent's tokens request by request, and the counts ---

@pytest.fixture(scope="module")
def tiny_lm():
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            head_dim=16, n_layers=2, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def make_engine(tiny_lm, name):
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, params = tiny_lm
    eng = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=4, name=name,
                       kv_page_size=16)
    eng._decode()
    return eng


@pytest.fixture(scope="module")
def engine(tiny_lm):
    eng = make_engine(tiny_lm, "sampler")
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def parents_engine(tiny_lm):
    """The same engine with the sampler its decode chunk had on the
    parent commit: the full formula a row under ``vmap``, no choice."""
    from kubeflow_tpu.models import generate

    def vmapped(logits, keys, temperature, top_k, live):
        return jax.vmap(
            lambda l, kk, t, tk: full_formula(l[None], kk, t, tk)[0]
        )(logits, keys, temperature, top_k)

    real, generate.sample_rows = generate.sample_rows, vmapped
    try:
        eng = make_engine(tiny_lm, "sampler-parent")
    finally:
        generate.sample_rows = real
    yield eng
    eng.close()


def serve(eng, requests, new=12):
    """Requests of (prompt, temperature, top_k, seed), enqueued at once
    so that they decode side by side; their tokens in that order."""
    reqs = [eng._make_request(p, new, t, k, s, None) for p, t, k, s in requests]
    eng._enqueue(reqs)
    return [r.result(60.0) for r in reqs]


PROMPTS = [[5, 9, 11, 3, 7], [2, 4], [1, 2, 3, 4, 5, 6, 7, 8, 9], [13, 14]]
MIXES = {
    "all_greedy": [(0.0, 0, 0)] * 4,
    "greedy_beside_sampled": [(0.0, 0, 0), (1.0, 0, 3), (0.7, 5, 4),
                              (0.0, 0, 0)],
    "greedy_beside_sampled_without_top_k": [(0.0, 0, 0), (1.0, 0, 3),
                                            (0.0, 9, 1), (0.6, -1, 8)],
    "all_sampled_with_top_k": [(0.9, 3, 1), (1.0, 8, 2), (0.7, 5, 4),
                               (1.2, 2, 5)],
}
# what each mix makes of the counters: (a step drew, a step sorted)
ENGAGES = {"all_greedy": (False, False),
           "greedy_beside_sampled": (True, True),
           "greedy_beside_sampled_without_top_k": (True, False),
           "all_sampled_with_top_k": (True, True)}


def counters(eng):
    value = lambda name: eng._reg().counter(name).value(model=eng.name)
    return np.array([value("kfx_lm_engine_chunks_total"),
                     value("kfx_lm_sample_steps_total"),
                     value("kfx_lm_sample_draw_steps_total"),
                     value("kfx_lm_sample_sort_steps_total")])


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_engine_serves_the_parents_tokens_request_by_request(
        tiny_lm, engine, parents_engine, mix):
    from kubeflow_tpu.models.generate import LMGenerator

    requests = [(p,) + knobs for p, knobs in zip(PROMPTS, MIXES[mix])]
    before = counters(engine)
    got = serve(engine, requests)
    chunks, steps, drew, sorted_ = counters(engine) - before
    assert got == serve(parents_engine, requests)
    # ... and the oracle's: a request is a row of its own seed
    cfg, params = tiny_lm
    gen = LMGenerator(cfg, params)
    assert got == [gen.generate([p], max_new_tokens=12, temperature=t,
                                top_k=k, seed=s)[0]
                   for p, t, k, s in requests]
    # counted on the device, a step: every step of every chunk, those
    # in which an active row drew, and those in which one also sorted
    assert chunks >= 3 and steps == chunks * engine.chunk_tokens
    assert (drew > 0, sorted_ > 0) == ENGAGES[mix]
    assert sorted_ <= drew <= steps


def test_a_retired_requests_knobs_do_not_engage_the_sort(engine):
    """A slot keeps the knobs of the request that left it: an all-greedy
    wave through slots that last held ``temperature 0.8, top_k 40``
    takes the argmax alone (the step's active mask gates the choice)."""
    serve(engine, [(p, 0.8, 40, 7 + i) for i, p in enumerate(PROMPTS)], new=5)
    assert (engine._temp > 0).all() and (engine._topk == 40).all()
    before = counters(engine)
    serve(engine, [(PROMPTS[0], 0.0, 0, 0), (PROMPTS[3], 0.0, 0, 0)])
    chunks, steps, drew, sorted_ = counters(engine) - before
    assert (engine._temp > 0).any() and steps == 4 * chunks > 0
    assert drew == 0 and sorted_ == 0
