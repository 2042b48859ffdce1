"""Multi-model weight pool (serving/weights.py + the engine's
per-request model selection): several full checkpoints time-share one
engine's HBM slots with refcounted LRU paging — scale-from-zero as a
measured weight SWAP. Pool unit coverage: acquire/release refcounts,
LRU victim order, pinned/in-flight slots never evicted (WeightSlotError
when every slot is worn), the idle sweep (scale-to-zero), evict-then-
reload byte-identity under a FRESH generation, v1/v2/int8 exports
coexisting in one f32 pool, and the ``weights.load`` chaos point.
Engine coverage: per-model greedy outputs byte-identical to dedicated
LMGenerator oracles (serial AND a concurrent mixed batch under slot
pressure), prefix chains invalidated on eviction, the timed-park idle
sweep, and the models=/adapters=/spec/role exclusion rules. The slow
fleet soak drives the same pool through LMPredictor + ModelServer:
"pooled but unloaded" readiness, per-request model selection over
HTTP, the operator's :evict push and a chaos load surfacing as 503."""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu import chaos

PROMPT = [5, 9, 11, 3, 7]
MODELS = ("m0", "m1", "m2")


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


@pytest.fixture(scope="module")
def exports(tiny_lm, tmp_path_factory):
    """Five exports sharing one architecture: m0/m1/m2 plain v2 f32
    (distinct seeds, so outputs VISIBLY differ), q8 an int8-quantized
    export, v1 an f32 export rewritten to the v1 on-disk format (no
    ``format_version``, no quant block). Returns (sources, params)."""
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.serving.lm_server import CONFIG_FILE, export_lm

    cfg, _ = tiny_lm
    root = tmp_path_factory.mktemp("models")
    sources, trees = {}, {}
    for i, name in enumerate(MODELS):
        p = TransformerLM(cfg).init(
            jax.random.PRNGKey(100 + i),
            jnp.zeros((1, 8), jnp.int32))["params"]
        trees[name] = p
        sources[name] = export_lm(str(root / name), cfg, p)
    p8 = TransformerLM(cfg).init(
        jax.random.PRNGKey(103), jnp.zeros((1, 8), jnp.int32))["params"]
    trees["q8"] = p8
    sources["q8"] = export_lm(str(root / "q8"), cfg, p8,
                              quantize="int8")
    pv1 = TransformerLM(cfg).init(
        jax.random.PRNGKey(104), jnp.zeros((1, 8), jnp.int32))["params"]
    trees["v1"] = pv1
    sources["v1"] = export_lm(str(root / "v1"), cfg, pv1)
    meta_path = root / "v1" / CONFIG_FILE
    meta = json.loads(meta_path.read_text())
    meta.pop("format_version", None)
    meta.pop("quant", None)
    meta["config"].pop("quant", None)
    meta_path.write_text(json.dumps(meta))
    return sources, trees


@pytest.fixture(scope="module")
def oracles(tiny_lm, exports):
    """Dedicated single-model generators — the acceptance references:
    a pooled model's greedy output must be byte-identical to what a
    dedicated engine over the same export would produce."""
    from kubeflow_tpu.models.generate import LMGenerator

    cfg, _ = tiny_lm
    _, trees = exports
    return {name: LMGenerator(cfg, trees[name]) for name in MODELS}


def _pool(tiny_lm, exports, names, n_slots, **kw):
    from kubeflow_tpu.serving.weights import WeightPool

    cfg, params = tiny_lm
    sources, _ = exports
    return WeightPool(cfg, params, n_slots,
                      {n: sources[n] for n in names}, **kw)


def _leaves(tree):
    from kubeflow_tpu.serving.weights import _tree_leaves_with_path

    return _tree_leaves_with_path(tree)


class TestWeightPoolUnit:
    def test_acquire_hit_miss_refcounts(self, tiny_lm, exports):
        pool = _pool(tiny_lm, exports, MODELS, 2)
        s1 = pool.acquire("m1")
        assert pool.loads == 1 and pool.ref[s1] == 1
        assert pool.loaded() == ["m1"]
        # Warm hit: same slot, no second artifact read, ref stacks.
        assert pool.acquire("m1") == s1
        assert pool.loads == 1 and pool.ref[s1] == 2
        pool.release(s1)
        pool.release(s1)
        assert pool.ref[s1] == 0
        assert pool.n_free == 2  # 1 free slot + 1 idle LRU candidate

    def test_lru_evicts_the_coldest_idle_model(self, tiny_lm, exports):
        pool = _pool(tiny_lm, exports, MODELS, 2)
        pool.release(pool.acquire("m1"))
        pool.release(pool.acquire("m2"))
        # m1 is now the LRU; paging m0 in must evict it, not m2.
        pool.release(pool.acquire("m0"))
        assert pool.loaded() == ["m0", "m2"]
        assert pool.evictions == 1

    def test_file_uri_sources_resolve(self, tiny_lm, exports):
        """Artifact URIs ride spec.models verbatim — the pool resolves
        them through the storage initializer at swap time, so file://
        (and remote schemes) page in exactly like bare paths."""
        from kubeflow_tpu.serving.weights import WeightPool

        cfg, params = tiny_lm
        sources, _ = exports
        pool = WeightPool(cfg, params, 2,
                          {"m1": "file://" + sources["m1"]})
        pool.release(pool.acquire("m1"))
        assert pool.loaded() == ["m1"] and pool.loads == 1

    def test_inflight_and_pinned_slots_are_never_victims(
            self, tiny_lm, exports):
        from kubeflow_tpu.serving.engine import WeightSlotError

        cfg, params = tiny_lm
        pool = _pool(tiny_lm, exports, MODELS, 2)
        pool.adopt("base", params, pin=True)
        s1 = pool.acquire("m1")  # the only swappable slot, held
        with pytest.raises(WeightSlotError):
            pool.acquire("m2")
        # A failed acquire must not leak state: the held slot still
        # resolves and the pool stays consistent.
        assert pool.acquire("m1") == s1 and pool.ref[s1] == 2
        # release_all (donated-death path) drops request pins but the
        # permanent residency flag survives.
        pool.release_all()
        assert pool.ref[s1] == 0 and bool(pool.pinned[0]) is True
        pool.release(pool.acquire("m2"))  # now m1 is evictable
        assert "base" in pool.loaded()
        assert not pool.evict_model("base")  # pinned: refused

    def test_evict_model_refuses_while_worn(self, tiny_lm, exports):
        pool = _pool(tiny_lm, exports, MODELS, 2)
        s1 = pool.acquire("m1")
        assert pool.evict_model("m1") is False  # in-flight
        pool.release(s1)
        assert pool.evict_model("m1") is True
        assert pool.evict_model("m1") is False  # already gone
        assert pool.loaded() == []

    def test_idle_sweep_is_scale_to_zero(self, tiny_lm, exports):
        pool = _pool(tiny_lm, exports, MODELS, 3)
        pool.release(pool.acquire("m1"))
        pool.release(pool.acquire("m2"))
        s0 = pool.acquire("m0")  # still worn: must survive the sweep
        for name in ("m1", "m2"):
            pool._last_used[pool._by_name[name]] -= 60.0
        out = pool.evict_idle(30.0, keep="m2")
        assert out == ["m1"]  # m2 kept (minReplicas=1), m0 worn
        assert pool.loaded() == ["m0", "m2"]
        pool.release(s0)
        assert pool.evict_idle(0.0) == []  # idle_s<=0: sweep disabled

    def test_unknown_model_is_a_load_error(self, tiny_lm, exports):
        from kubeflow_tpu.serving.engine import WeightLoadError

        pool = _pool(tiny_lm, exports, MODELS, 2)
        with pytest.raises(WeightLoadError, match="unknown model"):
            pool.acquire("nope")

    def test_evict_then_reload_is_byte_identical_fresh_generation(
            self, tiny_lm, exports):
        _, trees = exports
        dropped = []
        pool = _pool(tiny_lm, exports, MODELS, 2,
                     on_evict=lambda n, r: dropped.append((n, r)))
        s1 = pool.acquire("m1")
        root1 = pool.root(s1)
        first = [np.asarray(x) for _, x in _leaves(pool.tree(s1))]
        pool.release(s1)
        assert pool.evict_model("m1")
        assert dropped == [("m1", root1)]  # prefix hook saw the OLD root
        s1b = pool.acquire("m1")
        # Reload round-trips the export bit-for-bit...
        again = [np.asarray(x) for _, x in _leaves(pool.tree(s1b))]
        want = [np.asarray(x) for _, x in _leaves(trees["m1"])]
        for a, b, w in zip(first, again, want):
            assert np.array_equal(a, w) and np.array_equal(b, w)
        # ...but under a FRESH generation: chains built against the
        # evicted weights can never match the reloaded slot.
        assert pool.root(s1b) != root1
        assert pool.root(s1b).startswith(b"m1@")

    def test_v1_v2_and_int8_exports_coexist(self, tiny_lm, exports):
        """One f32 pool admits every format generation: a v1 export
        (no format_version), a v2 f32 export and an int8-quantized
        export (dequantized at load) all land as signature-identical
        f32 trees feeding the one compiled executable."""
        _, trees = exports
        pool = _pool(tiny_lm, exports, ("v1", "m1", "q8"), 3)
        slots = {n: pool.acquire(n) for n in ("v1", "m1", "q8")}
        assert pool.loaded() == ["m1", "q8", "v1"]
        for name in ("v1", "m1"):  # f32 paths: bit-exact round-trip
            got = [np.asarray(x)
                   for _, x in _leaves(pool.tree(slots[name]))]
            want = [np.asarray(x) for _, x in _leaves(trees[name])]
            for g, w in zip(got, want):
                assert np.array_equal(g, w), name
        # The int8 export was expanded to the pool's precision: every
        # leaf matches the pool signature (that's what admits it), and
        # the dequantized kernels are close to the original f32.
        q8 = {p: np.asarray(x)
              for p, x in _leaves(pool.tree(slots["q8"]))}
        src = {p: np.asarray(x) for p, x in _leaves(trees["q8"])}
        assert set(q8) == set(src)
        for p in q8:
            assert q8[p].dtype == src[p].dtype == np.float32, p
            np.testing.assert_allclose(q8[p], src[p], atol=0.05)

    def test_chaos_weights_load(self, tiny_lm, exports):
        from kubeflow_tpu.serving.engine import WeightLoadError

        pool = _pool(tiny_lm, exports, MODELS, 2, name="lmx")
        chaos.install(chaos.ChaosPlan(
            [chaos.Rule("weights.load", p=1.0, count=1)], seed=7))
        try:
            with pytest.raises(WeightLoadError, match="chaos"):
                pool.acquire("m1")
            # The reserved slot went back on the free list and no
            # half-loaded state remains...
            assert pool.loaded() == [] and pool.loads == 0
            assert pool.n_free == 2
            # ...and the budgeted fault (count=1) clears: the retry
            # pages in normally.
            pool.release(pool.acquire("m1"))
            assert pool.loads == 1
        finally:
            chaos.install(None)
        chaos.install(chaos.ChaosPlan(
            [chaos.Rule("weights.load", p=1.0, count=1,
                        delay=0.2, mode="delay")], seed=7))
        try:
            t0 = time.perf_counter()
            pool.release(pool.acquire("m2"))
            assert time.perf_counter() - t0 >= 0.2
        finally:
            chaos.install(None)

    def test_metric_families_seed_before_any_swap(
            self, tiny_lm, exports):
        """touch() makes every kfx_lm_weight_* family scrapeable
        pre-traffic, with per-model residency an explicit 0 — "pooled
        but unloaded" is a value, never an absent series."""
        from kubeflow_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        pool = _pool(tiny_lm, exports, MODELS, 2, name="lm",
                     registry=reg)
        pool.touch()
        assert reg.gauge("kfx_lm_weight_slots").value(model="lm") == 2
        assert reg.gauge("kfx_lm_weight_slots_free").value(
            model="lm") == 2
        for m in MODELS:
            assert reg.gauge("kfx_lm_weight_model_loaded").value(
                model="lm", pooled=m) == 0
        for reason in ("lru", "idle", "explicit"):
            assert reg.counter("kfx_lm_weight_evictions_total").value(
                model="lm", reason=reason) == 0
        pool.release(pool.acquire("m1"))
        pool.touch()
        assert reg.counter("kfx_lm_weight_loads_total").value(
            model="lm") == 1
        assert reg.gauge("kfx_lm_weight_model_loaded").value(
            model="lm", pooled="m1") == 1


class TestPrefixRootDrop:
    def test_drop_root_invalidates_only_that_models_chains(self):
        """Identical tokens under different roots never share a page,
        and dropping one root leaves the other's chains intact — the
        weight-pool eviction hook's contract."""
        from kubeflow_tpu.serving.engine import BlockManager, PrefixCache

        mgr = BlockManager(n_pages=8, page_size=4)
        cache = PrefixCache(mgr)
        toks = [1, 2, 3, 4]
        pa, pb = mgr.alloc(2)
        cache.insert_full(b"m1@1", toks, pa, root=b"m1@1")
        cache.insert_full(b"m2@2", toks, pb, root=b"m2@2")
        mgr.decref([pa, pb])  # the cache holds the only refs now
        pages, _, matched, _ = cache.match(toks, 4, root=b"m1@1")
        assert pages == [pa] and matched == 4
        assert cache.drop_root(b"m1@1") == [pa]  # page freed
        pages, _, matched, _ = cache.match(toks, 4, root=b"m1@1")
        assert pages == [] and matched == 0
        pages, _, _, _ = cache.match(toks, 4, root=b"m2@2")
        assert pages == [pb]  # the other model's chain survives
        assert mgr.n_free == 7


class TestMultiModelEngine:
    @pytest.fixture(scope="class")
    def engine(self, tiny_lm, exports):
        """Three pooled models over TWO weight slots (the pinned
        default + one swappable), so every cross-model test also
        exercises LRU paging and slot-pressure requeues."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, _ = tiny_lm
        sources, trees = exports
        eng = DecodeEngine(cfg, trees["m0"], n_slots=4,
                           chunk_tokens=4, name="lm",
                           kv_page_size=16, max_queue=64,
                           models={n: sources[n] for n in MODELS},
                           model_default="m0", weight_slots=2)
        yield eng
        eng.close()

    def test_per_model_greedy_matches_dedicated_engines(
            self, engine, oracles):
        for name in MODELS:
            want = oracles[name].generate([PROMPT],
                                          max_new_tokens=8)[0]
            got = engine.generate([PROMPT], max_new_tokens=8,
                                  model=name)[0]
            assert got == want, name
        # None/"" select the resident default (m0).
        base = oracles["m0"].generate([PROMPT], max_new_tokens=8)[0]
        assert engine.generate([PROMPT], max_new_tokens=8)[0] == base
        stats = engine.weight_stats()
        assert stats["slots"] == 2 and "m0" in stats["loaded"]
        assert stats["loads"] >= 2  # m1 and m2 each paged in

    def test_concurrent_mixed_batch_under_slot_pressure(
            self, engine, oracles):
        """Six in-flight requests across three models with ONE
        swappable slot: dispatch groups rows by weight slot, slot
        pressure requeues like KV-page exhaustion, and every output
        still matches its dedicated-engine oracle byte-for-byte."""
        plan = [MODELS[i % 3] for i in range(6)]
        reqs = [engine.submit(PROMPT, max_new_tokens=6, model=m)
                for m in plan]
        outs = [r.result(60.0) for r in reqs]
        for m, out in zip(plan, outs):
            want = oracles[m].generate([PROMPT], max_new_tokens=6)[0]
            assert out == want, m

    def test_evict_drops_prefix_chains_then_reload_is_identical(
            self, engine, oracles):
        want = oracles["m1"].generate([PROMPT], max_new_tokens=6)[0]
        for _ in range(2):  # second pass hits m1's prefix chains
            assert engine.generate([PROMPT], max_new_tokens=6,
                                   model="m1")[0] == want
        before = engine.weight_stats()["evictions"]
        assert engine.evict_model("m1") is True
        assert engine.weight_stats()["evictions"] == before + 1
        assert engine.pooled_models()["m1"] is False
        # Reload under a fresh generation: no stale prefix page can
        # pair with the swapped-in tree, output stays oracle-exact.
        assert engine.generate([PROMPT], max_new_tokens=6,
                               model="m1")[0] == want

    def test_model_selection_errors(self, engine):
        with pytest.raises(ValueError, match="unknown model"):
            engine.submit(PROMPT, max_new_tokens=4, model="nope")
        assert engine.evict_model("nope") is False
        assert engine.evict_model("m0") is False  # pinned default

    def test_pooled_models_accessor(self, engine):
        pooled = engine.pooled_models()
        assert set(pooled) == set(MODELS)
        assert pooled["m0"] is True  # the resident default

    def test_ctor_exclusions(self, tiny_lm, exports):
        """The pool's compatibility envelope fails fast: one
        executable serves every slot, so anything deriving from ONE
        checkpoint (draft model, LoRA factors, KV peers) is out."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm
        sources, _ = exports
        models = {n: sources[n] for n in MODELS}

        def build(**kw):
            DecodeEngine(cfg, params, n_slots=2, name="bad", **kw)

        with pytest.raises(ValueError, match="require models="):
            build(weight_slots=2)
        with pytest.raises(ValueError, match="model_default"):
            build(models=models)
        with pytest.raises(ValueError, match="not a configured"):
            build(models=models, model_default="zz")
        with pytest.raises(ValueError, match="speculative"):
            build(models=models, model_default="m0", draft_layers=1)
        with pytest.raises(ValueError, match="adapters"):
            build(models=models, model_default="m0",
                  adapters={"a": "/nope"}, adapter_rank=4)
        with pytest.raises(ValueError, match="role='mixed'"):
            build(models=models, model_default="m0", role="prefill")

    def test_idle_sweep_fires_on_a_parked_engine(
            self, tiny_lm, exports):
        """The replica-side scale-to-zero: a non-default model idle
        past model_idle_s loses its slot WITHOUT any new traffic —
        the decode loop's timed park keeps the sweep ticking."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, _ = tiny_lm
        sources, trees = exports
        eng = DecodeEngine(cfg, trees["m0"], n_slots=4,
                           chunk_tokens=4, name="lmz",
                           kv_page_size=16, max_queue=64,
                           models={n: sources[n] for n in MODELS},
                           model_default="m0", weight_slots=2,
                           model_idle_s=0.3)
        try:
            eng.generate([PROMPT], max_new_tokens=4, model="m1")
            assert eng.pooled_models()["m1"] is True
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if not eng.pooled_models()["m1"]:
                    break
                time.sleep(0.1)
            assert eng.pooled_models()["m1"] is False
            # The pinned default never scales to zero.
            assert eng.pooled_models()["m0"] is True
        finally:
            eng.close()


@pytest.mark.slow
class TestFleetSoak:
    """The full serving path: LMPredictor reads the operator's
    KFX_LM_MODELS env export, the server surfaces pooled readiness,
    per-request model selection rides :generate, the operator's
    scale-to-zero push rides :evict, and a chaos'd artifact load
    surfaces as 503 (wrong weights are never a degrade option)."""

    @pytest.fixture()
    def fleet(self, tiny_lm, exports, monkeypatch):
        from kubeflow_tpu.serving.lm_server import LMPredictor
        from kubeflow_tpu.serving.server import ModelServer

        sources, _ = exports
        monkeypatch.setenv("KFX_LM_MODELS", json.dumps(
            {n: sources[n] for n in MODELS}))
        monkeypatch.setenv("KFX_LM_MODEL_DEFAULT", "m0")
        monkeypatch.setenv("KFX_LM_WEIGHT_SLOTS", "2")
        p = LMPredictor(sources["m0"], name="lm")
        p.load()
        srv = ModelServer(port=0)
        srv.register(p)
        srv.start()
        yield srv, p
        # The background bucket-warm thread is a daemon; let it finish
        # before teardown so interpreter exit never races an XLA
        # compile (abort at shutdown).
        if p._warm_thread is not None:
            p._warm_thread.join(timeout=120)
        srv.stop()

    def _get(self, port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.load(r)

    def _post(self, port, path, body, timeout=60):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.load(r)

    def test_pool_over_http(self, fleet, oracles):
        srv, p = fleet
        # "Pooled but unloaded" readiness: m1 resolves to its hosting
        # predictor before any traffic ever touched it.
        body = self._get(srv.port, "/v1/models/m1")
        assert body["pooled"] is True and body["loaded"] is False
        assert body["host"] == "lm"
        # The host's own status carries the pool map.
        assert self._get(srv.port, "/v1/models/lm")[
            "pooledModels"] == {"m0": True, "m1": False, "m2": False}
        # Per-request model selection over HTTP, oracle-exact.
        want = oracles["m1"].generate([PROMPT], max_new_tokens=6)[0]
        out = self._post(srv.port, "/v1/models/lm:generate",
                         {"prompt_tokens": [PROMPT],
                          "max_new_tokens": 6, "model": "m1"})
        assert out["generated_tokens"][0] == want
        assert self._get(srv.port, "/v1/models/m1")["loaded"] is True
        # The operator's scale-to-zero push.
        out = self._post(srv.port, "/v1/models/lm:evict",
                         {"model": "m1"})
        assert out == {"model": "m1", "evicted": True}
        assert self._get(srv.port, "/v1/models/m1")["loaded"] is False
        # A chaos'd swap is a clean 503 + Retry-After, never a serve
        # on wrong weights; the budgeted fault clears and the retry
        # pages back in.
        chaos.install(chaos.ChaosPlan(
            [chaos.Rule("weights.load", p=1.0, count=1)], seed=3))
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(srv.port, "/v1/models/lm:generate",
                           {"prompt_tokens": [PROMPT],
                            "max_new_tokens": 4, "model": "m1"})
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After")
        finally:
            chaos.install(None)
        out = self._post(srv.port, "/v1/models/lm:generate",
                         {"prompt_tokens": [PROMPT],
                          "max_new_tokens": 6, "model": "m1"})
        assert out["generated_tokens"][0] == want
        # The weight families made it onto the server registry.
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics",
            timeout=30).read().decode()
        for fam in ("kfx_lm_weight_slots", "kfx_lm_weight_slots_free",
                    "kfx_lm_weight_swap_seconds",
                    "kfx_lm_weight_evictions_total",
                    "kfx_lm_weight_model_loaded"):
            assert fam in metrics, fam
