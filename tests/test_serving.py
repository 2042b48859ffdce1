"""Serving stack tests: V1 protocol server, bucketed jit predict,
micro-batcher, router canary split, and the InferenceService operator
end-to-end (train -> export -> apply -> predict -> canary)."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

PY = sys.executable


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.load(resp)


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.load(resp)


@pytest.fixture(scope="module")
def export_dir(tmp_path_factory):
    """Train a tiny mlp and export it once for all serving tests."""
    import jax

    from kubeflow_tpu.data import get_dataset
    from kubeflow_tpu.models import get_model
    from kubeflow_tpu.serving.export import export_params
    from kubeflow_tpu.training import TrainLoop

    out = tmp_path_factory.mktemp("export")
    ds = get_dataset("mnist")
    model = get_model("mlp", num_classes=ds.num_classes)
    loop = TrainLoop(model)
    state = loop.init_state(ds.shape)
    for images, labels in ds.batches(128, steps=20):
        state, *_ = loop.train_step(state, images, labels)
    export_params(str(out), "mlp", ds.shape, ds.num_classes, state)
    return str(out)


class TestTorchServing:
    """pytorch-server parity: a TorchScript export behind the same V1
    protocol and InferenceService operator (framework auto-sniffed from
    the export format)."""

    @pytest.fixture(scope="class")
    def torch_export(self, tmp_path_factory):
        import torch

        from kubeflow_tpu.serving.torch_server import export_torchscript

        torch.manual_seed(0)
        module = torch.nn.Sequential(
            torch.nn.Flatten(), torch.nn.Linear(16, 8), torch.nn.ReLU(),
            torch.nn.Linear(8, 3))
        out = tmp_path_factory.mktemp("torch-export")
        export_torchscript(str(out), module, input_shape=(4, 4),
                           num_classes=3)
        return str(out)

    def test_predictor_direct(self, torch_export):
        from kubeflow_tpu.serving.torch_server import TorchPredictor

        p = TorchPredictor(torch_export, name="t")
        p.load()
        assert p.ready and p.input_shape == (4, 4)
        out = p.predict(np.zeros((5, 4, 4), np.float32),
                        probabilities=True)
        assert len(out["predictions"]) == 5
        assert np.allclose(np.sum(out["probabilities"], axis=-1), 1.0,
                           atol=1e-5)

    def test_isvc_e2e(self, torch_export, tmp_path):
        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: torchy
spec:
  predictor:
    minReplicas: 1
    pytorch:
      storageUri: file://{torch_export}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "torchy",
                                         "Ready", timeout=120)
            url = isvc.status["url"]
            x = np.zeros((2, 4, 4), np.float32)
            status, body = _post(f"{url}/v1/models/torchy:predict",
                                 {"instances": x.tolist()}, timeout=60)
            assert status == 200 and len(body["predictions"]) == 2


class TestSKLearnServing:
    """sklearn-server parity: a joblib export behind the same V1
    protocol and InferenceService operator (framework auto-sniffed from
    the export format)."""

    @pytest.fixture(scope="class")
    def sklearn_export(self, tmp_path_factory):
        from sklearn.linear_model import LogisticRegression

        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.serving.sklearn_server import export_sklearn

        ds = get_dataset("mnist")
        images, labels = next(ds.batches(512))
        est = LogisticRegression(max_iter=50)
        est.fit(images.reshape(len(images), -1), labels)
        out = tmp_path_factory.mktemp("sk-export")
        export_sklearn(str(out), est, input_shape=ds.shape,
                       num_classes=ds.num_classes)
        return str(out)

    def test_predictor_direct(self, sklearn_export):
        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.serving.sklearn_server import SKLearnPredictor

        p = SKLearnPredictor(sklearn_export, name="sk")
        p.load()
        assert p.ready and p.input_shape == (28, 28, 1)
        ds = get_dataset("mnist", split="eval")
        images, labels = ds.eval_arrays(64)
        out = p.predict(images, probabilities=True)
        assert (np.asarray(out["predictions"]) == labels).mean() > 0.5
        assert np.allclose(np.sum(out["probabilities"], axis=-1), 1.0,
                           atol=1e-5)

    def test_isvc_e2e(self, sklearn_export, tmp_path):
        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: sk
spec:
  predictor:
    minReplicas: 1
    sklearn:
      storageUri: file://{sklearn_export}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "sk",
                                         "Ready", timeout=120)
            url = isvc.status["url"]
            x = np.zeros((2, 28, 28, 1), np.float32)
            status, body = _post(f"{url}/v1/models/sk:predict",
                                 {"instances": x.tolist()}, timeout=60)
            assert status == 200 and len(body["predictions"]) == 2


class TestModelServer:
    @pytest.fixture(scope="class")
    def server(self, export_dir):
        from kubeflow_tpu.serving.server import JaxPredictor, ModelServer

        predictor = JaxPredictor(export_dir, name="mnist", max_batch_size=16)
        predictor.load()
        srv = ModelServer(port=0)
        srv.register(predictor)
        srv.start()
        yield srv
        srv.stop()

    def test_v1_protocol_surface(self, server):
        base = f"http://127.0.0.1:{server.port}"
        assert _get(f"{base}/healthz")[0] == 200
        status, body = _get(f"{base}/v1/models")
        assert status == 200 and body["models"] == ["mnist"]
        status, body = _get(f"{base}/v1/models/mnist")
        assert status == 200 and body["ready"] is True

    def test_predict_correctness(self, server, export_dir):
        from kubeflow_tpu.data import get_dataset

        ds = get_dataset("mnist", split="eval")
        images, labels = ds.eval_arrays(32)
        base = f"http://127.0.0.1:{server.port}"
        status, body = _post(f"{base}/v1/models/mnist:predict",
                             {"instances": images.tolist()})
        assert status == 200
        preds = np.asarray(body["predictions"])
        assert preds.shape == (32,)
        # trained model beats chance comfortably
        assert (preds == labels).mean() > 0.5
        # probabilities are opt-in (V1 response carries predictions only)
        assert "probabilities" not in body
        status, body = _post(f"{base}/v1/models/mnist:predict",
                             {"instances": images.tolist(),
                              "probabilities": True})
        assert status == 200
        assert len(body["probabilities"][0]) == ds.num_classes

    def test_bucket_padding_odd_batch(self, server):
        base = f"http://127.0.0.1:{server.port}"
        x = np.zeros((3, 28, 28, 1), np.float32)
        status, body = _post(f"{base}/v1/models/mnist:predict",
                             {"instances": x.tolist()})
        assert status == 200 and len(body["predictions"]) == 3

    def test_errors(self, server):
        base = f"http://127.0.0.1:{server.port}"
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/v1/models/nope:predict", {"instances": [[0.0]]})
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/v1/models/mnist:predict", {"wrong": 1})
        assert e.value.code == 400

    @pytest.mark.slow
    def test_vit_exports_and_serves(self, tmp_path):
        """Every registry classifier rides the same export -> predictor
        contract; prove it for the transformer family (ViT), not just
        conv nets."""
        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.serving.export import export_params
        from kubeflow_tpu.serving.server import JaxPredictor
        from kubeflow_tpu.training import TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("vit", num_classes=ds.num_classes))
        state = loop.init_state(ds.shape)
        for images, labels in ds.batches(128, steps=2):
            state, *_ = loop.train_step(state, images, labels)
        out = str(tmp_path / "vit-export")
        export_params(out, "vit", ds.shape, ds.num_classes, state)
        p = JaxPredictor(out, name="vit", max_batch_size=4)
        p.load()
        xe, _ = get_dataset("mnist", split="eval").eval_arrays(64)
        preds = np.asarray(p.predict(xe)["predictions"])
        assert preds.shape == (64,)
        # Served predictions must match the in-process forward exactly
        # (serving correctness, independent of how trained the model is).
        import jax.numpy as jnp

        model = get_model("vit", num_classes=ds.num_classes)
        direct = np.asarray(jnp.argmax(model.apply(
            {"params": state.params}, jnp.asarray(xe)), -1))
        assert (preds == direct).mean() > 0.95  # bf16 ties may flip

    def test_metrics_prometheus_and_json(self, server):
        import urllib.request

        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "# TYPE kfx_serving_requests_total counter" in text
        assert "kfx_serving_models 1" in text
        assert "kfx_serving_models_ready 1" in text
        status, body = _get(f"{base}/metrics?format=json")
        assert status == 200 and body["models"] == ["mnist"]

    @pytest.mark.parametrize("stats, lines", [
        # the CPU backend reports none: the family is absent
        (None, []),
        ({"bytes_in_use": 7, "peak_bytes_in_use": 9, "bytes_limit": 16,
          "num_allocs": 3},
         ['kfx_device_memory_bytes{kind="in_use"} 7',
          'kfx_device_memory_bytes{kind="peak"} 9',
          'kfx_device_memory_bytes{kind="limit"} 16']),
        # a backend that reports only part of them
        ({"bytes_in_use": 5},
         ['kfx_device_memory_bytes{kind="in_use"} 5']),
    ], ids=["none", "all", "partial"])
    def test_device_memory_gauge_is_read_at_scrape_time(
            self, server, monkeypatch, stats, lines):
        import jax

        class _Device:
            def memory_stats(self):
                return stats

        monkeypatch.setattr(jax, "local_devices", lambda: [_Device()])
        server.metrics.gauge("kfx_device_memory_bytes", "").clear()
        text = server.metrics.render()
        got = [l for l in text.splitlines()
               if l.startswith("kfx_device_memory_bytes")]
        assert sorted(got) == sorted(lines)


class TestMicroBatcher:
    def test_concurrent_requests_batched(self, export_dir):
        import threading

        from kubeflow_tpu.serving.server import JaxPredictor, MicroBatcher

        predictor = JaxPredictor(export_dir, name="m", max_batch_size=32)
        predictor.load()
        calls = []
        orig = predictor.predict

        def spy(instances, probabilities=False):
            calls.append(instances.shape[0])
            return orig(instances, probabilities=probabilities)

        predictor.predict = spy
        batcher = MicroBatcher(predictor, max_batch_size=32,
                               max_latency_ms=50.0)
        results = [None] * 8

        def hit(i):
            x = np.zeros((1, 28, 28, 1), np.float32)
            results[i] = batcher.predict(x)

        threads = [threading.Thread(target=hit, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batcher.close()
        assert all(r is not None and len(r["predictions"]) == 1
                   for r in results)
        # far fewer device dispatches than requests
        assert len(calls) < 8
        assert sum(calls) == 8

    def test_bad_shape_does_not_kill_batcher(self, export_dir):
        """A request with a mismatched instance shape errors out cleanly
        and the batcher keeps serving subsequent requests."""
        from kubeflow_tpu.serving.server import JaxPredictor, MicroBatcher

        predictor = JaxPredictor(export_dir, name="m", max_batch_size=8)
        predictor.load()
        batcher = MicroBatcher(predictor, max_batch_size=8,
                               max_latency_ms=1.0, reply_timeout_s=10.0)
        try:
            with pytest.raises(ValueError):
                batcher.predict(np.zeros((1, 7, 7, 1), np.float32))
            out = batcher.predict(np.zeros((2, 28, 28, 1), np.float32))
            assert len(out["predictions"]) == 2
        finally:
            batcher.close()

    def test_pipelined_workers_serve_all_requests(self, export_dir):
        """workers=2 (two batcher threads pipelining device dispatches
        into the transport's sync floor): every request still gets its
        own correct-length reply — per-request reply queues make the
        interleaving safe."""
        import threading

        from kubeflow_tpu.serving.server import JaxPredictor, MicroBatcher

        predictor = JaxPredictor(export_dir, name="m", max_batch_size=8)
        predictor.load()
        batcher = MicroBatcher(predictor, max_batch_size=8,
                               max_latency_ms=2.0, workers=2)
        results = [None] * 24

        def hit(i):
            n = 1 + (i % 3)
            x = np.zeros((n, 28, 28, 1), np.float32)
            results[i] = (n, batcher.predict(x))

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batcher.close()
        assert all(r is not None and len(r[1]["predictions"]) == r[0]
                   for r in results), results

    def test_close_joins_workers_and_drains_queue(self):
        """close() must resolve every outstanding request: the in-flight
        batch gets its reply, a queued request behind it gets an
        immediate error, and a racing predict() after close fails fast —
        none of them may stall until reply_timeout_s (round-5 advisor
        finding)."""
        import threading
        import time

        from kubeflow_tpu.serving.server import MicroBatcher, Predictor

        class Slow(Predictor):
            name = "slow"
            ready = True

            def load(self):
                pass

            def predict(self, instances, probabilities=False):
                time.sleep(0.3)
                return {"predictions": [0] * instances.shape[0]}

        batcher = MicroBatcher(Slow(), max_batch_size=1,
                               max_latency_ms=1.0, reply_timeout_s=60.0)
        outcomes = {}

        def hit(tag):
            try:
                outcomes[tag] = batcher.predict(
                    np.zeros((1, 2), np.float32))
            except Exception as e:
                outcomes[tag] = e

        t1 = threading.Thread(target=hit, args=("inflight",))
        t1.start()
        time.sleep(0.1)  # worker is inside the slow predict
        t2 = threading.Thread(target=hit, args=("queued",))
        t2.start()
        time.sleep(0.1)  # second request is parked on the queue
        t0 = time.monotonic()
        batcher.close()
        t1.join(timeout=10)
        t2.join(timeout=10)
        elapsed = time.monotonic() - t0
        assert elapsed < 10, "close/drain stalled toward reply_timeout_s"
        assert outcomes["inflight"] == {"predictions": [0]}
        assert isinstance(outcomes["queued"], RuntimeError)
        with pytest.raises(RuntimeError):
            batcher.predict(np.zeros((1, 2), np.float32))

    def test_non_pow2_max_batch_is_a_bucket(self, export_dir):
        from kubeflow_tpu.serving.server import JaxPredictor

        p = JaxPredictor(export_dir, name="m", max_batch_size=48)
        p.load()
        assert 48 in p._buckets
        out = p.predict(np.zeros((48, 28, 28, 1), np.float32))
        assert len(out["predictions"]) == 48


class TestRouter:
    def test_canary_split_and_cold(self):
        from kubeflow_tpu.serving.router import Router
        from kubeflow_tpu.serving.server import ModelServer, Predictor

        class Echo(Predictor):
            def __init__(self, name, tag):
                self.name = name
                self.tag = tag
                self.ready = True

            def load(self):
                pass

            def predict(self, instances, probabilities=False):
                return {"predictions": [self.tag] * instances.shape[0]}

        s1 = ModelServer(port=0)
        s1.register(Echo("m", "default"))
        s1.start()
        s2 = ModelServer(port=0)
        s2.register(Echo("m", "canary"))
        s2.start()
        router = Router().start()
        try:
            # cold: no backends yet
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"http://127.0.0.1:{router.port}/v1/models/m:predict",
                      {"instances": [[0.0]]})
            assert e.value.code == 503
            router.default.set_endpoints([f"127.0.0.1:{s1.port}"])
            router.canary.set_endpoints([f"127.0.0.1:{s2.port}"])
            router.canary_percent = 30
            tags = []
            for _ in range(200):
                _, body = _post(
                    f"http://127.0.0.1:{router.port}/v1/models/m:predict",
                    {"instances": [[0.0]]})
                tags.append(body["predictions"][0])
            frac = tags.count("canary") / len(tags)
            assert 0.15 < frac < 0.45, frac
        finally:
            router.stop()
            s1.stop()
            s2.stop()

    def test_forwards_headers(self):
        """The proxy passes client request headers to the backend and
        mirrors backend response headers (minus hop-by-hop)."""
        import threading
        from http.server import BaseHTTPRequestHandler, HTTPServer

        from kubeflow_tpu.serving.router import Router

        seen = {}

        class Backend(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                seen.update(self.headers.items())
                body = b"{}"
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("X-Model-Revision", "rev-7")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        backend = HTTPServer(("127.0.0.1", 0), Backend)
        threading.Thread(target=backend.serve_forever, daemon=True).start()
        router = Router().start()
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{backend.server_port}"])
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}/v1/models/m",
                headers={"Authorization": "Bearer tok",
                         "X-Custom": "yes"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["X-Model-Revision"] == "rev-7"
            assert seen.get("Authorization") == "Bearer tok"
            assert seen.get("X-Custom") == "yes"
        finally:
            router.stop()
            backend.shutdown()


class TestQuantEnvPlumbing:
    def test_quantization_spec_exports_env(self):
        """spec.predictor.quantization -> the replica's KFX_LM_QUANT /
        KFX_LM_KV_QUANT env (the knobs LMPredictor reads at load):
        int8 opts in, f32 is the manifest-level escape hatch (exported
        as the predictor's "0"), absent fields export nothing, and
        non-predictor roles export nothing."""
        from kubeflow_tpu.operators.serving import _Revision

        rev = _Revision(name="default", model_name="m", model_dir="d",
                        workdir="w", batcher=None,
                        quantization={"weights": "int8", "kv": "int8"})
        env: dict = {}
        rev._quant_env(env)
        assert env == {"KFX_LM_QUANT": "int8",
                       "KFX_LM_KV_QUANT": "int8"}
        env = {}
        rev.quantization = {"weights": "f32"}
        rev._quant_env(env)
        assert env == {"KFX_LM_QUANT": "0"}
        env = {}
        rev.quantization = {"kv": "f32"}
        rev._quant_env(env)
        assert env == {"KFX_LM_KV_QUANT": "0"}
        env = {}
        rev.quantization = None
        rev._quant_env(env)
        assert env == {}
        rev.quantization = {"weights": "int8"}
        rev.role = "transformer"
        env = {}
        rev._quant_env(env)
        assert env == {}


class TestPrefillEnvPlumbing:
    def test_prefill_chunk_spec_exports_env(self):
        """spec.predictor.prefillChunkTokens -> the replica's
        KFX_LM_PREFILL_CHUNK env (the chunked-prefill knob LMPredictor
        reads): only an explicit field exports (the predictor owns the
        default), 0 exports as the monolithic escape hatch, and
        non-predictor roles export nothing."""
        from kubeflow_tpu.operators.serving import _Revision

        rev = _Revision(name="default", model_name="m", model_dir="d",
                        workdir="w", batcher=None, prefill_chunk=128)
        env: dict = {}
        rev._prefill_env(env)
        assert env == {"KFX_LM_PREFILL_CHUNK": "128"}
        env = {}
        rev.prefill_chunk = 0
        rev._prefill_env(env)
        assert env == {"KFX_LM_PREFILL_CHUNK": "0"}
        env = {}
        rev.prefill_chunk = None
        rev._prefill_env(env)
        assert env == {}
        rev.prefill_chunk = 64
        rev.role = "explainer"
        env = {}
        rev._prefill_env(env)
        assert env == {}


class TestAdapterEnvPlumbing:
    def test_adapters_spec_exports_env(self):
        """spec.predictor.adapters -> the replica's KFX_LM_ADAPTER*
        env (the multi-tenant LoRA knobs LMPredictor reads at load):
        the artifacts map rides as JSON, the optional knobs export
        only when explicit (the predictor owns the defaults), and
        non-predictor roles export nothing."""
        import json as _json

        from kubeflow_tpu.operators.serving import _Revision

        rev = _Revision(name="default", model_name="m", model_dir="d",
                        workdir="w", batcher=None,
                        adapters={"artifacts": {"a": "file:///ad/a"},
                                  "default": "a", "slots": 4,
                                  "rank": 8, "fallback": "error"})
        env: dict = {}
        rev._adapter_env(env)
        assert _json.loads(env["KFX_LM_ADAPTERS"]) == {
            "a": "file:///ad/a"}
        assert env["KFX_LM_ADAPTER_DEFAULT"] == "a"
        assert env["KFX_LM_ADAPTER_SLOTS"] == "4"
        assert env["KFX_LM_ADAPTER_RANK"] == "8"
        assert env["KFX_LM_ADAPTER_FALLBACK"] == "error"
        env = {}
        rev.adapters = {"artifacts": {"a": "file:///ad/a"}}
        rev._adapter_env(env)
        assert set(env) == {"KFX_LM_ADAPTERS"}
        env = {}
        rev.adapters = None
        rev._adapter_env(env)
        assert env == {}
        rev.adapters = {"artifacts": {"a": "file:///ad/a"}}
        rev.role = "transformer"
        env = {}
        rev._adapter_env(env)
        assert env == {}


class TestModelsEnvPlumbing:
    def test_models_spec_exports_env(self):
        """spec.predictor.models -> the replica's KFX_LM_MODELS /
        KFX_LM_MODEL_DEFAULT / KFX_LM_WEIGHT_* env (the multi-model
        weight-pool knobs LMPredictor reads at load): the artifacts
        map rides as JSON with the default model's name, slots/
        idleSeconds export only when explicit, and non-predictor
        roles export nothing."""
        import json as _json

        from kubeflow_tpu.operators.serving import _Revision

        rev = _Revision(name="default", model_name="m", model_dir="d",
                        workdir="w", batcher=None,
                        models={"artifacts": {"m0": "file:///m/m0",
                                              "m1": "file:///m/m1"},
                                "default": "m0", "slots": 2,
                                "idleSeconds": 600})
        env: dict = {}
        rev._models_env(env)
        assert _json.loads(env["KFX_LM_MODELS"]) == {
            "m0": "file:///m/m0", "m1": "file:///m/m1"}
        assert env["KFX_LM_MODEL_DEFAULT"] == "m0"
        assert env["KFX_LM_WEIGHT_SLOTS"] == "2"
        assert env["KFX_LM_WEIGHT_IDLE_S"] == "600.0"
        env = {}
        rev.models = {"artifacts": {"m0": "file:///m/m0"},
                      "default": "m0"}
        rev._models_env(env)
        assert set(env) == {"KFX_LM_MODELS", "KFX_LM_MODEL_DEFAULT"}
        env = {}
        rev.models = None
        rev._models_env(env)
        assert env == {}
        rev.models = {"artifacts": {"m0": "file:///m/m0"},
                      "default": "m0"}
        rev.role = "transformer"
        env = {}
        rev._models_env(env)
        assert env == {}

    def test_fmt_pooled_column(self):
        """`kfx get isvc`'s POOLED column renders status.pooledModels:
        resident names plain, pooled-but-unloaded parenthesized,
        loaded-anywhere wins across revisions."""
        from kubeflow_tpu.cli import _fmt_pooled

        assert _fmt_pooled({}) == "-"
        assert _fmt_pooled(
            {"default": {"m0": True, "m1": False}}) == "m0,(m1)"
        # A model loaded on ANY revision renders resident.
        assert _fmt_pooled(
            {"default": {"m1": False},
             "canary": {"m1": True}}) == "m1"


@pytest.mark.slow
class TestInferenceServiceE2E:
    def test_speculative_spec_exports_env(self):
        """spec.predictor.speculative -> the replica's KFX_LM_SPEC_*
        env (the knobs LMPredictor reads at load); classifier-graph
        roles and absent blocks export nothing, and enabled:false is
        the manifest-level escape hatch."""
        from kubeflow_tpu.operators.serving import _Revision

        rev = _Revision(name="default", model_name="m", model_dir="d",
                        workdir="w", batcher=None,
                        speculative={"draftLayers": 3,
                                     "proposeTokens": 6})
        env: dict = {}
        rev._spec_env(env)
        assert env == {"KFX_LM_SPEC_LAYERS": "3",
                       "KFX_LM_SPEC_TOKENS": "6"}
        env = {}
        rev.speculative = {"enabled": False}
        rev._spec_env(env)
        assert env == {"KFX_LM_SPEC": "0"}
        env = {}
        rev.speculative = None
        rev._spec_env(env)
        assert env == {}
        rev.speculative = {"draftLayers": 3}
        rev.role = "transformer"
        env = {}
        rev._spec_env(env)
        assert env == {}

    def test_apply_predict_canary_update(self, export_dir, tmp_path):
        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: mnist
spec:
  predictor:
    minReplicas: 1
    jax:
      storageUri: file://{export_dir}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "mnist",
                                         "Ready", timeout=120)
            url = isvc.status["url"]
            x = np.zeros((2, 28, 28, 1), np.float32)
            status, body = _post(f"{url}/v1/models/mnist:predict",
                                 {"instances": x.tolist()}, timeout=60)
            assert status == 200 and len(body["predictions"]) == 2

            # Add a canary revision at 50% using the same export.
            fresh = cp.store.get("InferenceService", "mnist")
            fresh.spec["canary"] = {"minReplicas": 1,
                                    "jax": {"storageUri": export_dir}}
            fresh.spec["canaryTrafficPercent"] = 50
            cp.store.update(fresh)
            import time

            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "mnist")
                if cur.status.get("readyReplicas", {}).get("canary"):
                    break
                time.sleep(0.2)
            else:
                raise AssertionError("canary never became ready")
            status, _ = _post(f"{url}/v1/models/mnist:predict",
                              {"instances": x.tolist()}, timeout=60)
            assert status == 200

    def test_custom_predictor_container(self, tmp_path):
        """KFServing custom-predictor parity (SURVEY.md §2.1 KFServing
        row): spec.predictor.containers[0] runs a user command that owns
        the port; the operator supervises it, probes readiness, and the
        router serves its traffic like any framework server."""
        import textwrap
        import time

        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        script = textwrap.dedent("""
            import json, os
            from http.server import BaseHTTPRequestHandler, HTTPServer

            name = os.environ["KFX_MODEL_NAME"]

            class H(BaseHTTPRequestHandler):
                def log_message(self, *a):
                    pass
                def _send(self, obj):
                    body = json.dumps(obj).encode()
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                def do_GET(self):
                    self._send({"ready": True, "name": name})
                def do_POST(self):
                    n = int(self.headers.get("Content-Length") or 0)
                    req = json.loads(self.rfile.read(n))
                    self._send({"predictions": [
                        sum(row) for row in req["instances"]]})

            HTTPServer(("127.0.0.1", int(os.environ["KFX_PORT"])),
                       H).serve_forever()
        """)
        path = tmp_path / "custom_server.py"
        path.write_text(script)
        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: custom-echo
spec:
  predictor:
    minReplicas: 1
    containers:
    - name: server
      command: ["{sys.executable}", "{path}"]
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "custom-echo",
                                         "Ready", timeout=60)
            url = isvc.status["url"]
            status, body = _post(f"{url}/v1/models/custom-echo:predict",
                                 {"instances": [[1, 2], [3, 4]]},
                                 timeout=30)
            assert status == 200 and body["predictions"] == [3, 7]

    def test_custom_predictor_spawn_failure_surfaces(self, tmp_path):
        """A typo'd custom command must become a SpawnFailed event and a
        NotReady service, never a reconcile crash loop."""
        import time

        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = """
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: typo
spec:
  predictor:
    minReplicas: 1
    containers:
    - name: server
      command: ["/no/such/binary-kfx-test"]
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                evs = [e for e in cp.store.events_for(
                    "InferenceService", "default/typo")
                    if e.reason == "SpawnFailed"]
                if evs:
                    break
                time.sleep(0.2)
            assert evs, "no SpawnFailed event"
            assert "binary-kfx-test" in evs[0].message
            cur = cp.store.get("InferenceService", "typo")
            assert not cur.has_condition("Ready")

    def test_inferenceservice_survives_controlplane_restart(
            self, export_dir, tmp_path):
        """A journaled control plane restart must bring an
        InferenceService back to Ready with working predicts: the
        resource replays from sqlite and the operator re-launches the
        server processes (the old ones died with the plane)."""
        import time

        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        home = str(tmp_path / "kfx")
        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: revive
spec:
  predictor:
    minReplicas: 1
    jax:
      storageUri: file://{export_dir}
"""
        x = np.zeros((2, 28, 28, 1), np.float32)
        with ControlPlane(home=home, journal=True) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "revive",
                                         "Ready", timeout=120)
            status, _ = _post(f"{isvc.status['url']}/v1/models/"
                              f"revive:predict",
                              {"instances": x.tolist()}, timeout=60)
            assert status == 200
        with ControlPlane(home=home, journal=True) as cp:
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "revive")
                url = cur.status.get("url")
                if url and cur.has_condition("Ready"):
                    try:
                        status, body = _post(
                            f"{url}/v1/models/revive:predict",
                            {"instances": x.tolist()}, timeout=30)
                        if status == 200:
                            break
                    except Exception:
                        pass
                time.sleep(0.3)
            else:
                raise AssertionError(
                    "InferenceService never served after restart")
            assert len(body["predictions"]) == 2

    def test_concurrency_autoscale_up_and_down(self, export_dir, tmp_path):
        """KPA analogue: concurrent traffic grows replicas toward
        maxReplicas; after the damping window they fall back to min."""
        import threading
        import time

        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: kpa
spec:
  predictor:
    minReplicas: 1
    maxReplicas: 3
    targetConcurrency: 1
    scaleDownWindowSeconds: 60
    jax:
      storageUri: file://{export_dir}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "kpa", "Ready",
                                         timeout=120)
            url = isvc.status["url"]
            x = np.zeros((4, 28, 28, 1), np.float32).tolist()
            # Pre-encode ONCE: per-request json.dumps of ~3k floats under
            # the GIL costs ~10x the server's inference time on a 1-core
            # host, so encoding in the hammer loop serializes the clients
            # and in-flight concurrency at the router never reaches 2 —
            # the autoscaler then correctly refuses to scale. The test's
            # subject is the KPA, not client-side JSON throughput.
            body = json.dumps({"instances": x}).encode()

            stop = threading.Event()
            deadline = time.monotonic() + 45

            def hammer():
                while not stop.is_set() and time.monotonic() < deadline:
                    try:
                        req = urllib.request.Request(
                            f"{url}/v1/models/kpa:predict", data=body,
                            headers={"Content-Type": "application/json"})
                        with urllib.request.urlopen(req, timeout=30) as r:
                            r.read()
                    except Exception:
                        time.sleep(0.1)

            threads = [threading.Thread(target=hammer) for _ in range(6)]
            for t in threads:
                t.start()
            grown = 0
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "kpa")
                # The autoscaler's decision is status.replicas (spawned):
                # on a 1-core host the hammer threads starve a NEW
                # replica's model load, so readiness during full load is
                # a host property, not a KPA property.
                grown = max(grown, cur.status.get(
                    "replicas", {}).get("default", 0))
                if grown >= 2:
                    break
                time.sleep(0.3)
            stop.set()  # end the load phase as soon as scale-up is seen
            for t in threads:
                t.join()
            assert grown >= 2, f"never scaled past 1 (saw {grown})"

            # With the load gone the CPU is free: inside the 60s damping
            # window the scaled-up replica must finish its model load
            # (jax import + the placement probe's compiles dominate) and
            # turn READY — covering the spawn->ready path the loaded-host
            # phase cannot.
            deadline = time.monotonic() + 55
            ready_grown = 0
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "kpa")
                ready_grown = max(ready_grown, cur.status.get(
                    "readyReplicas", {}).get("default", 0))
                if ready_grown >= 2:
                    break
                time.sleep(0.3)
            assert ready_grown >= 2, \
                f"scaled-up replica never became ready (saw {ready_grown})"

            deadline = time.monotonic() + 110
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "kpa")
                if cur.status.get("replicas", {}).get("default") == 1:
                    break
                time.sleep(0.5)
            final = cp.store.get("InferenceService", "kpa").status
            assert final["replicas"]["default"] == 1, \
                "never scaled back down"
            assert final["readyReplicas"]["default"] == 1

    def test_scale_to_zero_round_trip(self, export_dir, tmp_path):
        """minReplicas=0: cold request scales 0->1, idle scales 1->0."""
        import time

        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: ztest
spec:
  predictor:
    minReplicas: 0
    scaleToZeroIdleSeconds: 2
    jax:
      storageUri: file://{export_dir}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            deadline = time.monotonic() + 60
            url = None
            while time.monotonic() < deadline and url is None:
                cur = cp.store.get("InferenceService", "ztest")
                url = cur.status.get("url")
                time.sleep(0.1)
            assert url, "router url never published"
            x = np.zeros((1, 28, 28, 1), np.float32)

            # Cold requests 503 until the activator has spawned a replica.
            deadline = time.monotonic() + 120
            status = None
            while time.monotonic() < deadline:
                try:
                    status, body = _post(f"{url}/v1/models/ztest:predict",
                                         {"instances": x.tolist()},
                                         timeout=30)
                    break
                except urllib.error.HTTPError as e:
                    assert e.code == 503
                    time.sleep(0.5)
            assert status == 200 and len(body["predictions"]) == 1

            # After the idle window the revision must drop back to zero.
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "ztest")
                if cur.status.get("readyReplicas", {}).get("default") == 0:
                    break
                time.sleep(0.3)
            else:
                raise AssertionError("never scaled back to zero")


TRANSFORMER_MODULE = '''
import numpy as np


def preprocess(instances):
    # Undo the client's 0-255 encoding: the predictor was trained on
    # unit-scaled pixels.
    return (np.asarray(instances, dtype="float32") / 255.0).tolist()


def postprocess(predictions):
    return [{"label": int(p)} for p in predictions]
'''


class TestInferenceGraph:
    """Transformer + explainer components chained by the router
    (SURVEY.md §2.1 KFServing row, §3 CS3)."""

    @pytest.fixture(scope="class")
    def module_file(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("hooks") / "transform.py"
        path.write_text(TRANSFORMER_MODULE)
        return str(path)

    def test_components_inprocess(self, export_dir, module_file):
        from kubeflow_tpu.serving.graph import (
            ExplainerServer, PredictorClient, TransformerServer)
        from kubeflow_tpu.serving.router import Router
        from kubeflow_tpu.serving.server import JaxPredictor, ModelServer

        predictor = JaxPredictor(export_dir, name="m", max_batch_size=16)
        predictor.load()
        ms = ModelServer(port=0)
        ms.register(predictor)
        ms.start()
        router = Router().start()
        router.default.set_endpoints([f"127.0.0.1:{ms.port}"])
        client = PredictorClient(f"http://127.0.0.1:{router.port}", "m",
                                 retries=3)
        tr = TransformerServer("m", client, module_path=module_file).start()
        ex = ExplainerServer("m", client, feature_groups=8).start()
        router.transformer.set_endpoints([f"127.0.0.1:{tr.port}"])
        router.explainer.set_endpoints([f"127.0.0.1:{ex.port}"])
        router.transformer_configured = True
        router.explainer_configured = True
        try:
            x = (np.zeros((2, 28, 28, 1)) + 128).tolist()
            url = f"http://127.0.0.1:{router.port}"
            status, body = _post(f"{url}/v1/models/m:predict",
                                 {"instances": x}, timeout=60)
            assert status == 200
            # postprocess shape proves the transformer chain ran
            assert all(isinstance(p, dict) and "label" in p
                       for p in body["predictions"])
            status, body = _post(f"{url}/v1/models/m:explain",
                                 {"instances": [np.zeros((28, 28, 1)).tolist()]},
                                 timeout=60)
            assert status == 200
            e = body["explanations"][0]
            assert e["method"] == "occlusion"
            assert len(e["saliency"]) == 8
            assert 0.0 <= e["base_probability"] <= 1.0
        finally:
            tr.stop()
            ex.stop()
            router.stop()
            ms.stop()

    def test_isvc_full_graph_e2e(self, export_dir, module_file, tmp_path):
        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: graphy
spec:
  predictor:
    minReplicas: 1
    jax:
      storageUri: file://{export_dir}
  transformer:
    module: {module_file}
  explainer:
    method: occlusion
    featureGroups: 4
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "graphy",
                                         "Ready", timeout=120)
            assert isvc.has_condition("TransformerReady", "True")
            assert isvc.has_condition("ExplainerReady", "True")
            url = isvc.status["url"]
            x = (np.zeros((2, 28, 28, 1)) + 128).tolist()
            status, body = _post(f"{url}/v1/models/graphy:predict",
                                 {"instances": x}, timeout=60)
            assert status == 200
            assert all(isinstance(p, dict) and "label" in p
                       for p in body["predictions"])
            status, body = _post(
                f"{url}/v1/models/graphy:explain",
                {"instances": [np.zeros((28, 28, 1)).tolist()]}, timeout=60)
            assert status == 200
            e = body["explanations"][0]
            assert len(e["saliency"]) == 4 and e["feature_groups"] == 4


class TestTFServing:
    """TF SavedModel predictor (the reference's TFServing runtime): a
    registry model exported via jax2tf, served by pure TF on CPU."""

    @pytest.fixture(scope="class")
    def tf_export(self, tmp_path_factory, export_dir):
        import jax

        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.serving.tf_server import export_savedmodel
        from kubeflow_tpu.training import TrainLoop

        ds = get_dataset("mnist")
        model = get_model("mlp", num_classes=ds.num_classes)
        loop = TrainLoop(model)
        state = loop.init_state(ds.shape)
        for images, labels in ds.batches(128, steps=10):
            state, *_ = loop.train_step(state, images, labels)
        out = tmp_path_factory.mktemp("tf-export")
        export_savedmodel(str(out), "mlp", ds.shape, ds.num_classes, state)
        self._state = state
        return str(out), state, model

    def test_export_and_predict_matches_jax(self, tf_export):
        import jax.numpy as jnp

        from kubeflow_tpu.serving.tf_server import (
            TFPredictor, is_tf_export)

        path, state, model = tf_export
        assert is_tf_export(path)
        p = TFPredictor(path, name="tfm")
        p.load()
        assert p.ready and p.input_shape == (28, 28, 1)
        x = np.random.default_rng(0).normal(
            size=(5, 28, 28, 1)).astype(np.float32)
        out = p.predict(x, probabilities=True)
        assert np.allclose(np.sum(out["probabilities"], -1), 1.0, atol=1e-5)
        # Numerics parity with the jax forward on the same params.
        jax_logits = model.apply({"params": state.params}, jnp.asarray(x),
                                 train=False)
        assert out["predictions"] == \
            np.asarray(jax_logits).argmax(-1).tolist()

    def test_isvc_tensorflow_e2e(self, tf_export, tmp_path):
        from kubeflow_tpu.api.manifest import load_manifests
        from kubeflow_tpu.controlplane import ControlPlane

        path, _, _ = tf_export
        manifest = f"""
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: tfserve
spec:
  predictor:
    minReplicas: 1
    tensorflow:
      storageUri: file://{path}
"""
        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply(load_manifests(manifest))
            isvc = cp.wait_for_condition("InferenceService", "tfserve",
                                         "Ready", timeout=120)
            url = isvc.status["url"]
            x = np.zeros((3, 28, 28, 1), np.float32)
            status, body = _post(f"{url}/v1/models/tfserve:predict",
                                 {"instances": x.tolist()}, timeout=60)
            assert status == 200 and len(body["predictions"]) == 3
