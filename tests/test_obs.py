"""Observability subsystem tests: the metrics registry (concurrency,
label escaping round-trip, histogram exposition), trace-ID propagation
apiserver -> store -> gang env -> events, and scrape validation of the
live /metrics endpoints (the scripts/scrape_metrics.py contract)."""

import json
import math
import os
import sys
import threading
import time
import urllib.request

import pytest

from kubeflow_tpu.api.base import from_manifest
from kubeflow_tpu.controlplane import ControlPlane
from kubeflow_tpu.obs import (
    TRACE_ANNOTATION,
    MetricsRegistry,
    current_trace_id,
    set_trace_id,
    span,
)
from kubeflow_tpu.utils.prom import (
    parse_prom_text,
    prom_text,
    validate_exposition,
)

PY = sys.executable


class TestRegistry:
    def test_concurrent_increments(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total", "h")
        g = reg.gauge("depth", "d")
        h = reg.histogram("lat_seconds", "l", buckets=[0.1, 1.0])

        def work():
            for _ in range(1000):
                c.inc(1, worker="w")
                g.inc(1)
                h.observe(0.05)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(worker="w") == 8000
        assert g.value() == 8000
        assert h.count() == 8000

    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("a_total") is reg.counter("a_total")
        with pytest.raises(TypeError):
            reg.gauge("a_total")

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("a_total").inc(-1)

    def test_label_escaping_roundtrip(self):
        reg = MetricsRegistry()
        nasty = 'we"ird\nva\\lue'
        reg.gauge("kfx_g", "gauge with a hostile label").set(3, model=nasty)
        text = reg.render()
        assert validate_exposition(text) == []
        parsed = parse_prom_text(text)
        [(labels, value)] = parsed["kfx_g"]
        assert labels == {"model": nasty}
        assert value == 3

    def test_histogram_exposition(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "latency", buckets=[0.01, 0.1, 1.0])
        for v in (0.005, 0.05, 0.5, 0.5):
            h.observe(v, model="m")
        text = reg.render()
        assert validate_exposition(text) == []
        parsed = parse_prom_text(text)
        buckets = {lab["le"]: v for lab, v in parsed["lat_seconds_bucket"]}
        assert buckets == {"0.01": 1, "0.1": 2, "1": 4, "+Inf": 4}
        assert parsed["lat_seconds_count"][0][1] == 4
        assert abs(parsed["lat_seconds_sum"][0][1] - 1.055) < 1e-9

    def test_histogram_percentile_interpolation(self):
        h = MetricsRegistry().histogram("h", buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.5, 1.5, 3.0):
            h.observe(v)
        p50 = h.percentile(0.5)
        assert 1.0 <= p50 <= 2.0
        # +Inf landings clamp to the last finite bound.
        h.observe(100.0, n=10)
        assert h.percentile(0.99) == 4.0

    def test_bulk_observe(self):
        h = MetricsRegistry().histogram("h", buckets=[1.0])
        h.observe(0.5, n=16)
        assert h.count() == 16

    def test_collector_runs_at_render(self):
        reg = MetricsRegistry()
        reg.add_collector(lambda r: r.gauge("live").set(7))
        assert "live 7" in reg.render()
        assert reg.snapshot()["live"]["samples"][0]["value"] == 7


class TestHistogramRoundTrip:
    """parse/validate round-trips on histogram edge cases — the
    central scraper now parses the plane's OWN exposition output every
    cycle (obs/tsdb.py), so these shapes must survive the trip, not
    just render."""

    def _roundtrip(self, reg):
        text = reg.render()
        assert validate_exposition(text) == []
        return parse_prom_text(text), text

    def test_zero_observation_family(self):
        """A histogram family seeded with observe(v, n=0) (the
        --require pre-seeding idiom): every bucket renders cumulative
        0 and the count/sum are 0 — and the parse keeps the series."""
        reg = MetricsRegistry()
        reg.histogram("kfx_z_seconds", "seeded",
                      buckets=[0.1, 1.0]).observe(0.0, n=0, model="m")
        parsed, _ = self._roundtrip(reg)
        buckets = {lab["le"]: v
                   for lab, v in parsed["kfx_z_seconds_bucket"]}
        assert buckets == {"0.1": 0, "1": 0, "+Inf": 0}
        assert parsed["kfx_z_seconds_count"][0][1] == 0
        assert parsed["kfx_z_seconds_sum"][0][1] == 0

    def test_inf_only_bucket(self):
        """A histogram whose ONLY bound is +Inf (buckets=[]) still
        renders one le="+Inf" series and round-trips; the percentile
        clamps to the (nonexistent) finite bound, i.e. 0."""
        reg = MetricsRegistry()
        h = reg.histogram("kfx_i_seconds", "inf-only", buckets=[])
        h.observe(3.0)
        h.observe(50.0)
        parsed, _ = self._roundtrip(reg)
        [(lab, v)] = parsed["kfx_i_seconds_bucket"]
        assert lab["le"] == "+Inf" and v == 2
        assert parsed["kfx_i_seconds_sum"][0][1] == 53.0
        assert h.percentile(0.99) == 0.0  # +Inf landing clamps

    def test_escaped_label_values_on_histogram_series(self):
        """Hostile label values on HISTOGRAM series (model names ride
        the le label's row): escaping must survive _bucket/_sum/_count
        rendering AND the strict parse."""
        reg = MetricsRegistry()
        nasty = 'mo"del\\with\nnewline'
        reg.histogram("kfx_e_seconds", "esc",
                      buckets=[1.0]).observe(0.5, model=nasty)
        parsed, text = self._roundtrip(reg)
        assert r'\n' in text  # the newline is escaped, not raw
        labs = [lab for lab, _ in parsed["kfx_e_seconds_bucket"]]
        assert all(lab["model"] == nasty for lab in labs)
        assert {lab["le"] for lab in labs} == {"1", "+Inf"}
        [(lab, _)] = parsed["kfx_e_seconds_sum"]
        assert lab == {"model": nasty}


class TestMetricInventory:
    def test_every_code_family_is_documented(self):
        """The scrape_metrics --inventory contract as a tier-1 gate: a
        kfx_* family registered anywhere in the package without a row
        or mention in docs/observability.md fails here, so new
        instrumentation cannot land undocumented."""
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        import scrape_metrics

        assert scrape_metrics.main(["--inventory"]) == 0

    def test_inventory_catches_an_undocumented_family(self, tmp_path):
        """The checker itself must detect a gap: a synthetic package
        registering a family the docs never mention fails, and the
        same family documented passes."""
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        from scrape_metrics import check_inventory

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'REG.counter("kfx_totally_new_total", "h")\n')
        doc = tmp_path / "observability.md"
        doc.write_text("| nothing documented |\n")
        assert check_inventory(str(pkg), str(doc)) == 1
        doc.write_text("| `kfx_totally_new_total` | counter | — |\n")
        assert check_inventory(str(pkg), str(doc)) == 0


class TestExpositionValidation:
    def test_flags_malformed_lines(self):
        bad = ('# TYPE ok gauge\nok 1\n'
               '1bad_name 2\n'
               'noval\n'
               'badval{x="y"} abc\n'
               'nocomma{a="1"b="2"} 3\n'
               'kfx_foo.5\n'
               '# TYPE z wrongtype\n')
        errors = validate_exposition(bad)
        assert len(errors) == 6

    def test_prom_text_histogram_value(self):
        from kubeflow_tpu.utils.prom import HistogramValue

        text = prom_text([
            ("lat", "histogram", "h",
             [({"m": "x"}, HistogramValue(
                 [(0.1, 1), (math.inf, 2)], 0.6, 2))])])
        assert 'lat_bucket{m="x",le="0.1"} 1' in text
        assert 'lat_bucket{m="x",le="+Inf"} 2' in text
        assert 'lat_sum{m="x"} 0.6' in text
        assert 'lat_count{m="x"} 2' in text
        assert validate_exposition(text) == []


class TestTraceHelpers:
    def test_thread_local_scope(self):
        set_trace_id("")
        assert current_trace_id() == ""
        with span("unit", trace_id="abc123") as sp:
            assert current_trace_id() == "abc123"
        assert current_trace_id() == ""
        assert sp.elapsed >= 0

    def test_span_observes_histogram(self):
        h = MetricsRegistry().histogram("span_seconds")
        with span("unit", trace_id="t", histogram=h, phase="x"):
            pass
        assert h.count(phase="x") == 1


def _env_echo_job(name):
    return from_manifest({
        "apiVersion": "kubeflow.org/v1", "kind": "JAXJob",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"jaxReplicaSpecs": {"Worker": {
            "replicas": 1,
            "template": {"spec": {"containers": [{
                "name": "main",
                "command": [PY, "-c",
                            "import os;"
                            "print('trace_env='"
                            "+os.environ.get('KFX_TRACE_ID','missing'))"],
            }]}}}}}})


class TestTracePropagation:
    def test_apply_to_runner_env_and_events(self, tmp_path):
        """A trace ID minted at admission must land in the stored
        resource's metadata, in the gang member's environment (runner
        log), and on at least one recorded event."""
        with ControlPlane(home=str(tmp_path / "kfx"),
                          worker_platform="cpu") as cp:
            cp.apply([_env_echo_job("trace-job")])
            job = cp.store.get("JAXJob", "trace-job")
            trace = job.metadata.annotations.get(TRACE_ANNOTATION)
            assert trace, "admission did not mint a trace ID"

            cp.wait_for_job("JAXJob", "trace-job", timeout=90)
            log = cp.job_logs("JAXJob", "trace-job")
            assert f"trace_env={trace}" in log
            assert f"trace={trace}" in log  # gang attempt header

            events = cp.store.events_for("JAXJob", "default/trace-job")
            assert any(e.trace_id == trace for e in events)

            # Re-applying the unchanged manifest keeps the original ID
            # (and the "unchanged" verb — no resourceVersion churn).
            [(obj, verb)] = cp.apply([_env_echo_job("trace-job")])
            assert verb == "unchanged"
            assert obj.metadata.annotations[TRACE_ANNOTATION] == trace
            cp.store.delete("JAXJob", "trace-job")

    def test_kfx_top_and_events_show_telemetry(self, tmp_path, capsys):
        from kubeflow_tpu.cli import KfxCLI

        with ControlPlane(home=str(tmp_path / "kfx"),
                          worker_platform="cpu") as cp:
            cp.apply([_env_echo_job("top-job")])
            cp.wait_for_job("JAXJob", "top-job", timeout=90)
            # Negative offset = tail (what top uses for huge logs).
            text, off = cp.job_logs_from(
                "JAXJob", "top-job", "default", "", -100)
            full = cp.job_logs("JAXJob", "top-job")
            assert text == full[-len(text):] and len(text) <= 100
            assert off == len(full.encode())
            cli = KfxCLI(cp)
            assert cli.top() == 0
            out = capsys.readouterr().out
            assert "top-job" in out and "JAXJob" in out
            assert cli.events("JAXJob", "top-job", "default") == 0
            out = capsys.readouterr().out
            trace = cp.store.get(
                "JAXJob", "top-job").metadata.annotations[TRACE_ANNOTATION]
            assert f"[trace={trace}]" in out
            cp.store.delete("JAXJob", "top-job")


class TestApiServerMetrics:
    @pytest.fixture()
    def server(self, tmp_path):
        from kubeflow_tpu.apiserver import ApiServer

        with ControlPlane(home=str(tmp_path / "kfx"),
                          worker_platform="cpu") as cp:
            with ApiServer(cp, port=0) as srv:
                yield srv

    def test_scrape_validates_and_reconcile_histograms(self, server):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        import scrape_metrics

        # Drive at least one reconcile so the histogram exists.
        server.cp.apply([_env_echo_job("scrape-job")])
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = server.cp.metrics.snapshot()
            if snap.get("kfx_reconcile_duration_seconds",
                        {}).get("samples"):
                break
            time.sleep(0.1)

        assert scrape_metrics.main([f"{server.url}/metrics"]) == 0

        with urllib.request.urlopen(f"{server.url}/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        assert validate_exposition(text) == []
        assert "kfx_reconcile_duration_seconds_bucket" in text
        assert 'kind="JAXJob"' in text
        assert "kfx_workqueue_adds_total" in text

        with urllib.request.urlopen(f"{server.url}/metrics?format=json",
                                    timeout=10) as r:
            m = json.loads(r.read().decode())
        assert m["resources"].get("JAXJob") == 1
        assert set(m["controllers"]["JAXJob"]) == {
            "depth", "delayed", "processing", "retrying"}
        rec = m["reconcile"].get("JAXJob")
        assert rec and rec["count"] >= 1 and rec["p50_ms"] is not None
        server.cp.store.delete("JAXJob", "scrape-job")

    def test_train_mfu_bridged_and_require_scrapeable(self, server,
                                                      monkeypatch):
        """kfx_train_mfu{job,config} + kfx_train_step_seconds are
        recorded live into the process default registry by LMTrainLoop
        and bridged onto the plane's /metrics (MetricsRegistry
        add_external), so `scrape_metrics --require kfx_train_mfu` pins
        the family in CI — the ISSUE-8 satellite contract. MFU exists
        only for a device with a published peak, so the test gives this
        suite's CPU one."""
        from kubeflow_tpu.utils import flops

        monkeypatch.setitem(flops.PEAK_FLOPS, "cpu", 1e12)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        import scrape_metrics

        from kubeflow_tpu.data.lm import LMDataset
        from kubeflow_tpu.models.transformer import TransformerConfig
        from kubeflow_tpu.parallel.lm_train import (
            LMHyperParams, LMTrainLoop)
        from kubeflow_tpu.parallel.mesh import make_mesh

        cfg = TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                head_dim=8, n_layers=1, d_ff=32,
                                max_seq_len=16)
        mesh, plan = make_mesh(1)
        loop = LMTrainLoop(cfg, mesh, plan,
                           LMHyperParams(total_steps=4, warmup_steps=1))
        state = loop.init_state()
        ds = LMDataset(vocab_size=64, seq_len=16)
        it = ds.batches(4)
        state, _, _ = loop.train_many(state, [next(it)])  # compile call
        state, _, _ = loop.train_many(state, [next(it)])  # recorded call

        assert scrape_metrics.main(
            [f"{server.url}/metrics",
             "--require", "kfx_train_mfu",
             "--require", "kfx_train_step_seconds"]) == 0
        with urllib.request.urlopen(f"{server.url}/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        assert validate_exposition(text) == []
        assert 'kfx_train_mfu{' in text
        assert 'job="local"' in text
        assert 'config="pp1/dp1/cp1/tp1-d16L1"' in text
        assert "kfx_train_step_seconds_bucket" in text

    def test_trace_header_adopted(self, server):
        body = ("apiVersion: kubeflow.org/v1\nkind: Profile\n"
                "metadata:\n  name: tr-prof\n"
                "spec:\n  owner:\n    name: alice\n").encode()
        req = urllib.request.Request(f"{server.url}/apis", data=body,
                                     method="POST")
        req.add_header("X-Kfx-Trace-Id", "deadbeef00000001")
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json.loads(r.read().decode())
        assert out["applied"][0]["traceId"] == "deadbeef00000001"
        prof = server.cp.store.get("Profile", "tr-prof")
        assert prof.metadata.annotations[TRACE_ANNOTATION] == \
            "deadbeef00000001"


class TestModelServerMetrics:
    def test_latency_histogram_from_requests(self):
        import numpy as np

        from kubeflow_tpu.serving.server import ModelServer, Predictor

        class Echo(Predictor):
            name = "echo"
            ready = True

            def load(self):
                pass

            def predict(self, instances, probabilities=False):
                return {"predictions": [0] * instances.shape[0]}

        server = ModelServer(port=0)
        server.register(Echo())
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            payload = json.dumps({"instances": [[1.0]]}).encode()
            for _ in range(5):
                req = urllib.request.Request(
                    f"{base}/v1/models/echo:predict", data=payload)
                req.add_header("X-Kfx-Trace-Id", "feedface00000001")
                with urllib.request.urlopen(req, timeout=10) as r:
                    assert r.status == 200
                    assert r.headers["X-Kfx-Trace-Id"] == \
                        "feedface00000001"
            # A request's time is observed once its response is out:
            # the fifth may still be on its way into the histogram.
            for _ in range(40):
                with urllib.request.urlopen(f"{base}/metrics",
                                            timeout=10) as r:
                    text = r.read().decode()
                parsed = parse_prom_text(text)
                counts = [v for lab, v in
                          parsed["kfx_serving_request_seconds_count"]
                          if lab.get("model") == "echo"]
                if counts and counts[0] == 5:
                    break
                time.sleep(0.05)
            assert validate_exposition(text) == []
            assert "kfx_serving_request_seconds_bucket" in text
            assert 'model="echo"' in text
            assert counts and counts[0] == 5
            with urllib.request.urlopen(f"{base}/metrics?format=json",
                                        timeout=10) as r:
                m = json.loads(r.read().decode())
            assert m["request_count"] == 5
            assert m["latency_ms"]["echo"]["p50"] is not None
        finally:
            server.stop()
