"""Flight recorder (obs/flightrec.py) + its surfaces: ring/trail
bounds and timing math, the engine hooks (records per iteration,
retired requests with latency breakdowns, ring frozen at the stalled
iteration under engine.wedge, compiling-suppressed wedge verdicts
still record flight entries, drain-while-prefilling retires through
the recorder), the model server's /debug/flight + /debug/requests +
X-Kfx-Timing surfaces and the /healthz-piggybacked snapshot file, the
chaos-point inventory gate (with a planted gap), and the --json CLI
renderers."""

import json
import os
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu import chaos
from kubeflow_tpu.obs import flightrec
from kubeflow_tpu.obs.flightrec import (FlightRecorder, MAX_EVENTS,
                                        render_timeline)


@pytest.fixture(scope="module")
def tiny_lm():
    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            head_dim=16, n_layers=2, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


class _FakeReq:
    """The duck-typed slice of Request the recorder reads."""

    def __init__(self, **kw):
        self.rid = 1
        self.events = []
        self.tokens = [7, 8, 9]
        self.error = None
        self.preempts = 0
        self.stall_s = 0.0
        self.spec_prop = 0
        self.spec_acc = 0
        self.t_enqueue = 100.0
        self.t_admitted = 100.5
        self.t_first = 101.5
        self.t_done = 102.0
        self.t_prefill_end = 100.9
        self.prefill_iters = 2
        self.slot = 0
        for k, v in kw.items():
            setattr(self, k, v)


# -- recorder unit -----------------------------------------------------------


class TestFlightRecorderUnit:
    def test_ring_is_bounded_and_keeps_newest(self):
        rec = FlightRecorder(ring_size=16, recent_size=8)
        for i in range(40):
            rec.record_iteration(iteration=i, active=[(0, i)],
                                 prefilling=[], pages_free=3,
                                 draft_pages_free=0, spec_proposed=0,
                                 spec_accepted=0, stall_s=0.0,
                                 queue_depth=1, preemptions=0)
        assert len(rec) == 16
        records = rec.snapshot()["records"]
        assert [r["it"] for r in records] == list(range(24, 40))
        assert records[-1]["active"] == [[0, 39]] or \
            records[-1]["active"] == [(0, 39)]
        for key in ("it", "ts", "active", "prefilling", "pages_free",
                    "draft_pages_free", "spec_proposed",
                    "spec_accepted", "stall_s", "queue_depth",
                    "preemptions"):
            assert key in records[-1]

    def test_recent_ring_is_bounded(self):
        rec = FlightRecorder(ring_size=16, recent_size=8)
        for i in range(20):
            rec.retire(_FakeReq(rid=i))
        reqs = rec.requests()["requests"]
        assert len(reqs) == 8
        assert [r["rid"] for r in reqs] == list(range(12, 20))
        assert reqs[-1]["timing"]["queue_wait_s"] == 0.5

    def test_event_trail_drops_middle_not_unbounded(self):
        req = _FakeReq()
        for i in range(MAX_EVENTS + 50):
            FlightRecorder.event(req, "prefill_chunk", start=i)
        # Bounded: the cap plus ONE collapsed "dropped" marker that
        # absorbs every further event.
        assert len(req.events) == MAX_EVENTS + 1
        assert req.events[-1]["ev"] == "dropped"
        assert req.events[-1]["n"] == 50
        assert req.events[0]["ev"] == "prefill_chunk"

    def test_timing_breakdown_math(self):
        req = _FakeReq(stall_s=0.25, spec_prop=10, spec_acc=7)
        t = FlightRecorder.timing(req)
        assert t["queue_wait_s"] == pytest.approx(0.5)
        assert t["prefill_s"] == pytest.approx(1.0)
        assert t["decode_s"] == pytest.approx(0.5)
        assert t["stalled_s"] == pytest.approx(0.25)
        assert t["spec_accept"] == pytest.approx(0.7)
        # No speculation -> None, never a divide-by-zero.
        assert FlightRecorder.timing(_FakeReq())["spec_accept"] is None

    @pytest.mark.parametrize("stamps, span, wait, iters", [
        # the last prompt chunk enqueued 0.4 s after admission
        ({}, 0.4, 0.6, 2),
        # never prefilled here (a KV import): the whole leg is the wait
        ({"t_prefill_end": 0.0, "prefill_iters": 0}, 0.0, 1.0, 0),
        # a stamp past the first token (a recompute after a preempt
        # must not have moved it, but a clock edge could): clamped
        ({"t_prefill_end": 101.7}, 1.0, 0.0, 2),
        # third decimals that do not add up in binary
        ({"t_admitted": 100.1000004, "t_prefill_end": 100.4000003,
          "t_first": 101.2000007}, 0.3, 0.8, 2),
    ], ids=["split", "imported", "clamped", "rounding"])
    def test_timing_splits_prefill_where_the_engine_spends_it(
            self, stamps, span, wait, iters):
        t = FlightRecorder.timing(_FakeReq(**stamps))
        assert t["prefill_span_s"] == pytest.approx(span, abs=2e-6)
        assert t["first_token_wait_s"] == pytest.approx(wait, abs=2e-6)
        assert t["prefill_iterations"] == iters
        assert t["prefill_span_s"] + t["first_token_wait_s"] == \
            pytest.approx(t["prefill_s"], abs=1e-9)
        assert t["prefill_span_s"] >= 0 and t["first_token_wait_s"] >= 0

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("KFX_FLIGHT", "0")
        assert not flightrec.enabled_from_env()
        monkeypatch.delenv("KFX_FLIGHT")
        assert flightrec.enabled_from_env()
        monkeypatch.setenv("KFX_FLIGHT_RING", "4")   # floor is 16
        assert flightrec.ring_size_from_env() == 16
        monkeypatch.setenv("KFX_FLIGHT_RING", "bogus")
        assert flightrec.ring_size_from_env() == flightrec.DEFAULT_RING
        monkeypatch.setenv("KFX_FLIGHT_RECENT", "9")
        assert flightrec.recent_size_from_env() == 9

    def test_render_timeline_marks_wedged_tail(self):
        rec = FlightRecorder(ring_size=16, recent_size=8)
        for i in range(5):
            rec.record_iteration(iteration=i, active=[(1, 42)],
                                 prefilling=[(0, 43)], pages_free=2,
                                 draft_pages_free=0, spec_proposed=8,
                                 spec_accepted=5, stall_s=0.001,
                                 queue_depth=3, preemptions=1)
        hb = {"wedged": True, "iterations": 4, "stalled_s": 7.5,
              "busy": True, "compiling": False}
        out = render_timeline(rec.snapshot()["records"], heartbeat=hb)
        assert "s1:r42" in out and "s0:r43*" in out
        assert "spec 5/8" in out
        assert "<== WEDGED after this iteration" in out
        assert "iterations=4" in out
        assert render_timeline([]) == "(flight ring empty)"


# -- engine hooks ------------------------------------------------------------


class TestEngineFlight:
    @pytest.fixture(scope="class")
    def engine(self, tiny_lm):
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm
        eng = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                           name="lm-flight", kv_page_size=16,
                           prefill_chunk_tokens=16,
                           stall_threshold_s=0.5)
        eng.warm([8])
        yield eng
        eng.close()

    def test_recorder_on_by_default_and_output_identical_off(
            self, engine):
        """The recorder is constructed unless KFX_FLIGHT=0, and the
        greedy token stream is byte-identical with it detached — the
        hooks observe, never steer."""
        assert engine.flight is not None
        prompts = [[5, 9, 11, 3], [2, 4]]
        with_rec = engine.generate(prompts, max_new_tokens=8)
        recorder = engine.flight
        engine.flight = None
        try:
            without = engine.generate(prompts, max_new_tokens=8)
        finally:
            engine.flight = recorder
        assert with_rec == without

    def test_iteration_records_and_request_trail(self, engine):
        # 40-token prompt over 16-token chunks: chunked admission, so
        # the trail carries per-chunk events.
        prompt = [(i % 50) + 2 for i in range(40)]
        out = engine.generate([prompt], max_new_tokens=6)
        assert len(out[0]) == 6
        snap = engine.flight.snapshot(heartbeat=engine.heartbeat())
        assert snap["records"], "no iteration records after traffic"
        its = [r["it"] for r in snap["records"]]
        assert its == sorted(its)
        assert snap["heartbeat"]["iterations"] >= its[-1]
        reqs = engine.flight.requests()["requests"]
        assert reqs, "no retired requests in the recent ring"
        last = reqs[-1]
        names = [e["ev"] for e in last["events"]]
        assert names[0] == "admit"
        assert "first_token" in names and names[-1] == "retire"
        # A 40-token prompt at prefill_chunk_tokens=16 takes >= 2
        # chunk dispatches.
        assert names.count("prefill_chunk") >= 2
        t = last["timing"]
        assert t["queue_wait_s"] >= 0 and t["prefill_s"] > 0
        assert last["tokens"] == 6 and last["error"] is None

    @pytest.mark.parametrize("prompt_len, chunks", [(40, 3), (5, 1)],
                             ids=["three-chunks", "monolithic"])
    def test_timing_counts_the_prompts_iterations(self, engine,
                                                  prompt_len, chunks):
        """40 tokens over 16-token chunks: admitted and first chunk in
        one iteration, then one chunk an iteration; 5 tokens: one
        monolithic dispatch. The split adds up to ``prefill_s`` and
        rides the recent-requests ring (/debug/requests, the stream's
        ``done`` event)."""
        # (tokens no other test of this class sends: a prefix-cache
        # hit would shorten the prompt's tail)
        prompt = [(i * 7 + 3) % 61 + 2 for i in range(prompt_len)]
        engine.generate([prompt], max_new_tokens=6)
        t = engine.flight.requests()["requests"][-1]["timing"]
        assert t["prefill_iterations"] == chunks
        assert t["prefill_span_s"] + t["first_token_wait_s"] == \
            pytest.approx(t["prefill_s"], abs=1e-6)
        assert t["first_token_wait_s"] > 0 and t["prefill_span_s"] >= 0
        if chunks > 1:
            assert t["prefill_span_s"] > 0

    def test_timing_split_survives_a_cut_event_trail(self, engine):
        """The stamps live on the request: a trail already at
        MAX_EVENTS when the prompt starts still gets its split."""
        prompt = [(i * 11 + 5) % 59 + 2 for i in range(40)]
        with engine._cond:   # the loop cannot admit it before this
            req = engine.submit(prompt, max_new_tokens=4)
            req.events.extend({"ev": "filler", "ts": 0.0}
                              for _ in range(MAX_EVENTS))
        assert len(req.result(60)) == 4
        assert any(e["ev"] == "dropped" for e in req.events)
        t = engine.flight.timing(req)
        assert t["prefill_iterations"] >= 1
        assert t["prefill_span_s"] + t["first_token_wait_s"] == \
            pytest.approx(t["prefill_s"], abs=1e-6)

    def test_kfx_flight_0_disables_recorder(self, tiny_lm, monkeypatch):
        from kubeflow_tpu.serving.engine import DecodeEngine

        monkeypatch.setenv("KFX_FLIGHT", "0")
        cfg, params = tiny_lm
        eng = DecodeEngine(cfg, params, n_slots=1, chunk_tokens=4,
                           name="lm-noflight", kv_page_size=16)
        try:
            assert eng.flight is None
            assert len(eng.generate([[3, 5]], max_new_tokens=4)[0]) == 4
        finally:
            eng.close()

    def test_wedge_suppression_while_compiling_still_records(
            self, engine):
        """Satellite: the heartbeat's compiling field suppresses the
        wedged VERDICT (slow-not-stuck), but never flight records —
        the ring still holds the stalled iteration with its slots, and
        a drain issued mid-prefill retires through the recorder."""
        retired_before = len(engine.flight.requests()["requests"])
        engine._building += 1   # a warm/AOT build "in progress"
        chaos.install(chaos.parse_spec("engine.wedge:count=1,delay=1.5"))
        try:
            prompt = [(i % 40) + 3 for i in range(40)]
            req = engine.submit(prompt, max_new_tokens=4)
            # Wait until the loop is visibly stalled past threshold.
            saw_suppressed = False
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                hb = engine.heartbeat()
                if hb["busy"] and hb["stalled_s"] > 0.6:
                    assert hb["compiling"] is True
                    assert hb["wedged"] is False, \
                        "compiling must suppress the wedged verdict"
                    saw_suppressed = True
                    break
                time.sleep(0.02)
            assert saw_suppressed, "never observed the suppressed stall"
            # The ring froze WITH the stalled iteration on it: the last
            # record carries the in-flight slot and the frozen counter.
            n1 = len(engine.flight)
            rec1 = engine.flight.snapshot()["records"][-1]
            assert rec1["active"] or rec1["prefilling"]
            assert rec1["it"] == engine.heartbeat()["iterations"]
            time.sleep(0.3)
            assert len(engine.flight) == n1, \
                "ring advanced while the loop was stalled"
            # Drain while the request is still in flight (admitted
            # pre-drain work finishes; the recorder sees the retire).
            assert engine.drain(wait_s=30) is True
            assert len(req.result(30)) == 4
            assert chaos.injected_counts().get("engine.wedge") == 1
        finally:
            engine._building -= 1
            chaos.reset()
        reqs = engine.flight.requests()["requests"]
        assert len(reqs) > retired_before
        last = reqs[-1]
        assert [e["ev"] for e in last["events"]][-1] == "retire"
        # The wedge hit between admit and first token, so its latency
        # is attributed to the prefill leg of the breakdown.
        assert last["timing"]["prefill_s"] > 1.0


# -- model server surfaces ---------------------------------------------------


class TestFlightHTTP:
    @pytest.fixture(scope="class")
    def lm_server(self, tiny_lm, tmp_path_factory):
        from kubeflow_tpu.serving.lm_server import LMPredictor, export_lm
        from kubeflow_tpu.serving.server import ModelServer

        cfg, params = tiny_lm
        root = str(tmp_path_factory.mktemp("flight-lm"))
        export_lm(os.path.join(root, "lm"), cfg, params)
        p = LMPredictor(os.path.join(root, "lm"), name="lm")
        p.load()
        srv = ModelServer(port=0)
        srv.register(p)
        srv.start()
        yield srv, p
        srv.stop()

    def _get(self, port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, json.load(r)

    def _generate(self, port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/lm:generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.headers, json.load(r)

    def test_generate_returns_timing_block_and_header(self, lm_server):
        srv, _ = lm_server
        headers, body = self._generate(
            srv.port, {"prompt_tokens": [[5, 9, 11]],
                       "max_new_tokens": 4})
        assert len(body["generated_tokens"][0]) == 4
        assert len(body["timing"]) == 1
        t = body["timing"][0]
        for key in ("queue_wait_s", "prefill_s", "decode_s",
                    "stalled_s", "spec_accept"):
            assert key in t
        hdr = headers.get("X-Kfx-Timing")
        assert hdr and "queue_wait_s=" in hdr and "decode_s=" in hdr

    def test_debug_flight_and_requests_endpoints(self, lm_server):
        srv, p = lm_server
        self._generate(srv.port, {"prompt_tokens": [[2, 4, 6]],
                                  "max_new_tokens": 4})
        status, doc = self._get(srv.port, "/debug/flight")
        assert status == 200
        snap = doc["models"]["lm"]
        assert snap["records"] and snap["ring_size"] >= 16
        assert snap["heartbeat"]["wedged"] is False
        status, doc = self._get(srv.port, "/debug/requests")
        assert status == 200
        reqs = doc["models"]["lm"]["requests"]
        assert reqs and reqs[-1]["timing"]["decode_s"] >= 0

    def test_debug_flight_404_when_recorder_off(self, lm_server):
        srv, p = lm_server
        recorder = p._engine.flight
        p._engine.flight = None
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.port, "/debug/flight")
            assert ei.value.code == 404
        finally:
            p._engine.flight = recorder

    def test_healthz_writes_snapshot_file(self, lm_server, tmp_path,
                                          monkeypatch):
        """The crash-reap source: /healthz piggybacks an atomic flight
        snapshot into $KFX_WORKDIR/flight/ so a SIGKILLed replica still
        leaves a readable last picture."""
        srv, _ = lm_server
        monkeypatch.setenv("KFX_WORKDIR", str(tmp_path))
        monkeypatch.setenv("KFX_COMPONENT", "default-0")
        self._generate(srv.port, {"prompt_tokens": [[1, 3]],
                                  "max_new_tokens": 2})
        self._get(srv.port, "/healthz")
        path = tmp_path / "flight" / f"default-0-{os.getpid()}.json"
        assert path.exists(), "healthz did not persist a flight snapshot"
        doc = json.loads(path.read_text())
        assert doc["pid"] == os.getpid()
        assert doc["models"]["lm"]["records"]
        # The snapshot renders through the same path `kfx flight` uses.
        from kubeflow_tpu.cli import _flight_models

        models = _flight_models(doc)
        assert "lm" in models
        out = render_timeline(models["lm"]["records"])
        assert "it " in out and "kv[" in out


# -- chaos-point inventory gate ----------------------------------------------


class TestChaosInventoryGate:
    def test_repo_catalog_is_complete(self, capsys):
        import scripts.scrape_metrics as scrape

        assert scrape.check_chaos_inventory() == 0
        out = capsys.readouterr().out
        assert "ok   chaos-inventory" in out

    def test_planted_gap_fails_the_gate(self, tmp_path, capsys):
        """Self-test: a KNOWN_POINTS entry missing from the catalog
        must FAIL (count >= 1), a documented-but-gone point only
        warns, and dotless backticked tokens (the spec-knob table)
        never parse as points."""
        import scripts.scrape_metrics as scrape

        doc = tmp_path / "chaos.md"
        doc.write_text(
            "| point | site | injection |\n"
            "| --- | --- | --- |\n"
            "| `engine.admit` | admission | delay |\n"
            "| `ghost.point` | nowhere | n/a |\n"
            "| `p` | knob, not a point | n/a |\n")
        assert scrape.documented_chaos_points(str(doc)) == \
            {"engine.admit", "ghost.point"}
        n = scrape.check_chaos_inventory(
            points={"engine.admit", "engine.wedge"},
            doc_path=str(doc))
        assert n == 1
        out = capsys.readouterr().out
        assert "FAIL chaos-inventory: engine.wedge" in out
        assert "warn chaos-inventory: ghost.point" in out
        # Clean doc -> clean gate.
        doc.write_text("| `engine.admit` | a | d |\n"
                       "| `engine.wedge` | w | d |\n")
        assert scrape.check_chaos_inventory(
            points={"engine.admit", "engine.wedge"},
            doc_path=str(doc)) == 0


# -- CLI --json renderers ----------------------------------------------------


class TestCliJson:
    def test_print_query_json_shape_and_rc(self, capsys):
        from kubeflow_tpu.cli import _print_query

        res = {"family": "kfx_up", "fn": "latest", "value": 1.0,
               "since": 300.0, "points": [[100.0, 1.0]], "labels": {}}
        assert _print_query(res, as_json=True) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["family"] == "kfx_up" and doc["value"] == 1.0
        # Empty window: rc 1, with --json and without alike.
        empty = {"family": "kfx_up", "fn": "latest", "value": None,
                 "since": 300.0, "points": []}
        assert _print_query(empty, as_json=True) == 1
        json.loads(capsys.readouterr().out)
        assert _print_query(empty) == 1
        capsys.readouterr()

    def test_print_alerts_json_shape_and_rc(self, capsys):
        from kubeflow_tpu.cli import _print_alerts

        quiet = [{"name": "r1", "state": "inactive"}]
        assert _print_alerts(quiet, as_json=True) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc == {"alerts": quiet, "firing": 0}
        firing = [{"name": "r1", "state": "firing"},
                  {"name": "r2", "state": "pending"}]
        assert _print_alerts(firing, as_json=True) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["firing"] == 1
        assert _print_alerts(firing) == 1
        capsys.readouterr()
