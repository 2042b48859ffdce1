"""Serving-fleet self-healing (serving/engine.py heartbeat+drain,
server /healthz liveness + /drain, router cross-replica recovery +
ejection counting, operator wedge-restart / crash backoff /
drain-before-kill): unit legs for each layer plus the tier-1 chaos e2e
— replica.kill mid-request on a 2-replica isvc recovers byte-identical
on the survivor, a scale-in under load drains with zero failed
requests, and engine.wedge gets the replica liveness-killed and
restarted with reason=wedged."""

import glob
import json
import os
import re
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu import chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.fixture(scope="module")
def lm_export(tiny_lm, tmp_path_factory):
    from kubeflow_tpu.serving.lm_server import export_lm

    cfg, params = tiny_lm
    return export_lm(str(tmp_path_factory.mktemp("fleet-lm")), cfg,
                     params)


def _post_json(url, payload, timeout=45.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.load(r)


# -- engine: heartbeat + drain + wedge ----------------------------------------


class TestEngineSelfHealing:
    @pytest.fixture(scope="class")
    def engine(self, tiny_lm):
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm
        eng = DecodeEngine(cfg, params, n_slots=1, chunk_tokens=4,
                           name="lm-heal", kv_page_size=16,
                           stall_threshold_s=0.5)
        eng.warm([8])
        yield eng
        eng.close()

    def test_heartbeat_advances_and_idle_is_never_wedged(self, engine):
        """The iteration counter advances with served work; an IDLE
        engine is never wedged no matter how stale the timestamp (the
        loop is parked, not stuck), and a fresh admission re-stamps
        progress so the parked interval can't read as a stall."""
        before = engine.heartbeat()
        assert not before["wedged"] and not before["busy"]
        engine.generate([[5, 9, 11]], max_new_tokens=8)
        after = engine.heartbeat()
        assert after["iterations"] > before["iterations"]
        time.sleep(0.7)  # > stall_threshold_s while idle
        hb = engine.heartbeat()
        assert hb["stalled_s"] > 0.5 and not hb["wedged"]
        # Work admitted after the idle stretch serves normally (the
        # enqueue re-stamped the clock: no false-wedge on wake).
        assert len(engine.generate([[1, 2]], max_new_tokens=4)[0]) == 4
        assert not engine.heartbeat()["wedged"]

    def test_wedge_chaos_stalls_loop_and_flags_heartbeat(self, engine):
        """engine.wedge stalls the loop with a slot active: the
        heartbeat reads wedged while the stall lasts (the liveness
        signal), then the request completes untouched — the stall
        costs latency, never correctness."""
        chaos.install(chaos.parse_spec("engine.wedge:count=1,delay=1.2"))
        try:
            req = engine.submit([5, 9, 11], max_new_tokens=6)
            saw_wedged = False
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and not req.done():
                if engine.heartbeat()["wedged"]:
                    saw_wedged = True
                time.sleep(0.02)
            assert saw_wedged, "heartbeat never read wedged mid-stall"
            assert len(req.result(30)) == 6
            assert chaos.injected_counts().get("engine.wedge") == 1
        finally:
            chaos.reset()

    def test_drain_finishes_slots_fails_queue_blocks_admission(
            self, engine):
        """drain(): the active slot runs to completion, the QUEUED
        request resolves with the retriable EngineDraining (what the
        router re-dispatches), and new submissions are refused with
        the same error. Runs last in the class: drain is one-way."""
        from kubeflow_tpu.serving.engine import EngineDraining

        active = engine.submit([4, 5], max_new_tokens=24)
        deadline = time.monotonic() + 30
        while engine.queue_depth and time.monotonic() < deadline:
            time.sleep(0.005)  # wait until it owns the only slot
        queued = engine.submit([6, 7], max_new_tokens=24)
        assert engine.drain(wait_s=30) is True
        assert len(active.result(1)) == 24
        with pytest.raises(EngineDraining):
            queued.result(1)
        with pytest.raises(EngineDraining):
            engine.submit([1], max_new_tokens=2)
        hb = engine.heartbeat()
        assert hb["draining"] and not hb["busy"]


# -- model server: /healthz liveness + /drain ---------------------------------


class TestServerSelfHealing:
    @pytest.fixture(scope="class")
    def lm_server(self, lm_export):
        from kubeflow_tpu.serving.lm_server import LMPredictor
        from kubeflow_tpu.serving.server import ModelServer

        saved = {k: os.environ.get(k)
                 for k in ("KFX_LM_SPEC", "KFX_LM_STALL_S")}
        os.environ["KFX_LM_SPEC"] = "0"
        os.environ["KFX_LM_STALL_S"] = "0.5"
        p = LMPredictor(lm_export, name="lm", warm_buckets=[8])
        p.load()
        srv = ModelServer(port=0)
        srv.register(p)
        srv.start()
        yield srv, p
        srv.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _healthz(self, port):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.load(e)

    def test_healthz_is_a_liveness_probe(self, lm_server):
        """200 alive normally; 503 {"status": "wedged"} while the
        decode loop is stalled with work in flight — the signal the
        operator's wedge-restart keys on (readiness keeps answering
        200 the whole time, which is exactly why it can't catch
        this)."""
        srv, p = lm_server
        assert self._healthz(srv.port) == (200, {"status": "alive"})
        chaos.install(chaos.parse_spec("engine.wedge:count=1,delay=2"))
        try:
            done = {}

            def client():
                done["body"] = _post_json(
                    f"http://127.0.0.1:{srv.port}/v1/models/lm:generate",
                    {"prompt_tokens": [[5, 9, 11]],
                     "max_new_tokens": 8})[1]

            t = threading.Thread(target=client)
            t.start()
            saw = None
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                code, body = self._healthz(srv.port)
                if code == 503 and body.get("status") == "wedged":
                    saw = body
                    break
                time.sleep(0.05)
            t.join(30)
            assert saw is not None, "/healthz never failed mid-wedge"
            assert "lm" in saw["models"]
            # Readiness stayed true throughout — liveness is the only
            # probe that can see a wedge.
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/v1/models/lm",
                    timeout=5) as r:
                assert json.load(r)["ready"] is True
            # The stall ended: the request completed, liveness healed.
            assert len(done["body"]["generated_tokens"][0]) == 8
            assert self._healthz(srv.port)[0] == 200
        finally:
            chaos.reset()

    def test_drain_endpoint_sheds_and_finishes(self, lm_server):
        """POST /drain: in-flight generations finish (the slot-active
        one 200s), queued ones shed retriably, readiness flips false,
        and new requests get 503 + Retry-After. Runs last: draining is
        one-way."""
        srv, p = lm_server
        url = f"http://127.0.0.1:{srv.port}/v1/models/lm:generate"
        # Hold the first admission 1s so work is provably in flight
        # when the drain lands.
        chaos.install(chaos.parse_spec(
            "engine.admit:mode=delay,delay=1.0,count=1"))
        results, errors = [], []

        def client():
            try:
                results.append(_post_json(
                    url, {"prompt_tokens": [[5, 9, 11]],
                          "max_new_tokens": 16}))
            except urllib.error.HTTPError as e:
                errors.append((e.code, e.headers.get("Retry-After")))

        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # first admission is mid-stall now
        try:
            code, verdict = _post_json(
                f"http://127.0.0.1:{srv.port}/drain?wait_s=20", {})
            assert code == 200 and verdict["drained"] is True
            for t in threads:
                t.join(30)
            # The in-flight request finished; the queued ones shed
            # with the retriable contract (503 + Retry-After), never a
            # hang or a hard failure.
            assert len(results) >= 1
            for status, body in results:
                assert status == 200
                assert len(body["generated_tokens"][0]) == 16
            for code_, retry in errors:
                assert code_ == 503 and retry is not None
            # Readiness follows the drain; new traffic sheds.
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/v1/models/lm",
                    timeout=5) as r:
                assert json.load(r)["ready"] is False
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(url, {"prompt_tokens": [[1]],
                                 "max_new_tokens": 2})
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") is not None
            assert self._healthz(srv.port) == (
                200, {"status": "draining"})
        finally:
            chaos.reset()


# -- router: cross-replica recovery + ejection counting -----------------------


class _DeadOnRequest(threading.Thread):
    """Accepts a connection, reads the request, then slams the socket
    shut — what a SIGKILL'd replica looks like to the router
    mid-request."""

    def __init__(self):
        super().__init__(daemon=True)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(8)
        self.port = self._srv.getsockname()[1]
        self.hits = 0
        self._stopped = False
        self.start()

    def run(self):
        while not self._stopped:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            try:
                conn.settimeout(5)
                conn.recv(65536)
                self.hits += 1
            except OSError:
                pass
            conn.close()

    def stop(self):
        self._stopped = True
        try:
            self._srv.close()
        except OSError:
            pass


class _StubLM(threading.Thread):
    """Healthy scripted backend: answers :generate with fixed tokens
    and :predict with fixed predictions."""

    def __init__(self, tokens):
        super().__init__(daemon=True)
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                if self.path.endswith(":generate"):
                    out = {"generated_tokens": [list(tokens)]}
                else:
                    out = {"predictions": [1]}
                body = json.dumps(out).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = HTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_port
        self.start()

    def run(self):
        self.httpd.serve_forever()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestRouterRecovery:
    def _router(self):
        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.serving.router import Router

        reg = MetricsRegistry()
        router = Router(metrics=reg, name="svc",
                        namespace="ns").start()
        return router, reg

    def test_generate_recovers_on_backend_death_and_counts(self):
        """A backend dying mid-:generate: the router re-dispatches the
        buffered request to the healthy replica (client sees 200, not
        502) and counts exactly one recovery."""
        dead, stub = _DeadOnRequest(), _StubLM([7, 8, 9])
        router, reg = self._router()
        try:
            # Round-robin starts at index 0: the dying backend takes
            # the first dispatch deterministically.
            router.default.set_endpoints(
                [f"127.0.0.1:{dead.port}", f"127.0.0.1:{stub.port}"])
            status, body = _post_json(
                f"http://127.0.0.1:{router.port}/v1/models/m:generate",
                {"prompt_tokens": [[1, 2]], "max_new_tokens": 3})
            assert status == 200
            assert body["generated_tokens"] == [[7, 8, 9]]
            assert dead.hits == 1  # it really held the request first
            assert reg.counter("kfx_router_recoveries_total").value(
                namespace="ns", isvc="svc", revision="default",
                mode="buffered") == 1
        finally:
            router.stop()
            dead.stop()
            stub.stop()

    def test_predict_retry_is_not_counted_as_recovery(self):
        """:predict keeps the bounded retry (idempotent traffic) but
        recovery accounting is the :generate story only — the family
        stays at its seeded zero."""
        dead, stub = _DeadOnRequest(), _StubLM([1])
        router, reg = self._router()
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{dead.port}", f"127.0.0.1:{stub.port}"])
            status, body = _post_json(
                f"http://127.0.0.1:{router.port}/v1/models/m:predict",
                {"instances": [[0.0]]})
            assert status == 200 and body["predictions"] == [1]
            samples = dict(
                (tuple(sorted(lab.items())), v) for lab, v in
                reg.counter("kfx_router_recoveries_total").samples())
            assert all(v == 0 for v in samples.values())
        finally:
            router.stop()
            dead.stop()
            stub.stop()

    def test_ejection_counter_seeded_and_counts_both_events(self):
        """kfx_router_ejections_total: seeded (zero sample) at router
        construction so --require holds pre-traffic; ejection and
        readmission each count with their endpoint label."""
        router, reg = self._router()
        e1, e2 = "127.0.0.1:7001", "127.0.0.1:7002"
        try:
            c = reg.counter("kfx_router_ejections_total")
            assert c.value(namespace="ns", isvc="svc",
                           revision="default", endpoint="",
                           event="eject") == 0  # the seed
            router.default.set_endpoints([e1, e2])
            for _ in range(3):
                router.default.report_failure(e1)
            assert c.value(namespace="ns", isvc="svc",
                           revision="default", endpoint=e1,
                           event="eject") == 1
            router.default.report_success(e1)
            assert c.value(namespace="ns", isvc="svc",
                           revision="default", endpoint=e1,
                           event="readmit") == 1
            # Plain success on a healthy endpoint is not a readmit.
            router.default.report_success(e2)
            assert c.value(namespace="ns", isvc="svc",
                           revision="default", endpoint=e2,
                           event="readmit") == 0
        finally:
            router.stop()


class _StubStreamLM(threading.Thread):
    """Scripted SSE backend: :generate streams one token frame per
    entry of ``tokens`` (honoring ``stream_skip`` in the body) and a
    terminal done frame. ``die_after=N`` severs the socket after N
    token frames — what a SIGKILL'd replica looks like to the router
    mid-stream (shutdown() first: rfile/wfile hold the socket's io
    refcount, so a bare close() would never send FIN). ``status``
    short-circuits with a buffered JSON answer (pre-stream shed)."""

    def __init__(self, tokens, die_after=None, status=None,
                 retry_after=None):
        super().__init__(daemon=True)
        stub = self
        self.bodies = []

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers.get("Content-Length", 0))))
                stub.bodies.append(body)
                if status is not None:
                    payload = json.dumps(
                        {"error": "scripted shed"}).encode()
                    self.send_response(status)
                    if retry_after is not None:
                        self.send_header("Retry-After", retry_after)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length",
                                     str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()  # HTTP/1.0: close-delimited body
                skip = int(body.get("stream_skip") or 0)
                sent = 0
                for i, t in enumerate(tokens):
                    if i < skip:
                        continue
                    frame = ("data: " + json.dumps(
                        {"index": i, "token": t}) + "\n\n").encode()
                    self.wfile.write(frame)
                    self.wfile.flush()
                    sent += 1
                    if die_after is not None and sent >= die_after:
                        self.connection.shutdown(socket.SHUT_RDWR)
                        self.connection.close()
                        return
                done = ("data: " + json.dumps(
                    {"done": True, "n_tokens": len(tokens)})
                    + "\n\n").encode()
                self.wfile.write(done)
                self.wfile.flush()

        self.httpd = HTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_port
        self.start()

    def run(self):
        self.httpd.serve_forever()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _post_sse(port, path, payload, timeout=30.0):
    """POST and read the full SSE response; returns (status, events)
    where each event is (is_error_frame, parsed_json)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout)
    try:
        data = json.dumps(payload).encode()
        conn.request("POST", path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        if "text/event-stream" not in resp.getheader(
                "Content-Type", ""):
            return resp.status, json.loads(raw)
        events = []
        for seg in raw.split(b"\n\n"):
            if b"data: " in seg:
                events.append((b"event: error" in seg, json.loads(
                    seg.split(b"data: ", 1)[1])))
        return resp.status, events
    finally:
        conn.close()


class TestRouterStreaming:
    def _router(self):
        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.serving.router import Router

        reg = MetricsRegistry()
        router = Router(metrics=reg, name="svc",
                        namespace="ns").start()
        return router, reg

    def _recoveries(self, reg, mode):
        return reg.counter("kfx_router_recoveries_total").value(
            namespace="ns", isvc="svc", revision="default", mode=mode)

    GEN = "/v1/models/m:generate"

    def test_stream_passthrough(self):
        """Healthy backend: the router relays the SSE stream as-is —
        every token frame in order, the done frame, zero recoveries
        (both mode samples stay at their seeded zero)."""
        stub = _StubStreamLM([7, 8, 9, 10])
        router, reg = self._router()
        try:
            router.default.set_endpoints([f"127.0.0.1:{stub.port}"])
            status, events = _post_sse(
                router.port, self.GEN,
                {"prompt_tokens": [[1, 2]], "max_new_tokens": 4,
                 "stream": True})
            assert status == 200
            toks = [e for err, e in events if "token" in e]
            assert [e["token"] for e in toks] == [7, 8, 9, 10]
            assert [e["index"] for e in toks] == [0, 1, 2, 3]
            assert events[-1][1]["done"] is True
            assert self._recoveries(reg, "buffered") == 0
            assert self._recoveries(reg, "mid_stream") == 0
        finally:
            router.stop()
            stub.stop()

    def test_mid_stream_recovery_byte_identical(self):
        """The backend dies after 2 streamed tokens: the router
        re-dispatches with stream_skip raised by the 2 frames the
        client already holds, the peer resumes at index 2, and the
        client's concatenated stream is byte-identical to an
        uninterrupted run — counted once as mode="mid_stream"."""
        dying = _StubStreamLM([7, 8, 9, 10], die_after=2)
        healthy = _StubStreamLM([7, 8, 9, 10])
        router, reg = self._router()
        try:
            # Round-robin index 0: the dying backend streams first.
            router.default.set_endpoints(
                [f"127.0.0.1:{dying.port}",
                 f"127.0.0.1:{healthy.port}"])
            status, events = _post_sse(
                router.port, self.GEN,
                {"prompt_tokens": [[1, 2]], "max_new_tokens": 4,
                 "stream": True})
            assert status == 200
            assert not any(err for err, _ in events)
            toks = [e for _, e in events if "token" in e]
            # Exactly once each, in order: no duplicates, no gap at
            # the failover seam.
            assert [e["index"] for e in toks] == [0, 1, 2, 3]
            assert [e["token"] for e in toks] == [7, 8, 9, 10]
            assert events[-1][1]["done"] is True
            assert self._recoveries(reg, "mid_stream") == 1
            assert self._recoveries(reg, "buffered") == 0
            # The resume really was a skip re-dispatch, not a replay.
            assert healthy.bodies[-1]["stream_skip"] == 2
        finally:
            router.stop()
            dying.stop()
            healthy.stop()

    def test_stream_cut_chaos_is_deterministic_mid_stream(self):
        """chaos router.stream_cut severs the relay after the first
        token reached the client — the deterministic stand-in for the
        e2e's replica.kill — and recovery must resume with skip >= 1
        and count as mid_stream."""
        a = _StubStreamLM([3, 4, 5])
        b = _StubStreamLM([3, 4, 5])
        router, reg = self._router()
        chaos.install(chaos.parse_spec(
            "seed=3;router.stream_cut:count=1"))
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"])
            status, events = _post_sse(
                router.port, self.GEN,
                {"prompt_tokens": [[1]], "max_new_tokens": 3,
                 "stream": True})
            assert status == 200
            toks = [e for _, e in events if "token" in e]
            assert [e["token"] for e in toks] == [3, 4, 5]
            assert [e["index"] for e in toks] == [0, 1, 2]
            assert self._recoveries(reg, "mid_stream") == 1
            retried = (a.bodies + b.bodies)[-1]
            assert retried["stream_skip"] >= 1
        finally:
            chaos.install(None)
            router.stop()
            a.stop()
            b.stop()

    def test_pre_token_death_is_buffered_mode(self):
        """A backend that dies BEFORE any token frame reached the
        client is the buffered special case: same recovery, counted
        as mode="buffered", and the peer serves from token 0 with no
        skip."""
        dead = _DeadOnRequest()
        healthy = _StubStreamLM([6, 7])
        router, reg = self._router()
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{dead.port}",
                 f"127.0.0.1:{healthy.port}"])
            status, events = _post_sse(
                router.port, self.GEN,
                {"prompt_tokens": [[1]], "max_new_tokens": 2,
                 "stream": True})
            assert status == 200
            toks = [e for _, e in events if "token" in e]
            assert [e["token"] for e in toks] == [6, 7]
            assert self._recoveries(reg, "buffered") == 1
            assert self._recoveries(reg, "mid_stream") == 0
            assert not healthy.bodies[-1].get("stream_skip")
        finally:
            router.stop()
            dead.stop()
            healthy.stop()

    def test_pre_stream_shed_relays_buffered(self):
        """A 400 from the backend (validation, before any SSE bytes)
        relays to the client as a plain buffered response — no retry,
        no recovery."""
        shedding = _StubStreamLM([], status=400)
        router, reg = self._router()
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{shedding.port}"])
            status, body = _post_sse(
                router.port, self.GEN,
                {"prompt_tokens": [[1]], "stream": True})
            assert status == 400
            assert body["error"] == "scripted shed"
            assert len(shedding.bodies) == 1  # no blind retry on 4xx
            assert self._recoveries(reg, "buffered") == 0
            assert self._recoveries(reg, "mid_stream") == 0
        finally:
            router.stop()
            shedding.stop()

    def test_retry_after_honored_with_jitter(self):
        """A 503 + Retry-After: 0.3 shed: the bounded retry waits the
        decorrelated jitter (>= 0.5 x advertised) before the peer
        dispatch instead of re-slamming the overloaded fleet — and a
        response-level shed is NOT an in-flight recovery."""
        shedding = _StubStreamLM([], status=503, retry_after="0.3")
        healthy = _StubLM([4, 5, 6])
        router, reg = self._router()
        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{shedding.port}",
                 f"127.0.0.1:{healthy.port}"])
            t0 = time.perf_counter()
            status, body = _post_json(
                f"http://127.0.0.1:{router.port}{self.GEN}",
                {"prompt_tokens": [[1, 2]], "max_new_tokens": 3})
            elapsed = time.perf_counter() - t0
            assert status == 200
            assert body["generated_tokens"] == [[4, 5, 6]]
            assert elapsed >= 0.14  # 0.5 x 0.3, minus clock slack
            samples = dict(
                (tuple(sorted(lab.items())), v) for lab, v in
                reg.counter("kfx_router_recoveries_total").samples())
            assert all(v == 0 for v in samples.values())
        finally:
            router.stop()
            shedding.stop()
            healthy.stop()


# -- router: prefix-affinity routing ------------------------------------------


class TestPrefixAffinity:
    def _router(self, capacity=512):
        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.serving.router import Router

        reg = MetricsRegistry()
        router = Router(metrics=reg, name="svc", namespace="ns",
                        affinity_capacity=capacity).start()
        return router, reg

    def test_affinity_hit_sticks_and_counts(self):
        """Same prefix key -> same endpoint, counted on the seeded
        kfx_router_prefix_affinity_hits_total family; keyless traffic
        keeps plain round-robin."""
        router, reg = self._router()
        e1, e2 = "127.0.0.1:7001", "127.0.0.1:7002"
        try:
            router.default.set_endpoints([e1, e2])
            c = reg.counter("kfx_router_prefix_affinity_hits_total")
            assert c.value(namespace="ns", isvc="svc") == 0  # the seed
            first = router._pick_in_set(router.default, "k1")
            picks = {router._pick_in_set(router.default, "k1")
                     for _ in range(5)}
            assert picks == {first}
            assert c.value(namespace="ns", isvc="svc") == 5
            # Round-robin without a key alternates endpoints.
            assert {router._pick_in_set(router.default, "")
                    for _ in range(4)} == {e1, e2}
        finally:
            router.stop()

    def test_ejected_target_falls_back_least_loaded(self):
        """An ejected affinity target degrades to a least-loaded
        healthy pick — and the map re-learns the replacement, so the
        prefix sticks to the survivor afterwards."""
        router, _ = self._router()
        e1, e2, e3 = ("127.0.0.1:7001", "127.0.0.1:7002",
                      "127.0.0.1:7003")
        try:
            router.default.set_endpoints([e1, e2, e3])
            router._remember_affinity("k", router.default, e1)
            for _ in range(3):
                router.default.report_failure(e1)  # eject the target
            router.default.ep_enter(e2)  # e2 busy: e3 is least-loaded
            got = router._pick_in_set(router.default, "k")
            assert got == e3
            router.default.ep_exit(e2)
            # Re-learned, under the per-set scoped key (a canary split
            # must not churn the default set's entries).
            assert router._affinity["default:k"] == e3
            assert router._pick_in_set(router.default, "k") == e3
        finally:
            router.stop()

    def test_overloaded_target_falls_back(self):
        """An affinity target far past its least-loaded healthy peer's
        in-flight count is 'overloaded': cache locality must not pile
        a hot prefix onto one replica while its peers idle."""
        from kubeflow_tpu.serving.router import BackendSet

        router, _ = self._router()
        e1, e2 = "127.0.0.1:7001", "127.0.0.1:7002"
        try:
            router.default.set_endpoints([e1, e2])
            router._remember_affinity("k", router.default, e1)
            for _ in range(BackendSet.AFFINITY_OVERLOAD_LEAD):
                router.default.ep_enter(e1)
            assert router._pick_in_set(router.default, "k") == e2
        finally:
            router.stop()

    def test_lru_bound(self):
        """The affinity map is a bounded LRU: the oldest key evicts at
        capacity, and a touched key survives."""
        router, _ = self._router(capacity=2)
        e1 = "127.0.0.1:7001"
        try:
            router.default.set_endpoints([e1])
            router._remember_affinity("a", router.default, e1)
            router._remember_affinity("b", router.default, e1)
            router._pick_in_set(router.default, "a")  # touch "a"
            router._remember_affinity("c", router.default, e1)
            # "b" evicted (keys scoped per backend set).
            assert set(router._affinity) == {"default:a", "default:c"}
        finally:
            router.stop()

    def test_chaos_affinity_loss_is_loss_free(self):
        """router.affinity chaos (forced misses + map eviction): every
        request still serves — affinity loss degrades to plain load
        balancing, never a failure."""
        s1, s2 = _StubLM([1]), _StubLM([2])
        router, reg = self._router()
        from kubeflow_tpu.serving.prefix import PREFIX_HEADER, \
            affinity_key

        try:
            router.default.set_endpoints(
                [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"])
            prompt = list(range(40))
            hdrs = {PREFIX_HEADER: affinity_key(prompt)}

            def gen():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{router.port}"
                    "/v1/models/m:generate",
                    data=json.dumps(
                        {"prompt_tokens": [prompt]}).encode(),
                    headers={"Content-Type": "application/json",
                             **hdrs})
                with urllib.request.urlopen(req, timeout=15) as r:
                    return r.status

            assert gen() == 200  # learn the map
            chaos.install(chaos.parse_spec("router.affinity:count=50"))
            try:
                assert all(gen() == 200 for _ in range(6))
                assert chaos.injected_counts().get(
                    "router.affinity", 0) >= 6
            finally:
                chaos.reset()
            assert not router._affinity or gen() == 200
        finally:
            router.stop()
            s1.stop()
            s2.stop()

    def test_two_replica_e2e_same_prefix_same_replica(self, lm_export):
        """The fleet-level prefix-cache e2e: two in-process LM servers
        behind one Router, chunked prefill ON, three same-prefix
        requests with the client-computed X-Kfx-Prefix header — the
        2nd and 3rd route to the SAME replica and skip the shared
        prefill there (that replica's engine reports reused prompt
        tokens; the other replica never saw the prefix), with zero
        failed requests; under router.affinity chaos requests keep
        succeeding on plain load balancing."""
        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.serving.lm_server import LMPredictor
        from kubeflow_tpu.serving.prefix import PREFIX_HEADER, \
            affinity_key
        from kubeflow_tpu.serving.router import Router
        from kubeflow_tpu.serving.server import ModelServer

        saved = {k: os.environ.get(k)
                 for k in ("KFX_LM_SPEC", "KFX_LM_KV_PAGE_SIZE",
                           "KFX_LM_PREFILL_CHUNK")}
        os.environ.update({"KFX_LM_SPEC": "0",
                           "KFX_LM_KV_PAGE_SIZE": "16",
                           "KFX_LM_PREFILL_CHUNK": "16"})
        servers = []
        router = None
        try:
            for _ in range(2):
                p = LMPredictor(lm_export, name="fleet",
                                warm_buckets=[8])
                p.load()
                srv = ModelServer(port=0)
                srv.register(p)
                srv.start()
                servers.append(srv)
            reg = MetricsRegistry()
            router = Router(metrics=reg, name="fleet",
                            namespace="ns").start()
            router.default.set_endpoints(
                [f"127.0.0.1:{s.port}" for s in servers])
            system = [(5 * i + 7) % 60 for i in range(32)]  # 2 pages
            url = (f"http://127.0.0.1:{router.port}"
                   "/v1/models/fleet:generate")

            def gen(tail_tok):
                prompt = system + [tail_tok]
                req = urllib.request.Request(
                    url, data=json.dumps(
                        {"prompt_tokens": [prompt],
                         "max_new_tokens": 4}).encode(),
                    headers={"Content-Type": "application/json",
                             PREFIX_HEADER: affinity_key(prompt)})
                with urllib.request.urlopen(req, timeout=45) as r:
                    return json.load(r)["generated_tokens"][0]

            outs = [gen(60 + i) for i in range(3)]
            assert all(len(o) == 4 for o in outs)
            assert reg.counter(
                "kfx_router_prefix_affinity_hits_total").value(
                    namespace="ns", isvc="fleet") >= 2

            def engine_stats(srv):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics"
                        "?format=json", timeout=10) as r:
                    return json.load(r)["engine"]["fleet"]

            stats = [engine_stats(s) for s in servers]
            reused = [s.get("prefix_tokens_reused", 0) for s in stats]
            admitted = [s.get("prompt_tokens_admitted", 0)
                        for s in stats]
            # One replica served all three (2 followers x 2 shared
            # pages = 64+ reused tokens); the other never admitted a
            # prompt at all — the per-replica cache became a fleet
            # cache.
            assert sorted(admitted) [0] == 0, (admitted, reused)
            assert max(reused) >= 2 * 32, (admitted, reused)
            # Affinity loss under chaos: plain LB, zero failures.
            chaos.install(chaos.parse_spec("router.affinity:count=10"))
            try:
                assert all(len(gen(50 + i)) == 4 for i in range(3))
            finally:
                chaos.reset()
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


# -- operator: crash-loop backoff (host-side unit) ----------------------------


class _FakeProc:
    def __init__(self):
        self.dead = False

    def poll(self):
        return 1 if self.dead else None

    def terminate(self):
        self.dead = True

    def kill(self):
        self.dead = True


class TestCrashLoopBackoff:
    def _rev(self, tmp_path, monkeypatch):
        from kubeflow_tpu.operators.serving import _Replica, _Revision

        rev = _Revision(name="default", model_name="m", model_dir="",
                        workdir=str(tmp_path), batcher=None)

        def fake_spawn():
            rev.replicas.append(
                _Replica(proc=_FakeProc(),
                         port=9000 + len(rev.replicas)))

        monkeypatch.setattr(rev, "spawn", fake_spawn)
        return rev

    def test_backoff_doubles_gates_respawn_and_resets(self, tmp_path,
                                                      monkeypatch):
        rev = self._rev(tmp_path, monkeypatch)
        rev.reap_and_respawn(1)
        assert len(rev.replicas) == 1 and rev.last_crashes == 0
        rev.replicas[0].proc.dead = True
        rev.reap_and_respawn(1)
        # Crash counted, respawn gated by the fresh backoff window.
        assert rev.last_crashes == 1 and rev.restarts == 1
        assert rev.backoff_s == 0.5
        assert len(rev.replicas) == 0
        rev.backoff_until = 0.0  # window elapsed
        rev.reap_and_respawn(1)
        assert len(rev.replicas) == 1
        rev.replicas[0].proc.dead = True
        rev.reap_and_respawn(1)
        assert rev.backoff_s == 1.0  # doubled
        # What the controller does when a replica reaches readiness:
        # the next crash backs off from 0.5s again.
        rev.backoff_s = 0.0
        rev.backoff_until = 0.0
        rev.reap_and_respawn(1)
        rev.replicas[0].proc.dead = True
        rev.reap_and_respawn(1)
        assert rev.backoff_s == 0.5


# -- the chaos e2e: kill / drain / wedge on a 2-replica isvc ------------------


MANIFEST = """
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: fleet
spec:
  predictor:
    minReplicas: {n}
    maxReplicas: {n}
    drainWindowSeconds: 6
    speculative: {{enabled: false}}
    {quant}jax:
      storageUri: file://{export}
"""


def _replica_ports(home):
    ports = []
    for path in glob.glob(os.path.join(home, "serving", "*",
                                       "default-*.log")):
        with open(path) as f:
            ports += [int(m) for m in
                      re.findall(r"server_ready .*?port=(\d+)",
                                 f.read())]
    return sorted(set(ports))


def _busy_replica_port(home, timeout=30):
    """Which replica holds the in-flight request right now? Polls each
    replica's /metrics JSON for queue depth or slot occupancy — works
    even while the engine loop is wedged (the HTTP threads live on)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for p in _replica_ports(home):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{p}/metrics?format=json",
                        timeout=2) as r:
                    eng = json.load(r).get("engine") or {}
            except (OSError, ValueError):
                continue
            if any(row.get("queue_depth", 0) > 0
                   or row.get("slot_occupancy", 0) > 0
                   for row in eng.values()):
                return p
        time.sleep(0.1)
    raise AssertionError("never saw the in-flight request on a replica")


class TestFleetSelfHealingE2E:
    def test_kill_drain_wedge(self, lm_export, tmp_path, monkeypatch,
                              capsys):
        """The acceptance e2e, three legs on one 2-replica LM isvc:

        1. replica.kill SIGKILLs the replica holding an in-flight
           generate (held mid-admission by a deterministic chaos
           delay) -> the router re-dispatches and the completion is
           byte-identical to the uninterrupted reference; the operator
           counts a crashed restart and respawns.
        2. scale-in (minReplicas 2 -> 1) under continuous load drains
           the doomed replica before the kill: zero failed client
           requests, ReplicaDrained event + serving.drain span.
        3. a quantization spec change respawns the revision (drain on
           the respawn path too); the new replicas carry an
           engine.wedge budget — the first busy loop stalls, liveness
           fails, the operator kills it with reason=wedged and the
           in-flight request recovers on the peer."""
        from kubeflow_tpu.apiserver import ApiServer
        from kubeflow_tpu.controlplane import ControlPlane

        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        import scrape_metrics

        home = str(tmp_path / "kfx")
        state1 = str(tmp_path / "chaos-admit.json")
        # Replica-inherited plan: exactly ONE admission — the second
        # ever, i.e. the kill-leg request (after=1 skips the
        # reference) — stalls 8s, so the SIGKILL lands mid-request
        # deterministically.
        monkeypatch.setenv(
            "KFX_CHAOS",
            f"state={state1};engine.admit:mode=delay,delay=8,"
            "after=1,count=1")

        def manifest(n, quant=False):
            q = "quantization: {kv: int8}\n    " if quant else ""
            return MANIFEST.format(n=n, quant=q, export=lm_export)

        with ControlPlane(home=home) as cp:
            cp.apply_text(manifest(2))
            cp.wait_for_condition("InferenceService", "fleet", "Ready",
                                  timeout=240)
            url = cp.store.get("InferenceService", "fleet").status["url"]
            gen = f"{url}/v1/models/fleet:generate"
            body = {"prompt_tokens": [[5, 9, 11, 3, 7]],
                    "max_new_tokens": 12, "seed": 0}

            def post(timeout=60.0):
                return _post_json(gen, body, timeout=timeout)[1][
                    "generated_tokens"][0]

            def ready_replicas():
                st = cp.store.get("InferenceService", "fleet").status
                return int((st.get("readyReplicas") or {})
                           .get("default") or 0)

            def restarts(reason):
                return sum(
                    int(v) for labels, v in cp.metrics.counter(
                        "kfx_replica_restarts_total").samples()
                    if labels.get("reason") == reason)

            def wait_for(pred, timeout, what):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if pred():
                        return
                    time.sleep(0.2)
                raise AssertionError(f"timed out waiting for {what}")

            reference = post()  # admission draw 0: undelayed
            assert len(reference) == 12

            # ---- leg 1: replica.kill mid-request -> recovery --------
            result = {}
            t = threading.Thread(
                target=lambda: result.update(tokens=post()))
            t.start()
            assert len(_replica_ports(home)) >= 2
            busy = _busy_replica_port(home)
            # SIGKILL exactly the replica holding the request.
            chaos.install(chaos.parse_spec(
                f"replica.kill:count=1,match=/{busy}"))
            try:
                t.join(90)
            finally:
                chaos.install(None)
            assert not t.is_alive(), "recovered generate never returned"
            # Byte-identical greedy completion on the survivor.
            assert result["tokens"] == reference
            assert sum(
                int(v) for _, v in cp.metrics.counter(
                    "kfx_router_recoveries_total").samples()) >= 1
            wait_for(lambda: restarts("crashed") >= 1, 30,
                     "crashed-restart counter")
            # The reap reconcile counts the restart BEFORE it syncs
            # status, so readyReplicas can still read the stale
            # pre-kill 2 in that window — wait for the RESPAWNED
            # replica's own server_ready line (a third port in the
            # logs) before trusting readiness, the same stale-status
            # guard leg 3 uses for the revision swap.
            wait_for(lambda: len(_replica_ports(home)) >= 3, 120,
                     "respawned replica to print server_ready")
            wait_for(lambda: ready_replicas() >= 2, 90,
                     "respawn after kill")

            # ---- leg 2: scale-in under load drains ------------------
            failures = []
            stop = threading.Event()
            short = {"prompt_tokens": [[5, 9, 11, 3, 7]],
                     "max_new_tokens": 4, "seed": 0}

            def hammer():
                while not stop.is_set():
                    try:
                        _post_json(gen, short, timeout=30)
                    except Exception as e:
                        failures.append(repr(e))
                    time.sleep(0.05)

            threads = [threading.Thread(target=hammer)
                       for _ in range(3)]
            for th in threads:
                th.start()
            time.sleep(1.0)
            cp.apply_text(manifest(1))
            try:
                wait_for(lambda: ready_replicas() == 1, 60,
                         "scale-in to 1 replica")
                time.sleep(1.0)  # stragglers resolve
            finally:
                stop.set()
                for th in threads:
                    th.join()
            assert not failures, (
                f"in-flight requests failed during drained scale-in: "
                f"{failures[:5]}")
            reasons = [e.reason for e in cp.store.events_for(
                "InferenceService", "default/fleet")]
            assert "ReplicaDrained" in reasons

            # ---- leg 3: wedge after the quant-respawn path ----------
            state2 = str(tmp_path / "chaos-wedge.json")
            monkeypatch.setenv("KFX_LM_STALL_S", "1")
            monkeypatch.setenv(
                "KFX_CHAOS",
                f"state={state2};engine.wedge:count=1,delay=25")

            def revisions_created():
                return sum(1 for e in cp.store.events_for(
                    "InferenceService", "default/fleet")
                    if e.reason == "RevisionCreated")

            n_created = revisions_created()
            cp.apply_text(manifest(2, quant=True))
            # The ready count is stale until the operator processes
            # the spec change: wait for the swap itself (a second
            # RevisionCreated event) before trusting readiness.
            wait_for(lambda: revisions_created() > n_created, 60,
                     "revision swap to be observed")
            wait_for(lambda: ready_replicas() >= 2, 180,
                     "revision respawn with the wedge budget")
            out = post(timeout=90.0)  # wedges one replica; peer serves
            assert len(out) == 12
            wait_for(lambda: restarts("wedged") >= 1, 30,
                     "wedged-restart counter")
            reasons = [e.reason for e in cp.store.events_for(
                "InferenceService", "default/fleet")]
            assert "ReplicaWedged" in reasons

            # ---- leg 3b: postmortem bundle for the wedged kill ------
            # The liveness kill captured a bundle BEFORE the SIGKILL:
            # the flight ring inside is frozen at the stalled
            # iteration, with the wedged request's slot on the last
            # record and the heartbeat that condemned the replica.
            assert "ReplicaPostmortem" in reasons
            bundles = sorted(glob.glob(os.path.join(
                home, "serving", "*", "postmortem", "*")))
            assert bundles, "no postmortem bundle on disk"
            with open(os.path.join(bundles[-1], "meta.json")) as f:
                meta = json.load(f)
            assert meta["reason"] == "wedged"
            assert meta["isvc"] == "fleet"
            with open(os.path.join(bundles[-1], "flight.json")) as f:
                flight_doc = json.load(f)
            snap = next(iter(flight_doc["models"].values()))
            recs = snap["records"]
            hb = snap.get("heartbeat") or {}
            assert recs, "bundled flight ring is empty"
            assert hb.get("wedged") is True
            assert recs[-1]["it"] == hb["iterations"]
            assert recs[-1]["active"] or recs[-1]["prefilling"]
            assert sum(int(v) for labels, v in cp.metrics.counter(
                "kfx_postmortems_total").samples()
                if labels.get("reason") == "wedged") >= 1
            # `kfx postmortem fleet` lists the bundle and renders the
            # ring with the stalled iteration marked.
            from kubeflow_tpu.cli import KfxCLI
            capsys.readouterr()
            assert KfxCLI(cp).postmortem("fleet", "default") == 0
            rendered = capsys.readouterr().out
            assert "wedged" in rendered
            assert "<== WEDGED after this iteration" in rendered

            # ---- observability: span + scrape -----------------------
            span_names = set()
            for path in glob.glob(os.path.join(home, "spans",
                                               "*.jsonl")):
                with open(path) as f:
                    span_names |= {json.loads(line).get("name")
                                   for line in f if line.strip()}
            assert "serving.drain" in span_names
            with ApiServer(cp, port=0) as srv:
                assert scrape_metrics.main(
                    [f"{srv.url}/metrics",
                     "--require", "kfx_replica_restarts_total",
                     "--require", "kfx_router_ejections_total",
                     "--require", "kfx_router_recoveries_total",
                     "--require", "kfx_serving_drain_seconds"]) == 0

    def test_stream_mid_stream_recovery_e2e(self, lm_export, tmp_path,
                                            monkeypatch):
        """ISSUE 17 acceptance: SIGKILL the replica AFTER >= 1 token
        event already reached the SSE client — the router re-dispatches
        to the peer with ``stream_skip`` raised by the relayed count,
        the peer regenerates from the same seed and suppresses the
        prefix, and the client's concatenated stream is byte-identical
        to the uninterrupted greedy reference, counted under
        kfx_router_recoveries_total{mode="mid_stream"}.

        Determinism: the replicas inherit an engine.wedge budget over a
        shared state file (count=1, after=3) — with 4-token engine
        chunks the streaming request's replica freezes mid-decode with
        8-12 of its 32 tokens already relayed, holding the stream open
        for 20s while the client finds the busy port and installs the
        seeded replica.kill. The wedge count is consumed, so neither
        the peer nor the respawn ever stalls."""
        import http.client

        from kubeflow_tpu.controlplane import ControlPlane

        home = str(tmp_path / "kfx")
        state = str(tmp_path / "chaos-stream.json")
        monkeypatch.setenv("KFX_LM_ENGINE_CHUNK", "4")
        monkeypatch.setenv(
            "KFX_CHAOS",
            f"state={state};engine.wedge:count=1,delay=20,after=3")

        with ControlPlane(home=home) as cp:
            cp.apply_text(MANIFEST.format(n=2, quant="",
                                          export=lm_export))
            cp.wait_for_condition("InferenceService", "fleet", "Ready",
                                  timeout=240)
            url = cp.store.get("InferenceService", "fleet").status["url"]
            host, port = url.split("//", 1)[1].rsplit(":", 1)
            body = json.dumps({"prompt_tokens": [[5, 9, 11, 3, 7]],
                               "max_new_tokens": 32, "seed": 0,
                               "stream": True}).encode()
            conn = http.client.HTTPConnection(host, int(port),
                                              timeout=120)
            events, killed, lines = [], False, []
            try:
                conn.request("POST", "/v1/models/fleet:generate",
                             body=body,
                             headers={"Content-Type":
                                      "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200
                assert "text/event-stream" in resp.getheader(
                    "Content-Type", "")
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    lines.append(line)
                    if line not in (b"\n", b"\r\n"):
                        continue
                    for ln in b"".join(lines).splitlines():
                        if ln.startswith(b"data: "):
                            events.append(json.loads(ln[6:]))
                    lines = []
                    if events and events[-1].get("done"):
                        break
                    if not killed and any("token" in e
                                          for e in events):
                        # >= 1 token is client-visible and the holder
                        # is wedged: SIGKILL exactly that replica.
                        busy = _busy_replica_port(home)
                        chaos.install(chaos.parse_spec(
                            f"replica.kill:count=1,match=/{busy}"))
                        killed = True
            finally:
                chaos.install(None)
                conn.close()
            assert killed, "no token event ever reached the client"
            tokens = [e["token"] for e in events if "token" in e]
            indices = [e["index"] for e in events if "token" in e]
            # Zero duplicates, zero gaps across the splice point.
            assert indices == list(range(32)), events
            assert events[-1].get("done")
            assert events[-1]["n_tokens"] == 32
            # Byte-identical to an uninterrupted greedy run (same
            # seed, buffered, served by the surviving replica).
            ref = _post_json(
                f"{url}/v1/models/fleet:generate",
                {"prompt_tokens": [[5, 9, 11, 3, 7]],
                 "max_new_tokens": 32, "seed": 0},
                timeout=60)[1]["generated_tokens"][0]
            assert tokens == ref
            assert sum(
                int(v) for labels, v in cp.metrics.counter(
                    "kfx_router_recoveries_total").samples()
                if labels.get("mode") == "mid_stream") >= 1
