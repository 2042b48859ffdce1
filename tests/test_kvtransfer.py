"""KV transfer plane tests (docs/serving.md "KV as a fleet resource"):
the wire codec's chain-digest discipline, the host-RAM offload tier,
live migration byte-parity (mid-decode greedy AND seeded, mid-prefill
cursor), the prefill->decode disaggregation handoff, severed-transfer
fail-safety (zero lost requests), and the fleet e2e — a migration
UNDER an open SSE stream whose client-visible bytes must concatenate
identical to an uninterrupted run."""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu import chaos
from kubeflow_tpu.serving import kvtransfer

PROMPT = [5, 9, 11, 3, 7]


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


# -- wire codec ----------------------------------------------------------------


class TestWireCodec:
    HEADER = {"format": 1, "model": "m", "resume": "abc",
              "blocks": [0, 2]}
    FRAMES = [b"A" * 100, b"B" * 7, b""]

    def test_roundtrip_and_peek(self):
        raw = kvtransfer.encode(self.HEADER, self.FRAMES)
        hdr = kvtransfer.peek(raw)
        # encode stamps the per-frame sizes; peek never walks frames.
        assert hdr["frames"] == [100, 7, 0]
        assert hdr["model"] == "m" and hdr["blocks"] == [0, 2]
        hdr2, frames = kvtransfer.decode(raw)
        assert hdr2 == hdr
        assert frames == self.FRAMES

    def test_verification_is_per_page(self):
        raw = kvtransfer.encode(self.HEADER, self.FRAMES)
        # A single flipped payload bit breaks the chain at that frame.
        flipped = bytearray(raw)
        flipped[raw.index(b"A" * 100) + 5] ^= 0x40
        with pytest.raises(kvtransfer.TransferCorrupt,
                           match="chain digest"):
            kvtransfer.decode(bytes(flipped))
        # A severed stream (mid-frame truncation) fails loudly.
        with pytest.raises(kvtransfer.TransferCorrupt,
                           match="severed|truncated"):
            kvtransfer.decode(raw[:-3])
        # Bytes past the last frame are an error, not ignored.
        with pytest.raises(kvtransfer.TransferCorrupt,
                           match="trailing"):
            kvtransfer.decode(raw + b"zz")
        with pytest.raises(kvtransfer.TransferError, match="magic"):
            kvtransfer.decode(b"HTTP/1.1 200 OK\r\n\r\n")

    def test_resume_key_covers_every_knob(self):
        base = ([1, 2, 3], 8, 0.5, 4, 7, -1, "")
        key = kvtransfer.resume_key(*base)
        assert key == kvtransfer.resume_key(*base)  # deterministic
        for i, changed in enumerate([
                ([1, 2, 9], 8, 0.5, 4, 7, -1, ""),
                ([1, 2, 3], 9, 0.5, 4, 7, -1, ""),
                ([1, 2, 3], 8, 0.6, 4, 7, -1, ""),
                ([1, 2, 3], 8, 0.5, 5, 7, -1, ""),
                ([1, 2, 3], 8, 0.5, 4, 8, -1, ""),
                ([1, 2, 3], 8, 0.5, 4, 7, 0, ""),
                ([1, 2, 3], 8, 0.5, 4, 7, -1, "tuned")]):
            assert kvtransfer.resume_key(*changed) != key, i


class TestHostOffloadTier:
    def test_lru_bound_and_counters(self):
        tier = kvtransfer.HostOffloadTier(2)
        tier.put(b"k1", b"p1")
        tier.put(b"k2", b"p2")
        tier.put(b"k1", b"p1")  # refresh, not duplicate
        assert len(tier) == 2 and tier.demoted == 2
        tier.put(b"k3", b"p3")  # k2 (LRU) falls out
        assert tier.get(b"k2") is None
        assert tier.get(b"k1") == b"p1"
        assert tier.pop(b"k3") == b"p3" and tier.promoted == 1
        assert tier.pop(b"k3") is None and tier.promoted == 1
        tier.clear()
        assert len(tier) == 0


# -- live decode migration (engine level) --------------------------------------


@pytest.fixture(scope="module")
def pair(tiny_lm):
    """A donor/receiver engine pair with identical KV geometry, page
    gather/scatter pre-warmed so no compile lands inside a migration
    timing window."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, params = tiny_lm
    donor = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                         name="kv-donor", kv_page_size=16)
    recv = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                        name="kv-recv", kv_page_size=16)
    for e in (donor, recv):
        e.warm([8])
        e._gather_fn()
        e._scatter_fn()
    yield donor, recv
    donor.close()
    recv.close()


def _submit_throttled(eng, **kw):
    """Submit with a 50ms per-token brake (on_token runs on the loop
    thread), so a migration catches the request mid-decode instead of
    racing its completion: a migration is two round trips to the loop
    thread and the peer's import, 0.2-0.35 s on an idle host, and at
    20ms a token the request's 0.5 s were gone first on a loaded one."""
    return eng.submit(PROMPT, max_new_tokens=24,
                      on_token=lambda t: time.sleep(0.05), **kw)


def _wait_tokens(req, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while len(req.tokens) < n:
        assert time.monotonic() < deadline, \
            f"only {len(req.tokens)} tokens after {timeout}s"
        time.sleep(0.002)


class TestLiveMigration:
    def _migrate(self, donor, recv, **kw):
        from kubeflow_tpu.serving.engine import RequestMigrated

        adopted = []
        req = _submit_throttled(donor, **kw)
        _wait_tokens(req, 2)
        stats = donor.migrate_out(
            reason="drain",
            send=lambda p: (adopted.append(recv.kv_import(p)),
                            "recv-local")[1])
        assert stats["moved"] == 1 and stats["pages"] >= 1, stats
        with pytest.raises(RequestMigrated) as ei:
            req.result(timeout=30)
        assert ei.value.peer == "recv-local"
        assert len(req.tokens) >= 2  # the donor really was mid-decode
        return adopted[0].result(timeout=60)

    def test_mid_decode_greedy_byte_parity(self, pair):
        donor, recv = pair
        ref = donor.generate([PROMPT], max_new_tokens=24)[0]
        out = self._migrate(donor, recv)
        assert out == ref

    def test_mid_decode_seeded_byte_parity(self, pair):
        """Sampled decodes resume byte-identically too: the RNG stash
        and the pending logits row ride the transfer."""
        donor, recv = pair
        ref = donor.generate([PROMPT], max_new_tokens=24,
                             temperature=0.8, top_k=8, seed=7)[0]
        out = self._migrate(donor, recv, temperature=0.8, top_k=8,
                            seed=7)
        assert out == ref
        assert len(out) == 24

    def test_severed_transfer_loses_nothing(self, pair):
        """The kv.transfer chaos point severs the send mid-migration:
        the donor's copy stays authoritative and serves the request
        exactly as if no migration was attempted."""
        donor, recv = pair
        ref = donor.generate([PROMPT], max_new_tokens=24)[0]
        req = _submit_throttled(donor)
        _wait_tokens(req, 2)
        chaos.install(chaos.parse_spec("kv.transfer:count=1"))
        try:
            stats = donor.migrate_out(
                reason="drain",
                send=lambda p: pytest.fail(
                    "chaos must sever before the send"))
        finally:
            chaos.reset()
        assert stats == {"moved": 0, "failed": 1, "pages": 0}
        assert req.result(timeout=60) == ref  # zero lost

    def test_corrupt_import_discards_whole_and_leaks_no_pages(
            self, pair):
        donor, recv = pair
        ref = donor.generate([PROMPT], max_new_tokens=24)[0]
        grabbed = []

        def sever(payload):
            grabbed.append(payload)
            raise kvtransfer.TransferError("sever after capture")

        req = _submit_throttled(donor)
        _wait_tokens(req, 2)
        stats = donor.migrate_out(reason="drain", send=sever)
        assert stats["failed"] == 1 and grabbed
        assert req.result(timeout=60) == ref  # donor kept its copy
        free_before = recv._mgr.n_free
        corrupt = bytearray(grabbed[0])
        corrupt[-40] ^= 0x01  # inside the last frame's payload
        with pytest.raises(kvtransfer.TransferCorrupt):
            recv.kv_import(bytes(corrupt))
        assert recv._mgr.n_free == free_before
        # The pristine payload still imports cleanly afterward — the
        # discarded corrupt stream poisoned nothing — and the adopted
        # copy resumes byte-identically from the snapshot point.
        adopted = recv.kv_import(grabbed[0])
        assert adopted.result(timeout=60) == ref


class TestPrefillCursorMigration:
    def test_mid_prefill_cursor_byte_parity(self, tiny_lm):
        """A request migrated while still CHUNKING its prompt ships
        the prefill cursor; the receiver resumes chunking at ``next``
        and the final stream is byte-identical."""
        from kubeflow_tpu.serving.engine import (DecodeEngine,
                                                 RequestMigrated)

        cfg, params = tiny_lm
        prompt = [(3 * i + 5) % 60 for i in range(40)]
        donor = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                             name="kv-cur-donor", kv_page_size=16,
                             prefill_chunk_tokens=8)
        recv = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                            name="kv-cur-recv", kv_page_size=16,
                            prefill_chunk_tokens=8)
        try:
            # Oracle on the RECEIVER: the donor must see the prompt
            # cold, or its own prefix cache would skip the chunked
            # prefill and close the mid-cursor window.
            ref = recv.generate([prompt], max_new_tokens=12)[0]
            donor.warm([64])
            donor._gather_fn()
            recv._scatter_fn()
            grabbed, adopted = [], []

            def send(payload):
                grabbed.append(payload)
                adopted.append(recv.kv_import(payload))
                return "recv-local"

            # 50ms/iteration wedge on the donor only: 5 prefill
            # chunks take >= 250ms, so the export (serviced at the
            # next iteration boundary) lands mid-cursor.
            chaos.install(chaos.parse_spec(
                "engine.wedge:count=500,delay=0.05,match=kv-cur-donor"))
            try:
                req = donor.submit(prompt, max_new_tokens=12)
                stats = None
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    if donor._prefilling:
                        stats = donor.migrate_out(reason="rebalance",
                                                  send=send)
                        break
                    time.sleep(0.002)
            finally:
                chaos.reset()
            assert stats is not None, "prefill window never opened"
            assert stats["moved"] == 1, stats
            hdr = kvtransfer.peek(grabbed[0])
            assert hdr["phase"] == "prefill"
            assert 0 < hdr["cursor"]["next"] < len(prompt)
            with pytest.raises(RequestMigrated):
                req.result(timeout=30)
            assert adopted[0].result(timeout=60) == ref
        finally:
            donor.close()
            recv.close()


class TestDisaggHandoff:
    def test_prefill_role_ships_to_decode_peer(self, tiny_lm, pair):
        """A ``role: prefill`` engine exports every finished prompt's
        pages before its first decode step; the decode peer's adopted
        generation equals a mixed engine's output."""
        from kubeflow_tpu.serving.engine import (DecodeEngine,
                                                 RequestMigrated)

        cfg, params = tiny_lm
        _, recv = pair
        ref = recv.generate([PROMPT], max_new_tokens=24)[0]
        adopted = []
        donor = DecodeEngine(
            cfg, params, n_slots=2, chunk_tokens=4,
            name="kv-pf-tier", kv_page_size=16, role="prefill",
            kv_peer_send=lambda p: (adopted.append(recv.kv_import(p)),
                                    "recv-local")[1])
        try:
            donor.warm([8])
            req = donor.submit(PROMPT, max_new_tokens=24)
            with pytest.raises(RequestMigrated):
                req.result(timeout=60)
            assert adopted
            assert adopted[0].result(timeout=60) == ref
            # The handoff thread counts AFTER the loop thread has woken
            # the waiter: where the peer finished first, nothing else
            # stands between that wake-up and this read.
            migrations = donor._reg().counter("kfx_lm_kv_migrations_total")
            deadline = time.monotonic() + 10
            while (migrations.value(model="kv-pf-tier", reason="disagg") < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert migrations.value(model="kv-pf-tier",
                                    reason="disagg") >= 1
        finally:
            donor.close()

    def test_no_peer_degrades_to_local_decode(self, tiny_lm):
        """An empty peer list (the operator has not pushed :kvpeers
        yet) refuses every handoff — the prefill replica decodes
        locally, zero lost."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm

        def no_peers(payload):
            raise kvtransfer.TransferError("no decode peers configured")

        donor = DecodeEngine(cfg, params, n_slots=2, chunk_tokens=4,
                             name="kv-pf-alone", kv_page_size=16,
                             role="prefill", kv_peer_send=no_peers)
        try:
            donor.warm([8])
            ref = donor.generate([[9, 2, 44]], max_new_tokens=8)[0]
            assert len(ref) == 8
        finally:
            donor.close()


# -- host-RAM offload tier (engine level) ---------------------------------------


class TestOffloadRoundTrip:
    def test_demote_then_promote_byte_identical(self, tiny_lm):
        """Cold prefix pages demote to host RAM at eviction and
        promote back through the compiled scatter on the next
        chain-hash match — the re-served output is byte-identical."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm
        # 1 slot x 4 blocks = a 4-page pool: every new 32-token
        # prompt (2 full pages + growth) forces evictions.
        eng = DecodeEngine(cfg, params, n_slots=1, chunk_tokens=4,
                           name="kv-off", kv_page_size=16,
                           kv_offload_pages=16)
        try:
            eng.warm([32])
            eng._gather_fn()
            eng._scatter_fn()
            prompts = [[(7 * i + j + 2) % 60 for j in range(32)]
                       for i in range(4)]
            firsts = [eng.generate([p], max_new_tokens=8)[0]
                      for p in prompts]
            assert eng._offload is not None
            assert eng._offload.demoted >= 1
            again = eng.generate([prompts[0]], max_new_tokens=8)[0]
            assert again == firsts[0]
            assert eng._offload.promoted >= 1
            # The kv.offload chaos point drops a demotion (next miss
            # recomputes) without ever corrupting service.
            chaos.install(chaos.parse_spec("kv.offload:count=1"))
            try:
                out = eng.generate([prompts[1]], max_new_tokens=8)[0]
            finally:
                chaos.reset()
            assert out == firsts[1]
        finally:
            eng.close()


# -- fleet e2e: migration under an open SSE stream ------------------------------


@pytest.fixture(scope="module")
def lm_export(tiny_lm, tmp_path_factory):
    from kubeflow_tpu.serving.lm_server import export_lm

    cfg, params = tiny_lm
    return export_lm(str(tmp_path_factory.mktemp("kv-lm")), cfg,
                     params)


class TestFleetMigrationE2E:
    def test_migration_under_open_sse_stream(self, lm_export,
                                             monkeypatch):
        """The acceptance e2e: a live migration fired while the SSE
        stream is OPEN. The donor severs the stream with the migrated
        503 hint, the router re-dispatches with ``stream_skip`` raised
        by the relayed count, the receiver attaches the re-dispatched
        body to the adopted in-flight generation by resume key, and
        the client's concatenated stream is byte-identical to an
        uninterrupted run — counted as a mid_stream recovery."""
        import http.client

        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.serving.lm_server import LMPredictor
        from kubeflow_tpu.serving.router import Router
        from kubeflow_tpu.serving.server import ModelServer

        monkeypatch.setenv("KFX_LM_SPEC", "0")
        monkeypatch.setenv("KFX_LM_KV_PAGE_SIZE", "16")
        monkeypatch.setenv("KFX_LM_ENGINE_CHUNK", "4")
        servers, preds, router = [], [], None
        try:
            for _ in range(2):
                p = LMPredictor(lm_export, name="kvfleet",
                                warm_buckets=[8])
                p.load()
                p._engine._gather_fn()
                p._engine._scatter_fn()
                srv = ModelServer(port=0)
                srv.register(p)
                srv.start()
                preds.append(p)
                servers.append(srv)
            reg = MetricsRegistry()
            router = Router(metrics=reg, name="kvfleet",
                            namespace="ns").start()
            router.default.set_endpoints(
                [f"127.0.0.1:{s.port}" for s in servers])
            url = f"http://127.0.0.1:{router.port}"

            # Operator-facing plumbing rides the same fleet:
            # ``:kvpeers`` replaces the live decode-peer set, and a
            # garbage ``:kvimport`` body is a clean 400, never a
            # crash.
            base = (f"http://127.0.0.1:{servers[0].port}"
                    "/v1/models/kvfleet")
            for peers in (["http://127.0.0.1:9"], []):
                req = urllib.request.Request(
                    f"{base}:kvpeers",
                    data=json.dumps(peers).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    assert r.status == 200
                    assert json.load(r)["peers"] == len(peers)
                assert preds[0].kv_peers == peers
            bad = urllib.request.Request(
                f"{base}:kvimport", data=b"not a transfer",
                headers={"Content-Type": "application/octet-stream"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=10)
            assert ei.value.code == 400

            body = {"prompt_tokens": [PROMPT], "max_new_tokens": 40,
                    "seed": 0}

            # Uninterrupted buffered reference, BEFORE any pacing.
            ref_req = urllib.request.Request(
                f"{url}/v1/models/kvfleet:generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(ref_req, timeout=60) as r:
                ref = json.load(r)["generated_tokens"][0]
            assert len(ref) == 40

            # 40ms/iteration wedge paces BOTH engines so the stream
            # stays open long enough to migrate under it (control
            # jobs — export/import — run before the wedge each
            # iteration, so migrate_to never waits out the full
            # pacing budget). 40 tokens at chunk 4 leaves ~9 paced
            # boundaries of donor runway past the trigger: the donor
            # keeps decoding until the peer ACKs, and a donor that
            # drains first makes the migration a benign no-op
            # (moved=0) — wide margin keeps that race out of CI even
            # on a loaded machine.
            chaos.install(chaos.parse_spec(
                "engine.wedge:count=2000,delay=0.04"))
            events, lines, stats = [], [], None
            conn = http.client.HTTPConnection("127.0.0.1",
                                              router.port,
                                              timeout=120)
            try:
                conn.request(
                    "POST", "/v1/models/kvfleet:generate",
                    body=json.dumps(dict(body, stream=True)).encode(),
                    headers={"Content-Type": "application/json"})
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
                        # The router settles its counters between the
                        # done frame and the terminal chunk: read to
                        # end-of-stream before scraping them.
                        resp.read()
                        break
                    n_tok = sum(1 for e in events if "token" in e)
                    if stats is None and n_tok >= 1:
                        # >= 1 token is client-visible: migrate the
                        # in-flight generation out from under the
                        # open stream, donor -> the other replica.
                        donor = next(
                            i for i, p in enumerate(preds)
                            if any(r is not None
                                   for r in p._engine._slots))
                        peer = (f"http://127.0.0.1:"
                                f"{servers[1 - donor].port}")
                        stats = preds[donor].migrate_to(
                            peer, reason="rebalance")
                        assert stats["moved"] == 1, stats
            finally:
                chaos.reset()
                conn.close()
            assert stats is not None, \
                "no token event ever reached the client"
            tokens = [e["token"] for e in events if "token" in e]
            indices = [e["index"] for e in events if "token" in e]
            # Zero duplicates, zero gaps across the migration splice.
            assert indices == list(range(40)), events
            assert events[-1].get("done")
            assert events[-1]["n_tokens"] == 40
            assert tokens == ref
            assert sum(
                int(v) for labels, v in reg.counter(
                    "kfx_router_recoveries_total").samples()
                if labels.get("mode") == "mid_stream") >= 1
            # The receiver adopted the pages (counted per replica).
            assert sum(
                int(v)
                for p in preds
                for labels, v in p.metrics.counter(
                    "kfx_lm_kv_migrations_total").samples()
                if labels.get("reason") == "adopted") >= 1
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()
