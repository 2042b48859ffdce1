"""SLOs as first-class resources (api/slo.py + obs/slo.py +
operators/slo.py) over the downsampled long-horizon TSDB tier
(obs/tsdb.py coarse ring) and the per-tenant metering vertical
(serving/metering.py): resource validation, the coarse-tier edge
cases (counter reset across a bucket boundary, born-mid-bucket,
fine->coarse stitch at the horizon seam, coarse-ring GC), the
deterministic burn-rate evaluation inside the scrape cycle, exact
token-ledger accounting through preemption and stream-skip recovery,
and the acceptance chaos e2e: a 2-replica LM isvc with an error-rate
SLO, an injected backend-failure burst walking the generated
fast-burn rule pending -> firing -> resolved on scrape cycles with
`kfx slo` rc 1 and a depleted budget."""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

from kubeflow_tpu.api.base import ValidationError, from_manifest
from kubeflow_tpu.api.slo import SLO
from kubeflow_tpu.obs.metrics import MetricsRegistry
from kubeflow_tpu.obs.rules import RuleEngine
from kubeflow_tpu.obs.slo import (
    FAST_BURN_THRESHOLD,
    SLOEngine,
    burn_windows,
    generated_rules,
    usage_summary,
)
from kubeflow_tpu.obs.tsdb import TSDB, CentralScraper

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _slo_dict(name="web", objective="error-rate", target=0.99,
              window=3600, selector=None, latency=None):
    spec = {"objective": objective, "target": target,
            "windowSeconds": window,
            "selector": selector if selector is not None
            else {"isvc": "web"}}
    if latency is not None:
        spec["latency"] = latency
    return {"apiVersion": "obs.kubeflow.org/v1alpha1", "kind": "SLO",
            "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


class TestSLOResource:
    def test_valid_objectives(self):
        for obj in ("error-rate", "availability"):
            slo = from_manifest(_slo_dict(objective=obj))
            assert isinstance(slo, SLO)
            slo.validate()
        lat = from_manifest(_slo_dict(
            objective="latency",
            latency={"percentile": 99, "thresholdMs": 250}))
        lat.validate()
        assert lat.latency_threshold_s() == pytest.approx(0.25)

    def test_rejects_bad_specs(self):
        bad = [
            _slo_dict(objective="uptime"),
            _slo_dict(target=1.0),
            _slo_dict(target=0.0),
            _slo_dict(target=True),
            _slo_dict(window=30),
            _slo_dict(window=7 * 86400),
            _slo_dict(selector={"pod": "x"}),
            _slo_dict(selector={"isvc": ""}),
            _slo_dict(objective="latency"),  # latency block required
            _slo_dict(objective="latency",
                      latency={"percentile": 75, "thresholdMs": 250}),
            _slo_dict(objective="latency",
                      latency={"percentile": 99, "thresholdMs": 0}),
            # latency block is meaningless on a counting objective
            _slo_dict(objective="error-rate",
                      latency={"percentile": 99, "thresholdMs": 250}),
        ]
        for d in bad:
            with pytest.raises(ValidationError):
                from_manifest(d).validate()

    def test_burn_windows_scale_and_cap(self):
        # 24h SLO alerts on the canonical SRE-workbook windows...
        assert burn_windows(86400) == ((300.0, 3600.0),
                                       (1800.0, 21600.0))
        # ...a 1h SLO tightens the short windows proportionally.
        assert burn_windows(3600) == ((300.0, 3600.0),
                                      (1800.0, 3600.0))
        assert burn_windows(60) == ((5.0, 60.0), (30.0, 60.0))
        names = [r.name for r in generated_rules("web")]
        assert names == ["slo-web-fast-burn", "slo-web-slow-burn"]


class TestCoarseTier:
    """The downsampled long-horizon tier's edge cases (ISSUE-18
    satellite): each one is a way a naive downsampler silently
    corrupts long-window answers."""

    def test_counter_reset_across_coarse_boundary(self):
        """A counter reset landing while the series is answered from
        the COARSE ring must contribute 0 increase, exactly like the
        fine path's `increase` rule — never a negative, never the
        post-reset cumulative re-counted."""
        t = TSDB(retention_s=120.0, max_samples=8, coarse_res_s=60.0)
        # 0 -> 100 -> 5 (reset, lands in a fresh coarse bucket) -> 45.
        for ts, v in [(0.0, 0.0), (50.0, 100.0), (60.0, 5.0),
                      (600.0, 45.0), (650.0, 50.0), (660.0, 55.0)]:
            t.ingest({"kfx_c_total": [({}, v)]}, ts=ts)
        # The fine ring only reaches back ~120s; the 700s window is a
        # coarse answer: 100 (pre-reset) + 0 (reset) + 40 + 5 + 5.
        res = t.query("kfx_c_total", "delta", None, 700, now=660.0)
        assert res.value == 150.0
        # No point in the series is negative (sparkline sanity).
        assert all(v >= 0 for _, v in res.points)

    def test_series_born_mid_bucket_keeps_increase_semantics(self):
        """A series whose first sample lands mid-bucket counts only
        increases AFTER birth — the birth cumulative value is a base,
        not an increase (exactly the fine path's delta contract)."""
        t = TSDB(retention_s=60.0, max_samples=4, coarse_res_s=60.0)
        t.ingest({"kfx_c_total": [({}, 500.0)]}, ts=90.0)  # born mid-bucket
        for ts, v in [(150.0, 510.0), (400.0, 520.0), (410.0, 521.0)]:
            t.ingest({"kfx_c_total": [({}, v)]}, ts=ts)
        res = t.query("kfx_c_total", "delta", None, 500, now=410.0)
        # 10 + 10 + 1 — never the all-time 521.
        assert res.value == 21.0

    def test_fine_to_coarse_stitch_at_horizon_seam(self):
        """The acceptance stitch regression: a 1h p99 keeps answering
        from the coarse histogram-bucket increases after the fine ring
        evicted the window's left edge — and agrees with the oracle
        computed from the true bucket deltas."""
        t = TSDB(retention_s=600.0, max_samples=720, coarse_res_s=60.0)
        # One hour of cumulative bucket counts at 10s scrape cadence:
        # every cycle adds 4 fast (<=0.5s), 1 slow (<=1.0s) request.
        n = 360
        for i in range(n + 1):
            t.ingest({"kfx_req_seconds_bucket": [
                ({"le": "0.5"}, 4.0 * i),
                ({"le": "1.0"}, 5.0 * i),
                ({"le": "+Inf"}, 5.0 * i)]}, ts=float(i * 10))
        now = float(n * 10)
        # The fine ring retains only ~600s of the 3600s window.
        res = t.query("kfx_req_seconds", "p99", None, 3600, now=now)
        assert res.value is not None
        # Oracle: 80% of observations <= 0.5, 100% <= 1.0 -> p99 in
        # (0.5, 1.0]; interpolation puts it near the top of the band.
        assert 0.5 < res.value <= 1.0
        fine_only = t.query("kfx_req_seconds", "p99", None, 300,
                            now=now)
        # Fine and stitched answers agree on the distribution.
        assert fine_only.value == pytest.approx(res.value, abs=0.05)
        # And a long delta stitches too (left-edge error is at most
        # one coarse bucket = 60s x the per-second rate).
        d = t.query("kfx_req_seconds_bucket", "delta", {"le": "+Inf"},
                    3600, now=now)
        assert d.value is not None
        assert abs(d.value - 5.0 * n) <= 5.0 * 6 + 1e-6

    def test_coarse_ring_gc_with_dead_series(self):
        """Dead-series GC reclaims the coarse accumulator with the
        fine ring — fleet churn must not leak one _Coarse (1440
        floats) per dead replica generation forever."""
        t = TSDB(max_series=2, retention_s=50.0)
        t.ingest({"kfx_c_total": [({"i": "old-a"}, 1.0),
                                  ({"i": "old-b"}, 1.0)]}, ts=0.0)
        assert len(t._coarse) == 2
        t.ingest({"kfx_c_total": [({"i": "new-a"}, 2.0),
                                  ({"i": "new-b"}, 2.0)]}, ts=100.0)
        got = {lab["i"] for lab, _ in t.latest_samples("kfx_c_total")}
        assert got == {"new-a", "new-b"}
        assert len(t._coarse) == 2  # old accumulators reclaimed
        assert {k[1] for k in t._coarse} == {
            (("i", "new-a"),), (("i", "new-b"),)}

    def test_same_ts_ingest_replaces_not_sums(self):
        """Last write wins per scrape timestamp: the SLO engine's
        same-cycle direct ingest of its gauges must supersede — not
        double — a registry-scraped copy of the same series at the
        same cycle ts."""
        t = TSDB()
        t.ingest({"kfx_g": [({"s": "a"}, 3.0)]}, ts=10.0)
        t.ingest({"kfx_g": [({"s": "a"}, 5.0)]}, ts=10.0)
        assert t.query("kfx_g", "latest", None, 60, now=10.0).value \
            == 5.0


class _Store:
    """Just enough of ResourceStore for SLOEngine status writes."""

    def __init__(self, objs):
        self.objs = {o.key: o for o in objs}
        self.events = []

    def get(self, kind, name, namespace="default"):
        return self.objs[f"{namespace}/{name}"]

    def list(self, kind, namespace=None):
        return list(self.objs.values())

    def update_status(self, obj):
        self.objs[obj.key] = obj

    def record_raw_event(self, kind, key, etype, reason, message=""):
        self.events.append((kind, key, etype, reason))


class TestSLOEngine:
    def _engine(self, slo_dicts):
        tsdb = TSDB()
        reg = MetricsRegistry()
        rules = RuleEngine(tsdb, [], metrics=reg)
        slos = [from_manifest(d) for d in slo_dicts]
        store = _Store(slos)
        eng = SLOEngine(tsdb, reg, store, rules)
        for s in slos:
            eng.ensure(s)
        return tsdb, reg, rules, store, eng

    def _traffic(self, tsdb, ts, good, bad):
        tsdb.ingest({"kfx_router_requests_total": [
            ({"namespace": "default", "isvc": "web", "revision": "r1",
              "code": "2xx"}, good),
            ({"namespace": "default", "isvc": "web", "revision": "r1",
              "code": "5xx"}, bad)]}, ts=ts,
            extra_labels={"instance": "router"})

    def test_error_rate_burn_and_budget_deterministic(self):
        """Pure in (tsdb, now): healthy traffic -> whole budget, an
        error burst -> burn above both thresholds on the cycle that
        scraped it, both generated rules firing in the SAME evaluate
        pass (for_s=0), status + BudgetHealthy flip + event recorded."""
        tsdb, reg, rules, store, eng = self._engine(
            [_slo_dict(window=3600)])
        bad = 0.0
        for i in range(10):
            ts = 1000.0 + i
            self._traffic(tsdb, ts, 100.0 + 50.0 * i, bad)
            rows = eng.evaluate(now=ts)
            rules.evaluate(now=ts)
        assert rows[0]["budgetRemaining"] == 1.0
        assert rows[0]["burnRateFast"] == 0.0
        slo = store.get("SLO", "web")
        assert slo.status["budgetRemaining"] == 1.0
        assert slo.has_condition("BudgetHealthy")
        # Error burst: every new request 5xx.
        for i in range(10, 40):
            ts = 1000.0 + i
            bad += 50.0
            self._traffic(tsdb, ts, 600.0, bad)
            rows = eng.evaluate(now=ts)
            rules.evaluate(now=ts)
        assert rows[0]["burnRateFast"] > FAST_BURN_THRESHOLD
        assert rows[0]["budgetRemaining"] < 0.0
        states = {st["name"]: st for st in rules.states()}
        assert states["slo-web-fast-burn"]["state"] == "firing"
        assert states["slo-web-slow-burn"]["state"] == "firing"
        # Triple-recording: gauges carry the same numbers...
        assert reg.gauge("kfx_slo_budget_remaining").value(slo="web") \
            == rows[0]["budgetRemaining"]
        assert reg.gauge("kfx_slo_burn_rate").value(
            slo="web", window="fast") == rows[0]["burnRateFast"]
        # ...the TSDB carries the same-cycle sample (not doubled)...
        assert tsdb.query("kfx_slo_burn_rate", "latest",
                          {"slo": "web", "window": "fast"}, 60,
                          now=ts).value == rows[0]["burnRateFast"]
        # ...and the store saw the BudgetHealthy flip.
        slo = store.get("SLO", "web")
        assert not slo.has_condition("BudgetHealthy")
        assert ("SLO", "default/web", "Warning", "BudgetBurning") in \
            store.events

    def test_no_traffic_is_whole_budget_not_breach(self):
        tsdb, reg, rules, store, eng = self._engine([_slo_dict()])
        rows = eng.evaluate(now=500.0)
        assert rows[0]["budgetRemaining"] == 1.0
        assert rows[0]["burnRateFast"] == 0.0

    def test_latency_objective_uses_discovered_bucket(self):
        """latency: bad = requests over the threshold, counted from
        the smallest exposed bucket bound >= thresholdMs."""
        tsdb, reg, rules, store, eng = self._engine([_slo_dict(
            objective="latency", target=0.9, window=3600,
            latency={"percentile": 99, "thresholdMs": 500})])
        for i in range(10):
            ts = 1000.0 + i * 10
            # 60% of requests <= 0.5s -> bad fraction 0.4 -> burn 4.
            tsdb.ingest({
                "kfx_serving_request_seconds_bucket": [
                    ({"namespace": "default", "isvc": "web",
                      "le": "0.5"}, 6.0 * i),
                    ({"namespace": "default", "isvc": "web",
                      "le": "+Inf"}, 10.0 * i)],
                "kfx_serving_request_seconds_count": [
                    ({"namespace": "default", "isvc": "web"},
                     10.0 * i)],
            }, ts=ts, extra_labels={"instance": "router"})
        rows = eng.evaluate(now=ts)
        assert rows[0]["burnRateSlow"] == pytest.approx(4.0)
        assert rows[0]["budgetRemaining"] == pytest.approx(-3.0)

    def test_availability_objective(self):
        """availability: bad = total - 2xx (4xx counts against the
        provider's availability here, unlike error-rate's 5xx-only)."""
        tsdb, reg, rules, store, eng = self._engine([_slo_dict(
            objective="availability", target=0.5, window=3600)])
        for i in range(5):
            ts = 1000.0 + i * 10
            tsdb.ingest({"kfx_router_requests_total": [
                ({"namespace": "default", "isvc": "web",
                  "code": "2xx"}, 3.0 * i),
                ({"namespace": "default", "isvc": "web",
                  "code": "4xx"}, 1.0 * i)]}, ts=ts,
                extra_labels={"instance": "router"})
        rows = eng.evaluate(now=ts)
        # bad fraction 0.25, denom 0.5 -> burn 0.5, budget 0.5.
        assert rows[0]["burnRateSlow"] == pytest.approx(0.5)
        assert rows[0]["budgetRemaining"] == pytest.approx(0.5)

    def test_resync_upsert_keeps_firing_state(self):
        """The controller's RESYNC re-ensures every SLO each period;
        an unchanged rule must keep its live AlertState — a resync
        that resolved a firing burn alert would mask an incident."""
        tsdb, reg, rules, store, eng = self._engine(
            [_slo_dict(window=3600)])
        bad = 0.0
        for i in range(10):
            ts = 1000.0 + i
            bad += 50.0
            self._traffic(tsdb, ts, 100.0, bad)
            eng.evaluate(now=ts)
            rules.evaluate(now=ts)
        states = {st["name"]: st for st in rules.states()}
        assert states["slo-web-fast-burn"]["state"] == "firing"
        eng.ensure(store.get("SLO", "web"))  # the resync
        states = {st["name"]: st for st in rules.states()}
        assert states["slo-web-fast-burn"]["state"] == "firing"
        # Deleting the SLO removes its rules and zeroes the gauge.
        eng.remove("web")
        assert all(not st["name"].startswith("slo-web-")
                   for st in rules.states())
        assert reg.gauge("kfx_alerts_firing").value(
            rule="slo-web-fast-burn") == 0

    def test_scrape_cycle_runs_slo_before_rules(self):
        """CentralScraper order: ingest -> SLO evaluate -> rule pass,
        all at the same cycle ts — the generated rules judge the burn
        values the CAUSING scrape produced, in one scrape_once call."""
        reg = MetricsRegistry()
        tsdb = TSDB()
        rules = RuleEngine(tsdb, [], metrics=reg)
        store = _Store([from_manifest(_slo_dict(window=3600))])
        eng = SLOEngine(tsdb, reg, store, rules)
        eng.ensure(store.get("SLO", "web"))
        sc = CentralScraper(tsdb, reg, interval_s=3600,
                            targets=lambda: [], rules=rules, slo=eng)
        c = reg.counter("kfx_router_requests_total")
        c.inc(100, namespace="default", isvc="web", code="2xx")
        c.inc(0, namespace="default", isvc="web", code="5xx")
        sc.scrape_once(now=100.0)
        c.inc(100, namespace="default", isvc="web", code="5xx")
        sc.scrape_once(now=101.0)
        states = {st["name"]: st for st in rules.states()}
        # The burst scrape itself flipped the rule — same cycle.
        assert states["slo-web-fast-burn"]["state"] == "firing"
        assert store.get("SLO", "web").status["budgetRemaining"] < 0


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            head_dim=16, n_layers=2, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


class TestTenantLedger:
    def test_ledger_units(self):
        from kubeflow_tpu.serving.metering import TenantLedger

        led = TenantLedger()
        led.admit("acme", "standard", "base", 4)
        led.retire("acme", "standard", "base", 6)
        led.admit("acme", "batch", "tuned", 2)
        led.retire("acme", "batch", "tuned", 3)
        tot = led.totals("acme")
        assert tot == {"requests": 2, "promptTokens": 6,
                       "generatedTokens": 9}
        # Projection into the registry: seeded rows export at zero.
        led.seed("newco", "standard", "newco")
        reg = MetricsRegistry()
        led.collect(reg)
        assert reg.counter("kfx_tenant_requests_total").value(
            tenant="newco", qos="standard", adapter="newco") == 0
        assert reg.counter("kfx_tenant_tokens_total").value(
            tenant="acme", qos="standard", adapter="base",
            kind="generated") == 6

    def test_engine_exactness_with_preemption_and_skip(self, tiny_lm):
        """The billing contract: ledger generated-token counts equal
        what each request actually RETURNED, exactly once — through
        preemption-by-recompute (re-prefill must not re-bill) and
        through a stream_skip recovery re-dispatch (the regenerated
        prefix is billed by meter_skip's deduction, so a recovered
        stream bills once fleet-wide)."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg, params = tiny_lm
        # The preemption pool from the engine suite: decode outgrows
        # 8x16 pages, the youngest slot completes by recompute.
        eng = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=4,
                           name="lm", kv_page_size=16, kv_pages=8,
                           prefix_cache=False)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
            reqs = [eng.submit(p, max_new_tokens=40, tenant="acme")
                    for p in prompts]
            outs = [r.result(120) for r in reqs]
            assert eng._reg().counter(
                "kfx_lm_kv_preemptions_total").value(model="lm") >= 1
            tot = eng.usage.totals("acme")
            assert tot["requests"] == 4
            assert tot["promptTokens"] == sum(len(p) for p in prompts)
            # Exactly the returned tokens — recompute re-prefilled but
            # never re-billed.
            assert tot["generatedTokens"] == sum(len(o) for o in outs)

            # Recovery semantics: a re-dispatch with meter_skip=N
            # regenerates N tokens the ORIGINAL attempt already billed
            # on a peer; this engine bills only the tail.
            req = eng.submit([9, 8, 7], max_new_tokens=8, tenant="acme",
                             meter_skip=3)
            out = req.result(60)
            tot2 = eng.usage.totals("acme")
            assert tot2["generatedTokens"] - tot["generatedTokens"] \
                == len(out) - 3
            # Unknown tenant defaults to the adapter ("base" when none).
            req = eng.submit([1, 2], max_new_tokens=4)
            req.result(60)
            led = eng.usage
            assert led.totals("base")["requests"] == 1
            # usage=None disables the hooks.
            eng.usage = None
            eng.generate([[3, 4]], max_new_tokens=4)
            assert led.totals("base")["requests"] == 1  # unchanged
        finally:
            eng.close()

    def test_usage_summary_aggregates_fleet(self):
        """usage_summary sums the newest sample per (tenant,qos,
        adapter) ACROSS instances (fleet totals) and window deltas
        stitch like any counter."""
        t = TSDB()
        fam = "kfx_tenant_tokens_total"
        rfam = "kfx_tenant_requests_total"
        for i, inst in enumerate(("r1", "r2")):
            for ts, v in [(0.0, 0.0), (50.0, 100.0 + 20 * i)]:
                t.ingest({
                    fam: [({"tenant": "acme", "qos": "standard",
                            "adapter": "base", "kind": "generated"},
                           v)],
                    rfam: [({"tenant": "acme", "qos": "standard",
                             "adapter": "base"}, v / 10.0)],
                }, ts=ts, extra_labels={"instance": inst})
        rows = usage_summary(t, window_s=100, now=50.0)
        assert len(rows) == 1
        assert rows[0]["tenant"] == "acme"
        assert rows[0]["generatedTokens"] == 220.0  # 100 + 120
        assert rows[0]["windowTokens"] == 220.0
        assert rows[0]["windowRequests"] == 22.0
        assert usage_summary(t, tenant="nobody") == []


class TestRuleInventory:
    def test_live_rule_inventory_documented(self):
        """Every rule the plane can emit — the default pack plus the
        SLO-generated templates — has a row in docs/observability.md,
        via the same check scrape_metrics --inventory runs."""
        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        from scrape_metrics import check_rule_inventory

        assert check_rule_inventory() == 0

    def test_rule_inventory_catches_undocumented_rule(self, tmp_path):
        """The checker itself must detect a gap: a rule name with no
        backticked table row fails, the same name documented passes,
        and snake_case family rows never satisfy a rule name."""
        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        from scrape_metrics import check_rule_inventory

        doc = tmp_path / "observability.md"
        doc.write_text("| `kfx_some_family_total` | counter | — |\n")
        assert check_rule_inventory(
            rules=["brand-new-rule"], doc_path=str(doc)) == 1
        doc.write_text("| `brand-new-rule` | watches x | warning |\n")
        assert check_rule_inventory(
            rules=["brand-new-rule"], doc_path=str(doc)) == 0
        # A template rendered with the <name> placeholder round-trips.
        doc.write_text("| `slo-<name>-fast-burn` | generated | c |\n")
        assert check_rule_inventory(
            rules=["slo-<name>-fast-burn"], doc_path=str(doc)) == 0


MANIFEST = """
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: tele
spec:
  predictor:
    minReplicas: 2
    maxReplicas: 2
    drainWindowSeconds: 4
    speculative: {{enabled: false}}
    jax:
      storageUri: file://{export}
---
apiVersion: obs.kubeflow.org/v1alpha1
kind: SLO
metadata:
  name: tele-errors
spec:
  objective: error-rate
  target: 0.99
  windowSeconds: 60
  selector:
    isvc: tele
"""


@pytest.fixture(scope="module")
def lm_export(tiny_lm, tmp_path_factory):
    from kubeflow_tpu.serving.lm_server import export_lm

    cfg, params = tiny_lm
    return export_lm(str(tmp_path_factory.mktemp("slo-lm")), cfg,
                     params)


class TestSLOFleetE2E:
    def test_error_burst_slo_lifecycle(self, lm_export, tmp_path,
                                       monkeypatch, capsys):
        """The ISSUE-18 acceptance e2e on one 2-replica LM isvc:

        1. applying the SLO generates its burn rules (status.rules,
           Ready condition) and seeds a whole budget;
        2. a chaos-injected backend-failure burst turns requests 5xx
           -> the fast-burn rule walks pending -> firing on the scrape
           cycle that saw it (kind=Alert events in order), `kfx slo`
           exits 1, status shows the budget depleted with a
           BudgetBurning event;
        3. clean traffic drains the short burn window -> resolved,
           `kfx slo` exits 0 — while the 60s budget window still
           remembers the burst;
        4. `kfx usage` totals equal the exact ledger counts of what
           the engines actually served."""
        from kubeflow_tpu.cli import KfxCLI
        from kubeflow_tpu.controlplane import ControlPlane

        state = str(tmp_path / "chaos-req.json")
        monkeypatch.setenv("KFX_OBS_INTERVAL", "0.25")
        # 8 injected connection failures = 4 fully-failed requests
        # (the router retries each once on the peer).
        monkeypatch.setenv(
            "KFX_CHAOS",
            f"state={state};serving.request:count=8")

        def wait_for(pred, timeout, what):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.2)
            raise AssertionError(f"timed out waiting for {what}")

        with ControlPlane(home=str(tmp_path / "kfx")) as cp:
            cp.apply_text(MANIFEST.format(export=lm_export))
            cp.wait_for_condition("InferenceService", "tele", "Ready",
                                  timeout=240)
            slo = cp.wait_for_condition("SLO", "tele-errors", "Ready",
                                        timeout=30)
            assert slo.status["rules"] == ["slo-tele-errors-fast-burn",
                                           "slo-tele-errors-slow-burn"]
            # Seeded: the budget gauge exports whole before traffic.
            assert cp.metrics.gauge("kfx_slo_budget_remaining").value(
                slo="tele-errors") == 1.0

            # Ledger exactness needs each replica's SEEDED zero rows
            # scraped before traffic: a series born mid-window keeps
            # its birth value as a base, so a request billed before
            # that replica's first scrape would be invisible to
            # window deltas (exactly Prometheus' increase() blind
            # spot). Both replicas export the base-tenant zero row
            # from startup — wait for the scraper to have seen both.
            from kubeflow_tpu.serving.metering import REQUESTS_FAMILY

            def scraped_instances():
                return {ls.get("instance") for ls, _ in
                        cp.telemetry.latest_samples(
                            REQUESTS_FAMILY, {"tenant": "base"})}

            wait_for(lambda: len(scraped_instances()) >= 2, 30,
                     "both replicas' seeded ledger rows scraped")

            url = cp.store.get("InferenceService",
                               "tele").status["url"]
            gen = f"{url}/v1/models/tele:generate"
            body = json.dumps({"prompt_tokens": [[5, 9, 11, 3]],
                               "max_new_tokens": 6,
                               "seed": 0}).encode()

            ok = {"posts": 0}

            def post():
                req = urllib.request.Request(
                    gen, data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=90) as r:
                        out = json.load(r)["generated_tokens"][0]
                    assert len(out) == 6
                    ok["posts"] += 1
                    return True
                except urllib.error.HTTPError as e:
                    assert e.code == 502  # the chaos burst
                    return False

            # The burst: the chaos budget fails both dispatch attempts
            # of 4 requests -> 4x 5xx against ~0 successes.
            failures = sum(0 if post() else 1 for _ in range(6))
            assert failures >= 3

            def alert_reasons():
                return [e.reason for e in cp.store.events_for(
                    "Alert", "slo-tele-errors-fast-burn")]

            wait_for(lambda: "AlertFiring" in alert_reasons(), 30,
                     "fast-burn alert firing")
            cli = KfxCLI(cp)
            capsys.readouterr()
            assert cli.slo() == 1  # page-now rc while fast-burn fires
            out = capsys.readouterr().out
            assert "slo-tele-errors-fast-burn" in out
            assert "firing" in out
            cur = cp.store.get("SLO", "tele-errors")
            assert cur.status["budgetRemaining"] <= 0
            assert any(
                e.reason == "BudgetBurning" for e in
                cp.store.events_for("SLO", "default/tele-errors"))

            # Clean traffic ages the burst out of the 5s fast window.
            def resolved():
                post()
                return "AlertResolved" in alert_reasons()

            wait_for(resolved, 60, "fast-burn resolution")
            reasons = alert_reasons()
            assert reasons.index("AlertPending") <= \
                reasons.index("AlertFiring") < \
                reasons.index("AlertResolved")
            capsys.readouterr()
            rc = cli.slo(as_json=True)
            payload = json.loads(capsys.readouterr().out)
            assert rc == 0 and payload["firingFast"] == 0
            # The 60s budget window still remembers the burst.
            row = next(s for s in payload["slos"]
                       if s["metadata"]["name"] == "tele-errors")
            assert row["status"]["budgetRemaining"] < 1.0

            # (4) ledger exactness: scraped fleet totals == what the
            # engines actually admitted/served — billed exactly once.
            expect_req = ok["posts"]

            def totals():
                rows = usage_summary(cp.telemetry, window_s=3600)
                base = [r for r in rows if r["tenant"] == "base"]
                return base[0] if base else None

            # A request is billed at admission and its generated tokens
            # at retirement: a scrape between the two already counts the
            # last request, so wait for its tokens too.
            def settled():
                row = totals() or {}
                return (row.get("windowRequests") == expect_req
                        and row.get("generatedTokens") == 6 * expect_req)

            wait_for(settled, 30,
                     "scraped ledger totals matching served requests")
            row = totals()
            assert row["promptTokens"] == 4 * expect_req
            assert row["generatedTokens"] == 6 * expect_req
            capsys.readouterr()
            assert cli.usage() == 0
            out = capsys.readouterr().out
            assert "base" in out and "TENANT" in out
            assert cli.usage(tenant="nobody") == 1  # empty -> rc 1
            capsys.readouterr()

            # `kfx trace --tenant` satellite: the router.dispatch spans
            # of this burst carry the billable tenant attribute.
            from kubeflow_tpu.obs import timeline
            from kubeflow_tpu.obs.trace import SPANS_DIRNAME
            import glob as _glob

            dirs = [os.path.join(cp.home, SPANS_DIRNAME)]
            dirs += sorted(_glob.glob(os.path.join(
                cp.home, "serving", "*", SPANS_DIRNAME)))
            spans = timeline.load_spans(timeline.span_files(dirs), "")
            tenant_spans = timeline.filter_spans(spans, tenant="base")
            assert any(s["name"] == "router.dispatch"
                       for s in tenant_spans)
            assert timeline.filter_spans(spans, tenant="nobody") == []
