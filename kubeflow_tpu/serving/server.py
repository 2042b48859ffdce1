"""Model server: the KFServing data plane, XLA-compiled.

V1 protocol parity (reference kfserving python server, SURVEY.md §3 CS3):
    GET  /v1/models                     -> {"models": [...]}
    GET  /v1/models/{m}                 -> {"name": m, "ready": true}
    POST /v1/models/{m}:predict         -> {"predictions": [...]}
    POST /v1/models/{m}:evict           -> {"model": n, "evicted": b}
    GET  /healthz | /metrics
    POST /drain[?wait_s=S]              -> {"draining": true, "drained": b}

/healthz is a real liveness probe, not a does-the-socket-answer ping:
it aggregates the LM decode engines' progress heartbeats and returns
503 {"status": "wedged"} when a loop has stalled with work in flight
(the operator's liveness probe restarts the replica). /drain is the
operator's pre-kill hook: readiness flips false, new requests shed
with 503 + Retry-After (the router re-dispatches them), and in-flight
work finishes within the bounded wait — planned replica churn
(scale-in, revision respawn) never loses a request.

TPU-first serving mechanics (vs the reference's per-request python
predict):
  * predict is jit-compiled per batch-size *bucket* (1,2,4,...,max) and
    pre-warmed at load, so no request ever pays a compile;
  * requests are padded up to the bucket — static shapes, no retrace;
  * an optional micro-batcher aggregates concurrent requests into one
    device dispatch (maxBatchSize/maxLatencyMs, the KFServing batcher
    contract) — throughput rides the MXU's preference for batched matmuls.

Runs standalone (`python -m kubeflow_tpu.serving.server --model-dir ...`)
or supervised by the InferenceService operator.
"""

from __future__ import annotations

import argparse
import functools
import json
import logging
import os
import queue
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import chaos
from ..obs import trace as obs_trace
from ..obs.metrics import MetricsRegistry
from ..obs.trace import SPAN_HEADER, TRACE_HEADER
from .engine import EngineOverloaded, quant_mode_string

request_log = logging.getLogger("kfx.serving")

# Request-latency buckets (seconds): sub-millisecond host predicts up
# to multi-second LM generations.
SERVING_BUCKETS = (
    0.001, 0.0025, 0.005, 0.0075, 0.01, 0.015, 0.02, 0.03, 0.04, 0.05,
    0.065, 0.08, 0.1, 0.13, 0.17, 0.25, 0.4, 0.65, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0)


class Predictor:
    """Base predictor: load() once, predict(instances) per request."""

    name: str = "model"
    ready: bool = False

    def load(self) -> None:
        raise NotImplementedError

    def predict(self, instances: np.ndarray,
                probabilities: bool = False) -> Dict[str, Any]:
        raise NotImplementedError


def load_export_meta(model_dir: str, filename: str = "config.json"):
    """(input_shape, num_classes) from an export's metadata sidecar —
    the shared shape every framework predictor records at export time."""
    path = os.path.join(model_dir, filename)
    if not os.path.exists(path):
        return None, None
    with open(path) as f:
        meta = json.load(f)
    shape = tuple(meta["input_shape"]) if meta.get("input_shape") else None
    ncls = int(meta["num_classes"]) if meta.get("num_classes") else None
    return shape, ncls


class JaxPredictor(Predictor):
    """Serves a `serving.export` directory with bucketed, pre-warmed
    jits on the device this process holds (``jax.devices()[0]``);
    ``device="cpu"`` is the explicit choice of the host instead.
    ``placement`` reports, per bucket, the platform it was compiled
    for."""

    def __init__(self, model_dir: str, name: str = "",
                 max_batch_size: int = 64, device: str = "default"):
        self.model_dir = model_dir
        self.name = name or "model"
        self.max_batch_size = max_batch_size
        self.device = device
        self._compiled: Dict[int, Any] = {}
        self._buckets: List[int] = []
        self.placement: Dict[int, str] = {}

    def load(self) -> None:
        import jax
        import jax.numpy as jnp

        from ..models import get_model
        from .export import load_exported

        config, payload = load_exported(self.model_dir)
        model = get_model(config["model"],
                          num_classes=config["num_classes"])
        params = payload["params"]
        batch_stats = payload.get("batch_stats") or {}
        self.input_shape = tuple(config["input_shape"])
        self.num_classes = config["num_classes"]

        def fn(p, bs, x):
            # Params/batch_stats are jit ARGUMENTS, not closures: a
            # closed-over tree is embedded in the lowered program as
            # constants, bloating every bucket's compile payload by the
            # full model size.
            variables = {"params": p}
            if bs:
                variables["batch_stats"] = bs
            logits = model.apply(variables, x, train=False)
            probs = jax.nn.softmax(logits, -1)
            return logits.argmax(-1), probs

        # AOT-compile every bucket (jit().lower().compile()): no request
        # ever pays a compile AND dispatch skips the jit signature-matching
        # cache lookup. A non-power-of-two max_batch_size is its own bucket
        # so oversized requests chunked by it still hit a compiled shape.
        self._buckets = []
        b = 1
        while b <= self.max_batch_size:
            self._buckets.append(b)
            b *= 2
        if self._buckets[-1] != self.max_batch_size:
            self._buckets.append(self.max_batch_size)

        dev = jax.devices("cpu")[0] if self.device == "cpu" \
            else jax.devices()[0]
        sharding = jax.sharding.SingleDeviceSharding(dev)
        p_dev = jax.device_put(params, dev)
        bs_dev = jax.device_put(batch_stats, dev) if batch_stats else {}
        self.placement = {b: dev.platform for b in self._buckets}
        self._compiled = {}
        for b in self._buckets:
            spec = jax.ShapeDtypeStruct((b,) + self.input_shape,
                                        jnp.float32, sharding=sharding)
            compiled = jax.jit(fn).lower(p_dev, bs_dev, spec).compile()
            # Bind the device-resident trees so callers keep the fn(x)
            # shape; args pass by reference, no per-call transfer.
            self._compiled[b] = functools.partial(compiled, p_dev, bs_dev)
            cls, _ = self._compiled[b](
                np.zeros((b,) + self.input_shape, np.float32))
            jax.device_get(cls)  # pre-warm the full request path
        self.ready = True

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def predict(self, instances: np.ndarray,
                probabilities: bool = False) -> Dict[str, Any]:
        import jax

        preds: List[Any] = []
        probs_out: List[Any] = []
        # Oversized requests run as several max-bucket dispatches; the
        # tail pads up to its bucket (always static shapes).
        for start in range(0, instances.shape[0], self.max_batch_size):
            chunk = instances[start:start + self.max_batch_size]
            n = chunk.shape[0]
            b = self._bucket(n)
            if n < b:
                pad = np.zeros((b - n,) + chunk.shape[1:], chunk.dtype)
                chunk = np.concatenate([chunk, pad], 0)
            cls, probs = self._compiled[b](chunk)
            # Only transfer what the response needs: probabilities are
            # opt-in (V1 protocol requires just "predictions", and the
            # device->host copy of a [B, classes] float tensor dominated
            # the old response path).
            if probabilities:
                cls, probs = jax.device_get((cls, probs))
                probs_out.extend(p.tolist() for p in probs[:n])
            else:
                cls = jax.device_get(cls)
            preds.extend(cls[:n].tolist())
        out: Dict[str, Any] = {"predictions": preds}
        if probabilities:
            out["probabilities"] = probs_out
        return out


class MicroBatcher:
    """Aggregates concurrent predict calls into one device dispatch.

    KFServing batcher contract: flush when maxBatchSize items are waiting
    or the oldest has waited maxLatencyMs.

    ``workers`` > 1 runs that many batcher threads so a second batch
    dispatches while the first is still in flight: the dispatch
    round-trip is dead time the next batch can pipeline into. Each JAX
    dispatch is thread-safe (the GIL releases during the blocking
    device fetch); per-request ordering is preserved by the per-request
    reply queues."""

    def __init__(self, predictor: Predictor, max_batch_size: int = 32,
                 max_latency_ms: float = 2.0, reply_timeout_s: float = 60.0,
                 workers: int = 1):
        self.predictor = predictor
        self.max_batch_size = max_batch_size
        self.max_latency_s = max_latency_ms / 1000.0
        self.reply_timeout_s = reply_timeout_s
        # Queue entries carry the submitting request's (trace, span)
        # context: the batcher executes on ITS worker thread, where
        # current_trace_id() would otherwise be empty — predictions (and
        # chaos draws, and the flush span) must still correlate to the
        # requests that triggered them.
        self._q: "queue.Queue[Tuple[np.ndarray, bool, queue.Queue, str, str]]" = \
            queue.Queue()
        self._stop = threading.Event()
        # Orders enqueue against close(): once close() sets _stop under
        # this gate, no new request can slip past the drain below.
        self._gate = threading.Lock()
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"kfx-batcher-{i}")
            for i in range(max(1, workers))]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            batch = [first]
            count = first[0].shape[0]
            deadline = time.monotonic() + self.max_latency_s
            while count < self.max_batch_size:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                batch.append(item)
                count += item[0].shape[0]
            # The whole per-batch body is inside the try: a bad request
            # (e.g. mismatched instance shapes failing the concatenate)
            # must reply an error to every caller in the batch, never kill
            # the batcher thread. The flush runs under a batcher.flush
            # span restored from the OLDEST request's captured context
            # (the one whose latency deadline forced the flush), so the
            # device dispatch lands in that request's trace tree and
            # current_trace_id() is correct inside predict.
            try:
                want_probs = any(b[1] for b in batch)
                stacked = np.concatenate([b[0] for b in batch], 0)
                with obs_trace.span("batcher.flush", trace_id=first[3],
                                    parent_id=first[4],
                                    requests=str(len(batch)),
                                    instances=str(stacked.shape[0])):
                    result = self.predictor.predict(
                        stacked, probabilities=want_probs)
                preds = result["predictions"]
                probs = result.get("probabilities")
                off = 0
                for arr, wp, reply, _, _ in batch:
                    n = arr.shape[0]
                    out = {"predictions": preds[off:off + n]}
                    if wp and probs is not None:
                        out["probabilities"] = probs[off:off + n]
                    reply.put(out)
                    off += n
            except Exception as e:  # propagate per-request
                for _, _, reply, _, _ in batch:
                    reply.put(e)

    def predict(self, instances: np.ndarray,
                probabilities: bool = False) -> Dict[str, Any]:
        # Shape mismatches fail fast here instead of poisoning a batch.
        want = getattr(self.predictor, "input_shape", None)
        if want is not None and tuple(instances.shape[1:]) != tuple(want):
            raise ValueError(
                f"instance shape {tuple(instances.shape[1:])} does not "
                f"match model input {tuple(want)}")
        reply: "queue.Queue" = queue.Queue()
        with self._gate:
            if self._stop.is_set():
                # A racing predict after close() must fail fast, not sit
                # on the queue until reply_timeout_s with no worker left.
                raise RuntimeError("batcher is closed")
            # Capture the caller's trace context here, on the request
            # thread — the worker thread restores it around execution.
            self._q.put((instances, probabilities, reply,
                         obs_trace.current_trace_id(),
                         obs_trace.current_span_id()))
        try:
            out = reply.get(timeout=self.reply_timeout_s)
        except queue.Empty:
            raise TimeoutError(
                f"batcher did not reply within {self.reply_timeout_s}s")
        if isinstance(out, Exception):
            raise out
        return out

    def close(self) -> None:
        """Stop workers AND resolve every request they leave behind:
        join the threads (none is mid-batch afterwards), then drain the
        queue with error replies — a request that raced the shutdown
        gets an immediate error instead of stalling its handler thread
        until reply_timeout_s."""
        with self._gate:
            self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)
        while True:
            try:
                reply = self._q.get_nowait()[2]
            except queue.Empty:
                break
            reply.put(RuntimeError("batcher closed while request queued"))


TIMING_HEADER = "X-Kfx-Timing"


def _timing_header(result: Dict[str, Any]) -> Optional[Dict[str, str]]:
    """Fold the first request's latency breakdown into the
    ``X-Kfx-Timing`` response header (``k=v;...``), so a client — or a
    curl on the incident bridge — reads where the time went without
    parsing the body. None when the engine/recorder is off."""
    timing = result.get("timing") if isinstance(result, dict) else None
    if not timing:
        return None
    first = timing[0]
    parts = []
    for key in ("queue_wait_s", "prefill_s", "decode_s", "stalled_s",
                "spec_accept"):
        v = first.get(key)
        if v is not None:
            parts.append(f"{key}={v:g}")
    return {TIMING_HEADER: ";".join(parts)} if parts else None


class ModelServer:
    """HTTP server hosting one or more predictors (V1 protocol)."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1"):
        self.predictors: Dict[str, Predictor] = {}
        self.batchers: Dict[str, MicroBatcher] = {}
        # Drain mode (operator shutdown preamble): readiness goes
        # false, new predict/generate requests shed with 503 +
        # Retry-After, in-flight work finishes. One-way.
        self.draining = False
        # Last flight-snapshot-file write (monotonic) — the /healthz
        # piggyback throttle (_maybe_snapshot_flight).
        self._flight_snap_ts = 0.0
        # Server-reported latency distribution (so serving_p50_ms is a
        # /metrics fact, not only a client's observation) + request/error
        # counters, all rendered by the registry on /metrics.
        self.metrics = MetricsRegistry()
        self.latency = self.metrics.histogram(
            "kfx_serving_request_seconds",
            "End-to-end predict/generate handling time by model and verb.",
            buckets=SERVING_BUCKETS)
        self.requests_total = self.metrics.counter(
            "kfx_serving_requests_total",
            "Predict requests served since startup.")
        self.errors_total = self.metrics.counter(
            "kfx_serving_errors_total",
            "Requests answered with a non-2xx status.")
        self.metrics.add_collector(self._collect_model_gauges)
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # Latency path: never let Nagle hold a partial segment waiting
            # on a delayed ACK (worth ~40ms per request on loopback).
            disable_nagle_algorithm = True

            def log_message(self, *a):  # quiet
                pass

            def _send(self, code: int, payload: Dict[str, Any],
                      extra_headers: Optional[Dict[str, str]] = None
                      ) -> None:
                self._send_text(code, json.dumps(payload),
                                "application/json",
                                extra_headers=extra_headers)

            def _send_text(self, code: int, text, ctype: str,
                           extra_headers: Optional[Dict[str, str]] = None
                           ) -> None:
                body = text if isinstance(text, bytes) else text.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                trace = self.headers.get(TRACE_HEADER, "")
                if trace:
                    # Echo the caller's correlation ID (obs.trace flow).
                    self.send_header(TRACE_HEADER, trace)
                span_id = getattr(self, "_span_id", "")
                if span_id:
                    # This request's span, so callers can parent to it.
                    self.send_header(SPAN_HEADER, span_id)
                self.end_headers()
                self.wfile.write(body)
                self._last_code = code

            def do_GET(self):
                server._handle_get(self)

            def do_POST(self):
                server._handle_post(self)

        class Server(ThreadingHTTPServer):
            # Default listen backlog is 5: a burst of concurrent clients
            # (32 connections at once) overflows it and the kernel
            # resets the excess SYNs. Size it for bursty fleets.
            request_queue_size = 128

        self.httpd = Server((host, port), Handler)
        self.port = self.httpd.server_port
        self._thread: Optional[threading.Thread] = None

    # -- observability ------------------------------------------------------
    @property
    def request_count(self) -> int:
        """Total routed predict/generate requests — a view over the
        registry counter, so the JSON and exposition formats can never
        disagree on the request total."""
        return int(sum(v for _, v in self.requests_total.samples()))

    def _collect_model_gauges(self, reg: MetricsRegistry) -> None:
        reg.gauge("kfx_serving_models",
                  "Registered models.").set(len(self.predictors))
        reg.gauge("kfx_serving_models_ready",
                  "Models ready to serve.").set(
                      sum(1 for p in self.predictors.values() if p.ready))
        # Chaos injections in THIS process (kfx_chaos_injected_total):
        # a chaos serving run exposes its fault counts on the same
        # /metrics a scraper already reads. Ditto span-log writes
        # (kfx_spans_recorded_total) — proof request tracing is flowing.
        chaos.collect(reg)
        obs_trace.collect(reg)
        self._collect_device_memory(reg)

    def _collect_device_memory(self, reg: MetricsRegistry) -> None:
        """Pull-time ``kfx_device_memory_bytes{kind=in_use|peak|limit}``
        from the first local device's ``memory_stats()``: what the
        replica holds on its chip (weights, KV pools, XLA's
        temporaries), read when /metrics is scraped. Absent until a
        model is ready (the scrape must not be what starts the
        backend) and where the backend reports no statistics (the
        CPU)."""
        jax = sys.modules.get("jax")
        if jax is None or not any(p.ready
                                  for p in self.predictors.values()):
            return
        try:
            stats = jax.local_devices()[0].memory_stats()
        except RuntimeError:    # a backend without the query
            return
        if not stats:
            return
        gauge = reg.gauge(
            "kfx_device_memory_bytes",
            "Device memory of the replica's first local device, from "
            "the backend's memory_stats().")
        for kind, key in (("in_use", "bytes_in_use"),
                          ("peak", "peak_bytes_in_use"),
                          ("limit", "bytes_limit")):
            if key in stats:
                gauge.set(int(stats[key]), kind=kind)

    def _latency_summary(self) -> Dict[str, Dict[str, Optional[float]]]:
        """Server-reported per-model p50/p99 (ms) from the request
        histogram — the number a client-observed p50 should agree
        with (±bucket resolution)."""
        out: Dict[str, Dict[str, Optional[float]]] = {}
        for name in self.predictors:
            if not self.latency.count(model=name):
                continue
            p50 = self.latency.percentile(0.5, {"model": name})
            p99 = self.latency.percentile(0.99, {"model": name})
            out[name] = {
                "p50": round(p50 * 1000, 3) if p50 is not None else None,
                "p99": round(p99 * 1000, 3) if p99 is not None else None,
            }
        return out

    def _engine_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-model decode-engine load from this registry's gauges —
        what the InferenceService autoscaler polls as its queue-depth
        signal (engine requests waiting for a slot are unmet
        concurrency the router's in-flight count cannot see). Empty for
        classifier servers: the operator stops polling on first sight
        of an empty block."""
        out: Dict[str, Dict[str, Any]] = {}
        for family, field in (("kfx_lm_queue_depth", "queue_depth"),
                              ("kfx_lm_slot_occupancy", "slot_occupancy"),
                              ("kfx_lm_slots", "slots"),
                              ("kfx_lm_kv_pages", "kv_pages"),
                              ("kfx_lm_kv_pages_free", "kv_pages_free"),
                              ("kfx_lm_kv_bytes_per_token",
                               "kv_bytes_per_token"),
                              ("kfx_lm_prefix_tokens_reused",
                               "prefix_tokens_reused"),
                              ("kfx_lm_prompt_tokens_admitted",
                               "prompt_tokens_admitted"),
                              ("kfx_lm_adapter_slots",
                               "adapter_slots"),
                              ("kfx_lm_adapter_slots_free",
                               "adapter_slots_free"),
                              ("kfx_lm_weight_slots",
                               "weight_slots"),
                              ("kfx_lm_weight_slots_free",
                               "weight_slots_free"),
                              ("kfx_lm_weight_models_loaded",
                               "weight_models_loaded"),
                              ("kfx_lm_spec_accept_rate",
                               "spec_accept_rate")):
            for labels, value in self.metrics.gauge(family).samples():
                model = labels.get("model", "")
                out.setdefault(model, {})[field] = value
        # Quantization info gauge: the mode rides the labels; the JSON
        # block renders it as the `kfx top` Q-column string ("w8",
        # "kv8", "w8+kv8", "d8", or "f32") via the one shared mapping.
        for labels, _ in self.metrics.gauge(
                "kfx_lm_quant_mode").samples():
            model = labels.get("model", "")
            out.setdefault(model, {})["quant"] = quant_mode_string(
                labels.get("weights", "f32"), labels.get("kv", "f32"))
        # Per-model weight-pool residency: the pooled label rides the
        # gauge; the JSON block flattens it into a {name: loaded?}
        # map the operator folds into status.pooledModels ("pooled
        # but unloaded" shows as False, never as absence).
        for labels, value in self.metrics.gauge(
                "kfx_lm_weight_model_loaded").samples():
            model = labels.get("model", "")
            pooled = labels.get("pooled", "")
            if pooled:
                out.setdefault(model, {}).setdefault(
                    "pooled", {})[pooled] = bool(value)
        # Per-QoS-class in-flight split (request plane): the qos label
        # rides the gauge; the JSON block flattens it into the
        # active_interactive / active_batch fields `kfx top` renders
        # as its I/B column.
        for labels, value in self.metrics.gauge(
                "kfx_lm_class_active").samples():
            model = labels.get("model", "")
            qos = labels.get("qos", "")
            if qos in ("interactive", "batch"):
                out.setdefault(model, {})[f"active_{qos}"] = value
        return out

    def _finish_request(self, h, name: str, verb: str, t0: float) -> None:
        """Record latency/outcome for one routed request and emit the
        structured request log line (trace ID echoed from the caller)."""
        dt = time.perf_counter() - t0
        # _last_code was reset at routing time, so 0 here means the
        # handler died before sending anything (connection reset,
        # write failure) — an error, not a success.
        code = getattr(h, "_last_code", 0)
        # The model label comes from the URL; only registered names may
        # become label values, or a scanner cycling arbitrary model
        # names would grow the counter's label space without bound.
        model = name if name in self.predictors else "unknown"
        self.requests_total.inc(1, model=model, verb=verb)
        if 200 <= code < 400:
            # Only successful requests shape the latency distribution —
            # sub-ms 4xx rejections (and aborted connections) would
            # distort the p50 clients actually experience.
            self.latency.observe(dt, model=model, verb=verb)
        else:
            self.errors_total.inc(1, model=model, verb=verb)
        request_log.info(
            "request model=%s verb=%s status=%s ms=%.2f trace=%s",
            name, verb, code, dt * 1000, h.headers.get(TRACE_HEADER, ""))

    # -- registration -------------------------------------------------------
    def register(self, predictor: Predictor,
                 batcher: Optional[Dict[str, Any]] = None) -> None:
        self.predictors[predictor.name] = predictor
        # Predictors with their own instruments (LM tokens/sec) record
        # into the server's registry so one /metrics shows everything.
        predictor.metrics = self.metrics
        hook = getattr(predictor, "on_metrics_attached", None)
        if hook is not None:
            # Re-seed gauges set before the swap (engine slot counts,
            # warm-bucket progress) so a scrape before the first
            # request already sees them on THIS registry.
            hook()
        if batcher:
            self.batchers[predictor.name] = MicroBatcher(
                predictor,
                max_batch_size=int(batcher.get("maxBatchSize", 32)),
                max_latency_ms=float(batcher.get("maxLatencyMs", 2.0)),
                reply_timeout_s=float(batcher.get("replyTimeoutS", 60.0)),
                workers=int(batcher.get("workers", 1)))

    # -- request handling ---------------------------------------------------
    def _liveness(self) -> Dict[str, Any]:
        """Aggregate decode-loop heartbeats across predictors: the
        /healthz verdict. ``wedged`` when any engine reports stale
        progress while busy — the server keeps answering HTTP just
        fine with a stuck loop, which is exactly why readiness alone
        cannot catch it."""
        wedged: Dict[str, Any] = {}
        for name, p in self.predictors.items():
            hb_fn = getattr(p, "engine_heartbeat", None)
            hb = hb_fn() if hb_fn is not None else None
            if hb and hb.get("wedged"):
                wedged[name] = {"iterations": hb["iterations"],
                                "stalled_s": hb["stalled_s"]}
        if wedged:
            return {"status": "wedged", "models": wedged}
        return {"status": "draining" if self.draining else "alive"}

    def drain(self, wait_s: float = 0.0) -> Dict[str, Any]:
        """Enter drain mode and wait up to ``wait_s`` for in-flight
        work to finish: flips readiness false and sheds new requests
        (503 + Retry-After), then drains every predictor that holds
        in-flight state (the LM decode engine fails its queue with a
        retriable error and finishes its slots). Returns the verdict
        the /drain endpoint reports."""
        self.draining = True
        deadline = time.monotonic() + max(float(wait_s), 0.0)
        drained = True
        for p in self.predictors.values():
            fn = getattr(p, "drain", None)
            if fn is None:
                continue  # no in-flight state beyond the HTTP handler
            drained = fn(max(deadline - time.monotonic(), 0.0)) and drained
        return {"draining": True, "drained": drained}

    def _handle_get(self, h) -> None:
        path = h.path
        if path == "/healthz" or path == "/":
            live = self._liveness()
            # Piggyback the flight-snapshot file on the liveness probe:
            # the operator polls /healthz every reconcile, so the
            # on-disk snapshot stays fresh enough to serve as the
            # postmortem source when a crash leaves no process to ask.
            self._maybe_snapshot_flight()
            h._send(503 if live["status"] == "wedged" else 200, live)
        elif path == "/debug/flight":
            snaps = {name: p.flight_snapshot()
                     for name, p in self.predictors.items()
                     if getattr(p, "flight_snapshot", None) is not None}
            snaps = {k: v for k, v in snaps.items() if v is not None}
            if not snaps:
                h._send(404, {"error": "no flight recorder (engine off "
                                       "or KFX_FLIGHT=0)"})
            else:
                h._send(200, {"models": snaps})
        elif path == "/debug/requests":
            snaps = {name: p.flight_requests()
                     for name, p in self.predictors.items()
                     if getattr(p, "flight_requests", None) is not None}
            snaps = {k: v for k, v in snaps.items() if v is not None}
            if not snaps:
                h._send(404, {"error": "no flight recorder (engine off "
                                       "or KFX_FLIGHT=0)"})
            else:
                h._send(200, {"models": snaps})
        elif path.startswith(("/debug/state?", "/debug/kv?")):
            # As an .npz: what a slot holds in the leaves indexed by
            # slot (a configuration with state-space layers),
            # /debug/state?model=<name>&slot=<i>; the keys and values
            # the live row in a slot holds in one layer's pages,
            # /debug/kv?model=<name>&slot=<i>&layer=<l>.
            from urllib.parse import parse_qs, urlsplit

            q = parse_qs(urlsplit(path).query)
            p = self.predictors.get((q.get("model") or [""])[0])
            what, keys = ("slot_state", ("slot",)) \
                if path.startswith("/debug/state?") \
                else ("row_kv", ("slot", "layer"))
            fn = getattr(p, what, None)
            try:
                if fn is None:
                    raise ValueError("no such model, or it has none to "
                                     "read")
                h._send_text(200, fn(*(int((q.get(k) or ["-1"])[0])
                                       for k in keys)),
                             "application/octet-stream")
            except ValueError as e:
                h._send(404, {"error": str(e)})
        elif path == "/metrics" or path.startswith("/metrics?"):
            # Prometheus exposition by default (the reference model
            # servers are Prometheus-scrapable); JSON via ?format=json.
            # Both formats render the same registry state.
            from urllib.parse import parse_qs, urlsplit

            q = parse_qs(urlsplit(path).query)
            if (q.get("format") or [""])[0] == "json":
                h._send(200, {"request_count": self.request_count,
                              "models": sorted(self.predictors),
                              "latency_ms": self._latency_summary(),
                              "engine": self._engine_summary()})
            else:
                from ..utils.prom import PROM_CTYPE

                h._send_text(200, self.metrics.render(), PROM_CTYPE)
        elif path == "/v1/models":
            h._send(200, {"models": sorted(self.predictors)})
        elif path.startswith("/v1/models/"):
            name = path[len("/v1/models/"):]
            p = self.predictors.get(name)
            if p is None:
                # A pooled model name resolves to the predictor that
                # hosts its weight pool: "pooled but unloaded" is
                # ready-after-one-swap, not 404 — the activator routes
                # the cold request here and the swap happens on
                # admission, no process spawn.
                for host in self.predictors.values():
                    pooled = getattr(host, "pooled_models",
                                     lambda: {})()
                    if name in pooled:
                        h._send(200, {
                            "name": name,
                            "ready": host.ready and not self.draining,
                            "pooled": True,
                            "loaded": bool(pooled[name]),
                            "host": host.name})
                        return
                h._send(404, {"error": f"model {name!r} not found"})
            else:
                # A draining server is deliberately not ready: the
                # operator's readiness probe (and the router behind it)
                # must route around a replica that is about to die.
                body = {"name": name,
                        "ready": p.ready and not self.draining}
                pooled = getattr(p, "pooled_models", lambda: {})()
                if pooled:
                    body["pooledModels"] = pooled
                h._send(200, body)
        else:
            h._send(404, {"error": f"no route {path}"})

    def _handle_post(self, h) -> None:
        path = h.path
        t0 = time.perf_counter()
        # Reset per request: the handler object persists across a
        # keep-alive connection, and a stale 200 from the previous
        # request must not mark an aborted one as served.
        h._last_code = 0
        if path == "/drain" or path.startswith("/drain?"):
            # Operator drain-before-kill hook: ?wait_s bounds how long
            # the call blocks for in-flight work (the operator's drain
            # window). Draining twice is harmless — the second call
            # just re-reports the (possibly now empty) state.
            from urllib.parse import parse_qs, urlsplit

            q = parse_qs(urlsplit(path).query)
            try:
                wait_s = float((q.get("wait_s") or ["0"])[0])
            except ValueError:
                h._send(400, {"error": "wait_s must be a number"})
                return
            h._send(200, self.drain(wait_s))
            return
        if path.startswith("/v1/models/") and path.endswith(":generate"):
            name = path[len("/v1/models/"):-len(":generate")]
            sp = self._request_span(h, "serving.generate", name)
            try:
                return self._handle_generate(h, name)
            finally:
                self._finish_request(h, name, "generate", t0)
                self._finish_span(h, sp)
        route = path.split("?", 1)[0]
        if route.startswith("/v1/models/") and route.endswith(":kvimport"):
            name = route[len("/v1/models/"):-len(":kvimport")]
            sp = self._request_span(h, "serving.kvimport", name)
            try:
                return self._handle_kvimport(h, name)
            finally:
                self._finish_request(h, name, "kvimport", t0)
                self._finish_span(h, sp)
        if route.startswith("/v1/models/") and route.endswith(":migrate"):
            name = route[len("/v1/models/"):-len(":migrate")]
            sp = self._request_span(h, "serving.migrate", name)
            try:
                return self._handle_migrate(h, name)
            finally:
                self._finish_request(h, name, "migrate", t0)
                self._finish_span(h, sp)
        if route.startswith("/v1/models/") and route.endswith(":kvpeers"):
            name = route[len("/v1/models/"):-len(":kvpeers")]
            return self._handle_kvpeers(h, name)
        if route.startswith("/v1/models/") and route.endswith(":evict"):
            name = route[len("/v1/models/"):-len(":evict")]
            return self._handle_evict(h, name)
        if not (path.startswith("/v1/models/") and path.endswith(":predict")):
            h._send(404, {"error": f"no route {path}"})
            return
        name = path[len("/v1/models/"):-len(":predict")]
        sp = self._request_span(h, "serving.predict", name)
        try:
            self._handle_predict(h, name)
        finally:
            self._finish_request(h, name, "predict", t0)
            self._finish_span(h, sp)

    @staticmethod
    def _request_span(h, name: str, model: str):
        """Open the request's span, adopting the caller's trace/span
        headers (the router forwards its dispatch span) so this hop
        joins the caller's trace tree across the HTTP boundary."""
        sp = obs_trace.start_span(
            name, trace_id=h.headers.get(TRACE_HEADER, ""),
            parent_id=h.headers.get(SPAN_HEADER, ""), model=model)
        h._span_id = sp.span_id  # echoed back by _send_text
        # Handlers that learn request attributes AFTER the span opened
        # (the tenant key lives in the body) reach it here.
        h._cur_span = sp
        return sp

    @staticmethod
    def _finish_span(h, sp) -> None:
        code = getattr(h, "_last_code", 0)
        obs_trace.finish_span(
            sp, status="ok" if 200 <= code < 400 else "error")
        h._span_id = ""

    def _handle_predict(self, h, name: str) -> None:
        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not p.ready or self.draining:
            h._send(503, {"error": f"model {name!r} not ready"
                          if not p.ready else "server draining"},
                    extra_headers={"Retry-After": "1"}
                    if self.draining else None)
            return
        # Fault point: in-server predict failure/latency — the flapping
        # backend a router's passive health must eject around.
        inj = chaos.draw("serving.predict", target=name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                h._send(500, {"error": f"chaos[serving.predict]: {name}"})
                return
        try:
            length = int(h.headers.get("Content-Length", 0))
            body = json.loads(h.rfile.read(length) or b"{}")
            instances = np.asarray(body["instances"], np.float32)
            want_probs = bool(body.get("probabilities", False))
        except (ValueError, KeyError) as e:
            h._send(400, {"error": f"bad request: {e}"})
            return
        try:
            batcher = self.batchers.get(name)
            result = (batcher or p).predict(instances,
                                            probabilities=want_probs)
        except Exception as e:
            h._send(500, {"error": str(e)})
            return
        h._send(200, result)

    def _handle_generate(self, h, name: str) -> None:
        """LM text generation (serving/lm_server.py): token ids in,
        generated token ids out."""
        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not getattr(p, "generate", None):
            h._send(400, {"error": f"model {name!r} does not support "
                                   f":generate"})
            return
        if not p.ready or self.draining:
            # Draining sheds like overload: retriable, another replica
            # serves it (the engine's own EngineDraining covers the
            # queue; this covers requests that raced the drain flip).
            h._send(503, {"error": f"model {name!r} not ready"
                          if not p.ready else "server draining"},
                    extra_headers={"Retry-After": "1"}
                    if self.draining else None)
            return
        try:
            length = int(h.headers.get("Content-Length", 0))
            body = json.loads(h.rfile.read(length) or b"{}")
        except ValueError as e:
            h._send(400, {"error": f"bad request: {e}"})
            return
        # Deadline header alias: proxies and CLIs that can't touch the
        # body set X-KFX-Deadline-Ms instead; the body field wins.
        hdr_deadline = h.headers.get("X-KFX-Deadline-Ms")
        if hdr_deadline is not None and "deadline_ms" not in body:
            try:
                body["deadline_ms"] = float(hdr_deadline)
            except ValueError:
                h._send(400, {"error": "X-KFX-Deadline-Ms must be "
                                       "a number"})
                return
        # Tenant key onto the serving.generate span (`kfx trace
        # --tenant`): the engine's resolution — explicit tenant, else
        # the resolved adapter tenant ("" / absent -> revision
        # default, base when none).
        sp = getattr(h, "_cur_span", None)
        if sp is not None and isinstance(body, dict):
            tenant = body.get("tenant")
            if not isinstance(tenant, str) or not tenant:
                adapter = body.get("adapter")
                if adapter is None:
                    adapter = getattr(p, "adapter_default", "")
                tenant = str(adapter or "") or "base"
            sp.attrs["tenant"] = tenant
        try:
            if body.get("stream"):
                if not getattr(p, "generate_stream", None):
                    h._send(400, {"error": f"model {name!r} does not "
                                           f"support streaming"})
                    return
                events = p.generate_stream(body)
                self._send_sse(h, events)
                return
            result = p.generate(body)
        except ValueError as e:
            h._send(400, {"error": str(e)})
            return
        except EngineOverloaded as e:
            # Bounded-queueing overflow is load shedding, not a client
            # mistake and not a server fault: 503 + Retry-After, the
            # same contract the router uses while scaling from zero.
            # Deadline/rate sheds carry their own feasibility-derived
            # Retry-After so the router's jittered retry can wait out
            # the actual deficit instead of hammering the same wall.
            retry = getattr(e, "retry_after_s", None)
            extra = {"Retry-After": f"{retry:.1f}" if retry else "1"}
            # A migrated request's 503 carries the adopting peer so
            # the router's re-dispatch can go straight there (the
            # peer's resume table holds the in-flight generation).
            peer = getattr(e, "peer", "")
            if peer:
                extra["X-Kfx-Migrated"] = str(peer)
            h._send(503, {"error": str(e)}, extra_headers=extra)
            return
        except Exception as e:
            h._send(500, {"error": str(e)})
            return
        h._send(200, result, extra_headers=_timing_header(result))

    def _handle_kvimport(self, h, name: str) -> None:
        """Adopt a migrated request's KV pages (serving/kvtransfer.py
        wire format, raw in the body). Refusals are honest: a corrupt
        or geometry-mismatched stream is a 400 (the donor must not
        retry the same bytes here), a capacity refusal is a 503
        (retriable at another peer); either way the donor's copy
        stays authoritative."""
        from . import kvtransfer

        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not getattr(p, "kv_import", None):
            h._send(400, {"error": f"model {name!r} does not accept "
                                   "KV imports"})
            return
        if not p.ready or self.draining:
            h._send(503, {"error": f"model {name!r} not ready"
                          if not p.ready else "server draining"},
                    extra_headers={"Retry-After": "1"})
            return
        raw = h.rfile.read(int(h.headers.get("Content-Length", 0)))
        try:
            result = p.kv_import(raw)
        except kvtransfer.TransferCorrupt as e:
            h._send(400, {"error": str(e), "corrupt": True})
            return
        except (kvtransfer.TransferError, ValueError) as e:
            h._send(400, {"error": str(e)})
            return
        except EngineOverloaded as e:
            retry = getattr(e, "retry_after_s", None)
            h._send(503, {"error": str(e)},
                    extra_headers={"Retry-After":
                                   f"{retry:.1f}" if retry else "1"})
            return
        except Exception as e:
            h._send(500, {"error": str(e)})
            return
        h._send(200, result)

    def _handle_migrate(self, h, name: str) -> None:
        """Operator hook: push this model's in-flight requests to a
        peer (``?peer=URL&reason=drain``) before a kill. Answers 200
        with the {moved, failed, pages} stats — a failed transfer is
        a degrade (the seeded re-dispatch recovery still covers those
        requests), never an HTTP error."""
        from urllib.parse import parse_qs, urlsplit

        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not getattr(p, "migrate_to", None):
            h._send(400, {"error": f"model {name!r} does not support "
                                   "migration"})
            return
        q = parse_qs(urlsplit(h.path).query)
        peer = (q.get("peer") or [""])[0]
        reason = (q.get("reason") or ["manual"])[0]
        if not peer:
            h._send(400, {"error": "peer=URL is required"})
            return
        try:
            stats = p.migrate_to(peer, reason=reason)
        except ValueError as e:
            h._send(400, {"error": str(e)})
            return
        except Exception as e:
            h._send(500, {"error": str(e)})
            return
        h._send(200, stats)

    def _handle_evict(self, h, name: str) -> None:
        """Operator scale-to-zero push: drop an idle pooled model's
        weight slot (body: {"model": name}). Evicting is best-effort —
        a slot refcount-held by in-flight requests (or the pinned
        default) stays resident and the response says so, letting the
        operator retry on the next reconcile instead of racing the
        decode loop."""
        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not getattr(p, "pooled_models", lambda: {})():
            h._send(400, {"error": f"model {name!r} does not host a "
                                   "weight pool"})
            return
        try:
            n = int(h.headers.get("Content-Length", 0))
            body = json.loads(h.rfile.read(n).decode() or "{}")
            target = body.get("model", "")
        except (ValueError, UnicodeDecodeError) as e:
            h._send(400, {"error": str(e)})
            return
        if not isinstance(target, str) or not target:
            h._send(400, {"error": "body must carry a model name"})
            return
        evicted = p.evict_model(target)
        h._send(200, {"model": target, "evicted": bool(evicted)})

    def _handle_kvpeers(self, h, name: str) -> None:
        """Operator hook: replace this replica's decode-peer URL set
        (body: JSON list). Pushed every reconcile — peer ports change
        on respawn, so the set is live state, not spawn-time env."""
        p = self.predictors.get(name)
        if p is None:
            h._send(404, {"error": f"model {name!r} not found"})
            return
        if not getattr(p, "set_kv_peers", None):
            h._send(400, {"error": f"model {name!r} does not support "
                                   "KV peers"})
            return
        try:
            n = int(h.headers.get("Content-Length", 0))
            peers = json.loads(h.rfile.read(n).decode() or "[]")
            p.set_kv_peers(peers)
        except (ValueError, UnicodeDecodeError) as e:
            h._send(400, {"error": str(e)})
            return
        h._send(200, {"peers": len(p.kv_peers)})

    def _send_sse(self, h, events) -> None:
        """Stream SSE events over a chunked HTTP/1.1 response. The
        predictor already validated and submitted before handing us
        the iterator, so admission failures never reach this path —
        once headers go out, mid-stream failures ride the in-band
        ``event: error`` frame. A client hangup just ends the relay
        (the engine request completes on its own)."""
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-store")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()
        h._last_code = 200

        def chunk(data: bytes) -> bytes:
            return b"%x\r\n%s\r\n" % (len(data), data)

        try:
            for ev in events:
                h.wfile.write(chunk(ev))
                h.wfile.flush()
            h.wfile.write(b"0\r\n\r\n")
            h.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # Leave the connection unterminated (no final chunk): the
            # router sees a truncated stream, which is the trigger for
            # mid-stream recovery. shutdown(), not just close() — the
            # handler's rfile/wfile still hold the socket's io
            # refcount, so a bare close() would never send FIN.
            try:
                h.connection.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        h.close_connection = True

    # -- flight recorder ----------------------------------------------------
    def _maybe_snapshot_flight(self) -> None:
        """Persist the newest flight snapshot to
        ``$KFX_WORKDIR/flight/<KFX_COMPONENT>-<pid>.json`` (atomic
        replace), throttled to once per KFX_FLIGHT_SNAP_S (default 1s;
        "0" disables). The file is what the operator's crash-reap path
        bundles when the replica died without answering HTTP — the
        liveness probe hitting /healthz every reconcile keeps it
        fresh."""
        workdir = os.environ.get("KFX_WORKDIR", "")
        if not workdir:
            return
        try:
            period = float(os.environ.get("KFX_FLIGHT_SNAP_S", "1"))
        except ValueError:
            period = 1.0
        if period <= 0:
            return
        now = time.monotonic()
        if now - self._flight_snap_ts < period:
            return
        self._flight_snap_ts = now
        snaps = {}
        for name, p in self.predictors.items():
            fn = getattr(p, "flight_snapshot", None)
            snap = fn() if fn is not None else None
            if snap is not None:
                snaps[name] = snap
        if not snaps:
            return
        comp = os.environ.get("KFX_COMPONENT", "server")
        d = os.path.join(workdir, "flight")
        path = os.path.join(d, f"{comp}-{os.getpid()}.json")
        try:
            os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump({"models": snaps, "pid": os.getpid()}, f)
            os.replace(tmp, path)
        except OSError:
            pass  # snapshotting must never fail the probe

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ModelServer":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="kfx-modelserver")
        self._thread.start()
        return self

    def stop(self) -> None:
        for b in self.batchers.values():
            b.close()
        for p in self.predictors.values():
            # Predictors with their own machinery (the LM decode
            # engine's loop thread) resolve in-flight requests here.
            close = getattr(p, "close", None)
            if close is not None:
                close()
        self.httpd.shutdown()
        self.httpd.server_close()


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description="kfx model server")
    p.add_argument("--model-dir", required=True,
                   help="export directory (storageUri)")
    p.add_argument("--name", default="model")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--max-batch-size", type=int, default=None,
                   help="classifiers default 64 (request bucketing); LM "
                        "defaults 8 — with the decode engine this sizes "
                        "the slotted KV cache, which is real HBM "
                        "(n_slots x max_seq_len per layer)")
    p.add_argument("--device", default="default",
                   choices=["default", "cpu"],
                   help="default: the device this process holds; cpu: "
                        "the host, explicitly")
    p.add_argument("--batcher-max-latency-ms", type=float, default=0.0,
                   help=">0 enables the micro-batcher")
    p.add_argument("--batcher-reply-timeout-s", type=float, default=60.0)
    p.add_argument("--batcher-workers", type=int, default=1,
                   help=">1 pipelines device dispatches across batcher "
                        "threads")
    p.add_argument("--framework", default="auto",
                   choices=["auto", "jax", "pytorch", "tensorflow",
                            "sklearn", "lm"],
                   help="predict backend; auto sniffs the export format")
    args = p.parse_args(argv)
    from ..runners.jax_runner import enable_compile_cache

    enable_compile_cache()

    framework = args.framework
    if framework == "auto":
        from .lm_server import is_lm_export
        from .sklearn_server import is_sklearn_export
        from .tf_server import is_tf_export
        from .torch_server import is_torch_export

        if is_lm_export(args.model_dir):
            framework = "lm"
        elif is_torch_export(args.model_dir):
            framework = "pytorch"
        elif is_tf_export(args.model_dir):
            framework = "tensorflow"
        elif is_sklearn_export(args.model_dir):
            framework = "sklearn"
        else:
            framework = "jax"
    if args.max_batch_size is None:
        args.max_batch_size = 8 if framework == "lm" else 64
    if framework == "lm":
        from .lm_server import LMPredictor

        predictor = LMPredictor(args.model_dir, name=args.name,
                                max_batch_size=args.max_batch_size,
                                device=args.device)
    elif framework == "pytorch":
        from .torch_server import TorchPredictor

        predictor = TorchPredictor(args.model_dir, name=args.name,
                                   max_batch_size=args.max_batch_size)
    elif framework == "tensorflow":
        from .tf_server import TFPredictor

        predictor = TFPredictor(args.model_dir, name=args.name,
                                max_batch_size=args.max_batch_size)
    elif framework == "sklearn":
        from .sklearn_server import SKLearnPredictor

        predictor = SKLearnPredictor(args.model_dir, name=args.name,
                                     max_batch_size=args.max_batch_size)
    else:
        predictor = JaxPredictor(args.model_dir, name=args.name,
                                 max_batch_size=args.max_batch_size,
                                 device=args.device)
    t0 = time.time()
    predictor.load()
    server = ModelServer(port=args.port)
    batcher = None
    if args.batcher_max_latency_ms > 0:
        batcher = {"maxBatchSize": args.max_batch_size,
                   "maxLatencyMs": args.batcher_max_latency_ms,
                   "replyTimeoutS": args.batcher_reply_timeout_s,
                   "workers": args.batcher_workers}
    server.register(predictor, batcher)
    server.start()
    if framework in ("lm", "jax"):
        # What this process holds, in its own words (same line as the
        # training runners print).
        from ..runners.jax_runner import device_report

        print(f"device {json.dumps(device_report())}", flush=True)
    print(f"server_ready name={args.name} port={server.port} "
          f"framework={framework} "
          f"load_seconds={time.time() - t0:.1f} "
          f"placement={json.dumps(getattr(predictor, 'placement', {}))}",
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
