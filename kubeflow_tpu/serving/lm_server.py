"""LM serving: text-generation predictor behind the model server.

Export format (``export_lm``): ``lm_config.json`` (the TransformerConfig,
dtypes as strings) + ``params.msgpack``. The predictor serves a
``:generate`` verb:

    POST /v1/models/{m}:generate
    {"prompt_tokens": [[1,2,3], ...], "max_new_tokens": 32,
     "temperature": 0.7, "top_k": 40, "seed": 1, "stop_token": 2,
     "adapter": "tenant-a"}
    -> {"generated_tokens": [[...], ...]}

(``adapter`` selects a LoRA adapter configured by
``spec.<rev>.adapters`` — multi-tenant serving, docs/serving.md;
absent = the revision's default adapter, "" = the base model.)

Decoding is the continuous-batching DecodeEngine (serving/engine.py):
each prompt becomes its own slotted request, admitted mid-flight
between decode chunks, so concurrent traffic batches on-device and
short requests retire past long ones; speculative decoding rides on
top by default (a layer-truncated draft proposes, the target verifies
multi-token windows — ``KFX_LM_SPEC*`` knobs below, ``KFX_LM_SPEC=0``
to disable, docs/serving.md for sizing). The one-shot LMGenerator
(models/generate.py) is not a serving mode: it is the oracle the tests
hold the engine's greedy bytes to.

Tokenization is caller-side (the platform is tokenizer-agnostic, like
the reference's bring-your-own-model servers).
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue as _queue
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from flax import serialization

from ..obs.metrics import default_registry
from . import kvtransfer
from .server import Predictor

CONFIG_FILE = "lm_config.json"
PARAMS_FILE = "params.msgpack"

# The router holds each backend attempt open for 60s
# (router._attempt); result waits here stay under it so a starved
# request surfaces as a clean engine error, never a router 502.
_BACKEND_TIMEOUT_S = 60.0

def export_lm(directory: str, cfg, params, quantize: str = "") -> str:
    """Write a servable LM export from train-time config + params.

    ``quantize="int8"`` rewrites the attention/MLP/lm_head kernels to
    per-output-channel symmetric int8 + f32 scales
    (models/transformer.quantize_params_int8) and flips the exported
    config's ``quant`` knob, so the loaded model runs the dequant-fused
    matmul path directly on the int8 tensors — a ~4x smaller artifact
    for f32 params AND 4x less weight HBM at serving. The config
    carries ``format_version`` (missing = v1) and a ``quant`` block;
    the default f32 export is unchanged and auto-detected on load."""
    import jax

    from ..serving.export import FORMAT_VERSION

    if quantize not in ("", "int8"):
        raise ValueError(
            f"unknown quantize {quantize!r} (expected '' or 'int8')")
    os.makedirs(directory, exist_ok=True)
    if quantize == "int8" and cfg.quant != "int8":
        from ..models.transformer import quantize_params_int8

        params = quantize_params_int8(params)
        cfg = dataclasses.replace(cfg, quant="int8")
    d = dataclasses.asdict(cfg)
    d["dtype"] = jnp.dtype(cfg.dtype).name
    d["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    d["ssm_state_dtype"] = jnp.dtype(cfg.ssm_state_dtype).name
    meta: Dict[str, Any] = {"framework": "lm",
                            "format_version": FORMAT_VERSION,
                            "config": d}
    if cfg.quant == "int8":
        meta["quant"] = {"weights": "int8",
                         "scheme": "per_channel_symmetric"}
    with open(os.path.join(directory, CONFIG_FILE), "w") as f:
        json.dump(meta, f)
    with open(os.path.join(directory, PARAMS_FILE), "wb") as f:
        f.write(serialization.to_bytes(jax.device_get(params)))
    return directory


def load_lm(directory: str):
    """Load an LM export. Tolerant of every format generation: v1
    exports carry neither ``format_version`` nor the quant knobs (the
    TransformerConfig defaults reconstruct them as f32); a quantized
    v2 export's config round-trips ``quant="int8"`` so the rebuilt
    model expects exactly the int8+scale param structure on disk."""
    from ..models.transformer import TransformerConfig

    with open(os.path.join(directory, CONFIG_FILE)) as f:
        meta = json.load(f)
    d = dict(meta["config"])
    d["dtype"] = jnp.dtype(d.get("dtype", "bfloat16"))
    d["param_dtype"] = jnp.dtype(d.get("param_dtype", "float32"))
    cfg = TransformerConfig(**d)
    with open(os.path.join(directory, PARAMS_FILE), "rb") as f:
        params = serialization.msgpack_restore(f.read())
    return cfg, params


def is_lm_export(model_dir: str) -> bool:
    return os.path.exists(os.path.join(model_dir, CONFIG_FILE))


class _RateWindow:
    """Sliding-window token-rate tracker: ``kfx_lm_tokens_per_second``
    is tokens counted over the trailing window, not the last call's
    instantaneous ratio — so a burst decays honestly toward 0 instead
    of a stale headline sticking to /metrics forever."""

    def __init__(self, window_s: float = 30.0):
        self.window_s = window_s
        self._lock = threading.Lock()
        self._events: "deque[tuple]" = deque()  # (monotonic ts, tokens)

    def record(self, n_tokens: int) -> None:
        with self._lock:
            self._events.append((time.monotonic(), n_tokens))

    def rate(self) -> float:
        now = time.monotonic()
        with self._lock:
            while self._events and self._events[0][0] < now - self.window_s:
                self._events.popleft()
            if not self._events:
                return 0.0
            total = sum(n for _, n in self._events)
            span = now - self._events[0][0]
        # Normalize by the span actually covered (floored at 1s so a
        # single fresh burst doesn't explode, capped at the window).
        return total / min(max(span, 1.0), self.window_s)


class LMPredictor(Predictor):
    """Generate-only predictor (classification ``:predict`` does not
    apply; the server routes ``:generate`` here).

    ``load()`` builds the continuous-batching DecodeEngine with
    ``n_slots = max_batch_size``; more prompts than slots queue, up to
    ``engine.max_queue``. ``self._engine`` is None only before
    ``load()``: the guards on it below are the routes the hosting
    server answers for a registered predictor that has not loaded yet
    (/healthz, /debug/*, the model listing, /drain, migrate, close);
    ``:generate`` and KV import are refused upstream until ``ready``."""

    def __init__(self, model_dir: str, name: str = "",
                 max_batch_size: int = 8, device: str = "default",
                 warm_buckets: Optional[Sequence[int]] = None):
        self.model_dir = model_dir
        self.name = name or "model"
        self.max_batch_size = max_batch_size
        self.device = device
        self._engine = None
        self._rate = _RateWindow()
        self._warm_count = 0
        self._warm_thread: Optional[threading.Thread] = None
        self.vocab_size = 0
        self.chunk_tokens = int(
            os.environ.get("KFX_LM_ENGINE_CHUNK", "8"))
        # Paged-KV knobs: page size in tokens; pool size in pages
        # (0 = dense-equivalent HBM, n_slots x max_seq_len tokens —
        # shrink to cap KV HBM and let admission gate on pages);
        # prefix cache on unless disabled.
        self.kv_page_size = int(
            os.environ.get("KFX_LM_KV_PAGE_SIZE", "32"))
        self.kv_pages = int(os.environ.get("KFX_LM_KV_PAGES", "0"))
        # (None: the engine's default, on where the configuration
        # can take it.)
        self.prefix_cache = {None: None, "0": False}.get(
            os.environ.get("KFX_LM_PREFIX_CACHE"), True)
        # Chunked prefill (docs/serving.md): prompt tails longer than
        # this admit in page-multiple chunks, one chunk dispatch per
        # engine iteration, bounding the decode stall a long prompt
        # can inflict on active slots. Default 256: prompts at or
        # below it behave exactly as before (one dispatch), longer
        # ones stop head-of-line blocking decode. 0 disables
        # (monolithic prefill, the escape hatch).
        self.prefill_chunk = int(
            os.environ.get("KFX_LM_PREFILL_CHUNK", "256"))
        # Speculative decoding (docs/serving.md): on by default — the
        # engine falls back per slot when the draft can't help, and
        # greedy output is byte-identical either way. KFX_LM_SPEC=0 is
        # the escape hatch; layers 0 = auto (n_layers // 4, >= 1);
        # tokens = proposals per verify window; pages 0 = same count
        # as the target pool.
        self.spec = os.environ.get("KFX_LM_SPEC", "1") != "0"
        self.spec_layers = int(os.environ.get("KFX_LM_SPEC_LAYERS", "0"))
        self.spec_tokens = int(os.environ.get("KFX_LM_SPEC_TOKENS", "4"))
        self.spec_pages = int(os.environ.get("KFX_LM_SPEC_PAGES", "0"))
        # Quantization knobs (docs/serving.md). KFX_LM_QUANT: "" =
        # follow the export's quant block; "int8" = quantize an f32
        # export's weights at load (per-channel symmetric, no
        # re-export needed); "0" = the escape hatch — DEQUANTIZE an
        # int8 export at load and serve the full-precision path.
        # KFX_LM_KV_QUANT="int8" stores the engine's paged KV pools as
        # int8 (+ per-token scale planes).
        # KFX_LM_QUANT_DRAFT="int8" quantizes only the speculative
        # DRAFT's weights (accept rate is the only thing at risk).
        self.quant = os.environ.get("KFX_LM_QUANT", "")
        self.kv_quant = os.environ.get("KFX_LM_KV_QUANT", "")
        self.draft_quant = os.environ.get("KFX_LM_QUANT_DRAFT", "")
        # Multi-tenant LoRA adapters (docs/serving.md): KFX_LM_ADAPTERS
        # is a JSON object {name: artifact URI} (spec.<rev>.adapters.
        # artifacts via the operator); requests select one with the
        # body field "adapter". DEFAULT applies when the body names
        # none; SLOTS sizes the HBM stack pool; RANK 0 = auto (max
        # declared by the artifacts); FALLBACK is the load-failure
        # policy ("base" = degrade to base-only, "error" = 503 +
        # Retry-After).
        try:
            self.adapters = json.loads(
                os.environ.get("KFX_LM_ADAPTERS", "") or "{}")
        except ValueError as e:
            raise ValueError(
                f"KFX_LM_ADAPTERS is not valid JSON: {e}") from e
        self.adapter_default = os.environ.get(
            "KFX_LM_ADAPTER_DEFAULT", "")
        self.adapter_slots = int(
            os.environ.get("KFX_LM_ADAPTER_SLOTS", "8"))
        self.adapter_rank = int(
            os.environ.get("KFX_LM_ADAPTER_RANK", "0"))
        self.adapter_fallback = os.environ.get(
            "KFX_LM_ADAPTER_FALLBACK", "base")
        # Multi-model weight pool (docs/serving.md "Weights as a
        # fleet resource"): KFX_LM_MODELS is a JSON object
        # {name: LM export dir} of whole checkpoints time-sharing
        # this replica's chips (spec.<rev>.models.artifacts via the
        # operator); requests select one with the body field "model".
        # MODEL_DEFAULT names the resident model ``model_dir``
        # already points at (required with MODELS); WEIGHT_SLOTS
        # sizes the HBM slot pool (0 = one slot per model);
        # WEIGHT_IDLE_S > 0 evicts models idle that long — the
        # replica-side scale-to-zero (the default stays warm).
        try:
            self.models = json.loads(
                os.environ.get("KFX_LM_MODELS", "") or "{}")
        except ValueError as e:
            raise ValueError(
                f"KFX_LM_MODELS is not valid JSON: {e}") from e
        if not isinstance(self.models, dict) or any(
                not isinstance(k, str) or not isinstance(v, str)
                for k, v in self.models.items()):
            raise ValueError(
                "KFX_LM_MODELS must be a JSON object "
                "{name: LM export dir}")
        self.model_default = os.environ.get("KFX_LM_MODEL_DEFAULT", "")
        self.weight_slots = int(
            os.environ.get("KFX_LM_WEIGHT_SLOTS", "0"))
        self.model_idle_s = float(
            os.environ.get("KFX_LM_WEIGHT_IDLE_S", "0"))
        # Liveness: seconds of decode-loop stall (while busy) before
        # the engine's heartbeat reads wedged and /healthz fails the
        # probe. Size it well above one worst-case dispatch (a chunk on
        # a big model is seconds); tests shrink it via the env knob.
        self.stall_threshold_s = float(
            os.environ.get("KFX_LM_STALL_S", "10.0"))
        # Request-plane policy (docs/serving.md "Request plane"):
        # QoS class default (per-request "qos" overrides), default
        # deadline in ms (0 = none; per-request "deadline_ms" or the
        # X-KFX-Deadline-Ms header overrides), and per-tenant
        # token-weighted rate budgets {adapter: tokens/s} with a burst
        # window — spec.<rev>.{qosDefault,deadlineMs,rateLimits} via
        # the operator.
        self.qos_default = os.environ.get(
            "KFX_LM_QOS_DEFAULT", "interactive")
        self.deadline_default_ms = float(
            os.environ.get("KFX_LM_DEADLINE_MS", "0"))
        try:
            self.rate_limits = json.loads(
                os.environ.get("KFX_LM_RATE_LIMITS", "") or "{}")
        except ValueError as e:
            raise ValueError(
                f"KFX_LM_RATE_LIMITS is not valid JSON: {e}") from e
        self.rate_burst_s = float(
            os.environ.get("KFX_LM_RATE_BURST_S", "2.0"))
        # KV transfer plane (docs/serving.md "KV as a fleet
        # resource"): ROLE is this replica's disaggregation tier —
        # "prefill" ships every finished prompt's pages to a decode
        # peer, "decode" receives them, "mixed" (default) does both
        # phases locally. KV_PEERS is a JSON list of peer base URLs
        # (the operator points prefill replicas at their decode
        # tier); OFFLOAD_PAGES > 0 spills cold prefix-cache pages to
        # a host-RAM tier of that many pages instead of dropping them.
        self.role = os.environ.get("KFX_LM_ROLE", "mixed")
        try:
            self.kv_peers = json.loads(
                os.environ.get("KFX_LM_KV_PEERS", "") or "[]")
        except ValueError as e:
            raise ValueError(
                f"KFX_LM_KV_PEERS is not valid JSON: {e}") from e
        if not isinstance(self.kv_peers, list) or any(
                not isinstance(p, str) for p in self.kv_peers):
            raise ValueError(
                "KFX_LM_KV_PEERS must be a JSON list of URLs")
        self.kv_offload_pages = int(
            os.environ.get("KFX_LM_KV_OFFLOAD_PAGES", "0"))
        # Peer round-robin cursor for _kv_send: the operator re-pushes
        # the decode-tier URL set via :kvpeers every reconcile (ports
        # change on respawn), so sends snapshot the CURRENT list.
        self._kv_rr = 0
        self._kv_rr_lock = threading.Lock()
        # Adopted in-flight generations by resume key (kv_import):
        # the router's re-dispatched :generate body claims its entry
        # here and attaches instead of recomputing.
        self._resume: Dict[str, Dict[str, Any]] = {}
        self._resume_lock = threading.Lock()
        self.warm_buckets = list(warm_buckets) if warm_buckets else None
        # Replaced with the hosting ModelServer's registry at register()
        # time so decode throughput shows up on that server's /metrics.
        self.metrics = default_registry()

    def load(self) -> None:
        import jax

        cfg, params = load_lm(self.model_dir)
        if self.quant == "int8" and cfg.quant != "int8":
            # Load-time quantization of an f32 export: same per-channel
            # scheme as a quantized export, no re-export required.
            from ..models.transformer import quantize_params_int8

            params = quantize_params_int8(params)
            cfg = dataclasses.replace(cfg, quant="int8")
        elif self.quant == "0" and cfg.quant == "int8":
            # Escape hatch: expand an int8 export back to f32 kernels
            # and serve the full-precision path (quality triage).
            from ..models.transformer import dequantize_params_int8

            params = dequantize_params_int8(params)
            cfg = dataclasses.replace(cfg, quant="")
        if self.device == "cpu":
            params = jax.device_put(params, jax.devices("cpu")[0])
        self.vocab_size = cfg.vocab_size
        from .engine import DecodeEngine

        # Draft depth: explicit KFX_LM_SPEC_LAYERS, else a quarter
        # of the target (floored at 1), always strictly shallower
        # than the target — a 1-layer model has nothing to
        # truncate, so speculation silently stays off there.
        draft = 0
        if self.spec and cfg.n_layers > 1 and not self.models and (
                self.spec_layers or not cfg.layer_pattern):
            # A weight pool excludes speculation (the draft would
            # need its own per-model truncation), and so does a stack
            # of several runs (the draft truncates ONE): the default
            # auto-disables rather than fail construction; an
            # explicit KFX_LM_SPEC_LAYERS reaches the engine, which
            # refuses by name.
            draft = self.spec_layers or max(1, cfg.n_layers // 4)
            draft = min(draft, cfg.n_layers - 1)
        # registry as a thunk: register() swaps self.metrics for
        # the hosting server's registry AFTER load; the engine must
        # follow it, not pin whatever was current at construction.
        self._engine = DecodeEngine(
            cfg, params, n_slots=self.max_batch_size,
            chunk_tokens=self.chunk_tokens, name=self.name,
            registry=lambda: self.metrics,
            kv_page_size=self.kv_page_size,
            kv_pages=self.kv_pages or None,
            prefix_cache=self.prefix_cache,
            draft_layers=draft,
            propose_tokens=max(1, self.spec_tokens),
            draft_kv_pages=self.spec_pages or None,
            kv_quant="int8" if self.kv_quant == "int8" else "",
            draft_quant="int8" if self.draft_quant == "int8" else "",
            stall_threshold_s=self.stall_threshold_s,
            prefill_chunk_tokens=max(0, self.prefill_chunk),
            adapters=self.adapters or None,
            adapter_slots=self.adapter_slots,
            adapter_rank=self.adapter_rank,
            adapter_default=self.adapter_default,
            adapter_fallback=self.adapter_fallback,
            qos_default=self.qos_default,
            deadline_default_s=self.deadline_default_ms / 1000.0,
            rate_limits=self.rate_limits or None,
            rate_burst_s=self.rate_burst_s,
            role=self.role,
            # A prefill-tier replica always gets a sender, even
            # before the operator's first :kvpeers push: an empty
            # list raises TransferError and the handoff degrades
            # to decoding locally (zero lost), exactly the severed
            # -transfer path.
            kv_peer_send=(self._kv_send
                          if (self.kv_peers or self.role == "prefill")
                          else None),
            kv_offload_pages=max(0, self.kv_offload_pages),
            models=self.models or None,
            weight_slots=(max(0, self.weight_slots)
                          if self.models else 0),
            model_default=(self.model_default
                           if self.models else ""),
            model_idle_s=max(0.0, self.model_idle_s))
        self._attach_usage()
        buckets = self.warm_buckets or self._engine.prompt_buckets
        # First bucket + the decode chunk warm synchronously —
        # ready means "can serve one request without a compile".
        self._engine.warm(buckets[:1])
        self._set_warm(1)
        self.ready = True
        # The remaining buckets compile on a background thread: the
        # first real request on a warm bucket pays nothing, and
        # readiness of the full bucket set is observable via the
        # kfx_lm_warm_buckets gauge instead of a first-request stall.
        self._warm_thread = threading.Thread(
            target=self._warm_rest, args=(buckets[1:],), daemon=True,
            name=f"kfx-lm-warm-{self.name}")
        self._warm_thread.start()

    def _set_warm(self, n: int) -> None:
        self._warm_count = n
        self.metrics.gauge(
            "kfx_lm_warm_buckets",
            "Prompt buckets with compiled decode paths.").set(
                n, model=self.name)

    def on_metrics_attached(self) -> None:
        """ModelServer.register swapped ``self.metrics`` — re-seed the
        load-time gauges (slots, occupancy, warm progress) onto the new
        registry so a scrape before the first request sees them."""
        if self._warm_count:
            self._set_warm(self._warm_count)
        if self._engine is not None:
            self._engine._touch_gauges()
        self._attach_usage()

    def _attach_usage(self) -> None:
        """Project the engine's tenant ledger into the CURRENT
        registry (a collector — the ledger owns the truth), seeding
        the default tenant's zero row so a pre-traffic
        ``scrape_metrics --require`` already sees both families."""
        if self._engine is None or self._engine.usage is None:
            return
        ledger = self._engine.usage
        tenant = self.adapter_default or "base"
        ledger.seed(tenant, self.qos_default, tenant)
        self.metrics.add_collector(ledger.collect)

    def _warm_rest(self, buckets) -> None:
        done = 1
        for b in buckets:
            try:
                self._engine.warm([b])
            except Exception:
                continue  # a failed warm costs the first request, only
            done += 1
            self._set_warm(done)

    def engine_heartbeat(self) -> Optional[Dict[str, Any]]:
        """Decode-loop liveness snapshot (None before ``load()``: no
        loop to wedge yet) — what turns the hosting server's /healthz
        into a real liveness probe."""
        if self._engine is None:
            return None
        return self._engine.heartbeat()

    def flight_snapshot(self) -> Optional[Dict[str, Any]]:
        """The /debug/flight payload: the engine's flight ring plus
        the current heartbeat (None before ``load()`` or when the
        recorder is disabled). Reading is safe from any HTTP thread —
        the ring is a deque the loop appends to atomically, and a
        wedged loop has stopped appending entirely."""
        if self._engine is None or self._engine.flight is None:
            return None
        return self._engine.flight.snapshot(
            heartbeat=self._engine.heartbeat())

    def flight_requests(self) -> Optional[Dict[str, Any]]:
        """The /debug/requests payload: recently retired requests with
        their latency breakdowns (None when recording is off)."""
        if self._engine is None or self._engine.flight is None:
            return None
        return self._engine.flight.requests()

    def slot_state(self, slot: int) -> bytes:
        """The /debug/state payload: what ``slot`` holds in the leaves
        indexed by slot (``DecodeEngine.slot_state``), as an .npz of
        float32 arrays. ValueError where the configuration has no such leaves."""
        import io

        if self._engine is None:
            raise ValueError(f"model {self.name} is not loaded")
        buf = io.BytesIO()
        # (bfloat16 and the float8s are no .npy types: as float32)
        np.savez(buf, **{
            k: v.astype(np.float32)
            for k, v in self._engine.slot_state(slot).items()})
        return buf.getvalue()

    def row_kv(self, slot: int, layer: int) -> bytes:
        """The /debug/kv payload: the keys and values the live row in
        ``slot`` holds in ``layer``'s pages (``DecodeEngine.row_kv``),
        as an .npz. ValueError where there is no such row or layer."""
        import io

        if self._engine is None:
            raise ValueError(f"model {self.name} is not loaded")
        buf = io.BytesIO()
        np.savez(buf, **self._engine.row_kv(slot, layer))
        return buf.getvalue()

    def pooled_models(self) -> Dict[str, bool]:
        """{model name: resident-in-HBM?} over the weight pool's full
        source set (docs/serving.md "Weights as a fleet resource") —
        empty without a pool. A name mapped to False is "pooled but
        unloaded": servable after one measured weight swap, so
        readiness reports it available rather than missing."""
        if self._engine is None:
            return {}
        return self._engine.pooled_models()

    def weight_stats(self) -> Optional[Dict[str, Any]]:
        """Weight-pool occupancy counters for /v1/models status (None
        without a pool)."""
        if self._engine is None:
            return None
        return self._engine.weight_stats()

    def evict_model(self, name: str) -> bool:
        """Operator scale-to-zero push: drop ``name``'s weight slot if
        it is idle (refcount 0, not the pinned default). Returns True
        when the slot was freed; False when unknown, not resident, or
        held by in-flight requests."""
        return self._engine.evict_model(name)

    def drain(self, wait_s: float = 0.0) -> bool:
        """Stop admitting and wait up to ``wait_s`` for in-flight
        generations to finish (serving/engine.py drain contract).
        Returns True when nothing is left in flight; trivially drained
        before ``load()``."""
        if self._engine is None:
            return True
        return self._engine.drain(wait_s)

    # -- KV transfer plane (docs/serving.md "KV as a fleet resource") -----
    _RESUME_TTL_S = 120.0

    def kv_import(self, raw: bytes) -> Dict[str, Any]:
        """Adopt a migrated in-flight generation: hand the page
        stream to the engine (verify, allocate, scatter, resume) and
        index the live Request by its content-derived resume key, so
        the router's re-dispatched ``:generate`` body — the seeded
        recovery it would have sent anyway — claims the adopted
        generation here instead of recomputing from the prompt."""
        header = kvtransfer.peek(raw)
        key = str(header.get("resume", ""))
        q: "_queue.Queue[Optional[int]]" = _queue.Queue()
        req = self._engine.kv_import(raw, on_token=q.put)
        if key:
            with self._resume_lock:
                self._prune_resume_locked()
                # What travelled with the pages (``meter_skip``), not
                # ``len(req.tokens)``: the loop is already decoding, and
                # a token it has landed reaches ``q`` when its chunk is
                # handed out — counted here too it would stream twice.
                self._resume[key] = {"req": req, "q": q,
                                     "imported": req.meter_skip,
                                     "t": time.monotonic()}
        self.metrics.counter(
            "kfx_lm_kv_migrations_total",
            "In-flight requests migrated to a peer replica, by "
            "reason.").inc(1, model=self.name, reason="adopted")
        return {"resume": key, "tokens": len(req.tokens),
                "pages": len(header.get("blocks", []))}

    def migrate_to(self, peer: str,
                   reason: str = "manual") -> Dict[str, int]:
        """Push every in-flight generation to ``peer`` (the operator's
        migrate-before-kill hook; also the rebalancing verb). Failed
        transfers keep running here — the stats say how many moved."""
        if self._engine is None:
            return {"moved": 0, "failed": 0, "pages": 0}
        return self._engine.migrate_out(
            reason=reason,
            send=lambda payload: kvtransfer.post_pages(
                peer, self.name, payload))

    def _kv_send(self, payload: bytes) -> str:
        """The engine's ``kv_peer_send``: round-robin over the LIVE
        peer list (set_kv_peers replaces it between sends), falling
        through the rest on refusal and raising the last TransferError
        only when every peer refused — the donor then keeps the
        request local."""
        peers = [p for p in list(self.kv_peers) if p]
        if not peers:
            raise kvtransfer.TransferError(
                "no decode peers configured (operator has not pushed "
                ":kvpeers yet)")
        with self._kv_rr_lock:
            start = self._kv_rr
            self._kv_rr += 1
        last: Optional[kvtransfer.TransferError] = None
        for off in range(len(peers)):
            peer = peers[(start + off) % len(peers)]
            try:
                return kvtransfer.post_pages(peer, self.name, payload)
            except kvtransfer.TransferError as e:
                last = e
        assert last is not None
        raise last

    def set_kv_peers(self, peers: List[str]) -> None:
        """Replace the decode-peer URL set (the operator's per-
        reconcile push: decode-tier ports change on respawn, so the
        set is live state, not spawn-time env)."""
        if not isinstance(peers, list) or any(
                not isinstance(p, str) for p in peers):
            raise ValueError("peers must be a JSON list of URLs")
        self.kv_peers = [p for p in peers if p]

    def _prune_resume_locked(self) -> None:
        now = time.monotonic()
        for key in [k for k, e in self._resume.items()
                    if now - e["t"] > self._RESUME_TTL_S]:
            del self._resume[key]  # unclaimed adoption idles out

    def _claim_resume(self, key: str) -> Optional[Dict[str, Any]]:
        with self._resume_lock:
            self._prune_resume_locked()
            return self._resume.pop(key, None)

    def _resume_key_for(self, p: Dict[str, Any]) -> str:
        """The resume key this parsed single-prompt body would carry —
        derived with the same adapter-default resolution the engine
        applies, so donor and receiver agree without a side channel.
        The per-request model is deliberately NOT part of the key: a
        weight-pool replica refuses KV transfer in both directions
        (the pages would decode under different weights), so a pooled
        request never has a resumable migration to claim."""
        adapter = p["adapter"]
        if adapter is None:
            adapter = self._engine.adapter_default
        kw = p["kw"]
        return kvtransfer.resume_key(
            p["prompts"][0], kw["max_new_tokens"], kw["temperature"],
            kw["top_k"], kw["seed"],
            -1 if p["stop"] is None else int(p["stop"]),
            str(adapter or ""))

    def close(self) -> None:
        if self._engine is not None:
            self._engine.close()

    def predict(self, instances, probabilities: bool = False
                ) -> Dict[str, Any]:
        raise NotImplementedError(
            "LM models serve :generate, not :predict")

    def _parse_generate(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Shared request-plane validation for the buffered and
        streaming :generate paths. Every defect here is a client
        mistake (ValueError -> 400), never a 503."""
        prompts = body.get("prompt_tokens")
        if not prompts or not isinstance(prompts, list):
            raise ValueError("prompt_tokens (list of token-id lists) "
                             "is required")
        if isinstance(prompts[0], int):  # single prompt convenience
            prompts = [prompts]
        limit = self._engine.max_queue
        if len(prompts) > limit:
            raise ValueError(f"batch {len(prompts)} exceeds "
                             f"queue capacity {limit}")
        for p in prompts:
            arr = np.asarray(p)
            if arr.size == 0 or arr.min() < 0 or \
                    arr.max() >= self.vocab_size:
                raise ValueError(
                    f"prompt token ids must be in [0, {self.vocab_size})")
        stop = body.get("stop_token")
        if stop is not None:
            stop = int(stop)
        # Per-request adapter selection (multi-tenant LoRA): a string
        # adapter name from spec.<rev>.adapters.artifacts; absent =
        # the revision's default adapter; "" = explicitly the base
        # model. Unknown names are a client 400, not a 503.
        adapter = body.get("adapter")
        if adapter is not None and not isinstance(adapter, str):
            raise ValueError("adapter must be a string adapter name")
        # Per-request model selection (multi-model weight pool): a
        # string name from spec.<rev>.models.artifacts; absent = the
        # revision's default model. Unknown names are a client 400; a
        # pool with every slot refcount-pinned is a 503 (requeue).
        model = body.get("model")
        if model is not None and not isinstance(model, str):
            raise ValueError("model must be a string model name")
        # QoS class ("interactive"/"batch"): per-request override of
        # the revision default; validated by the engine.
        qos = body.get("qos")
        if qos is not None and not isinstance(qos, str):
            raise ValueError("qos must be a string class name")
        # Billable tenant key (usage metering): an explicit non-empty
        # string, else the engine derives it from the resolved adapter
        # ("" and absent both mean "bill to the adapter tenant").
        tenant = body.get("tenant")
        if tenant is not None and not isinstance(tenant, str):
            raise ValueError("tenant must be a string")
        # Per-request deadline in milliseconds (the X-KFX-Deadline-Ms
        # header lands here too — the server merges it into the body).
        deadline_ms = body.get("deadline_ms")
        if deadline_ms is not None:
            if isinstance(deadline_ms, bool) \
                    or not isinstance(deadline_ms, (int, float)):
                raise ValueError("deadline_ms must be a number")
            if deadline_ms <= 0:
                raise ValueError("deadline_ms must be > 0")
        return {
            "prompts": [list(map(int, p)) for p in prompts],
            "stop": stop,
            "adapter": adapter,
            "model": model,
            "qos": qos,
            "tenant": tenant or None,
            "deadline_s": (float(deadline_ms) / 1000.0
                           if deadline_ms is not None else None),
            "kw": dict(
                max_new_tokens=int(body.get("max_new_tokens", 32)),
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                seed=int(body.get("seed", 0))),
        }

    def _wait_budget_s(self, deadline_s: Optional[float]) -> float:
        """The result-wait clock: the request's own deadline when it
        has one (deadline-derived timeout — the engine and the client
        agree on ONE clock), else the engine's request_timeout_s
        default (50s). Either way capped under the router's 60s
        backend timeout so a queue-starved request fails with a clean
        engine error, never a router 502."""
        cap = _BACKEND_TIMEOUT_S - 2.0
        if deadline_s is not None:
            return min(deadline_s, cap)
        return min(self._engine.request_timeout_s, cap)

    def _record_generate(self, n_tokens: int, elapsed: float) -> None:
        # Decode throughput over the trailing window, for `kfx top` and
        # /metrics. kfx_lm_generated_tokens_total is the engine's: it
        # counts emitted tokens itself, per chunk.
        self._rate.record(n_tokens)
        self.metrics.gauge(
            "kfx_lm_tokens_per_second",
            "Decode throughput over the trailing 30s window.").set(
                round(self._rate.rate(), 2), model=self.name)
        self.metrics.histogram(
            "kfx_lm_generate_seconds",
            "Wall time of generate calls.").observe(elapsed,
                                                    model=self.name)

    def generate(self, body: Dict[str, Any]) -> Dict[str, Any]:
        p = self._parse_generate(body)
        t0 = time.perf_counter()
        # A re-dispatched body whose generation migrated HERE
        # attaches to the adopted in-flight request instead of
        # recomputing (kv_import indexed it by resume key).
        entry = (self._claim_resume(self._resume_key_for(p))
                 if len(p["prompts"]) == 1 else None)
        if entry is not None:
            reqs = [entry["req"]]
        else:
            # submit_batch + result instead of generate(): identical
            # semantics (same atomic enqueue, same batch deadline),
            # but the Request handles survive for the per-request
            # timing block the flight recorder computes.
            reqs = self._engine.submit_batch(
                p["prompts"], stop_token=p["stop"],
                adapter=p["adapter"], model=p["model"],
                qos=p["qos"],
                deadline_s=p["deadline_s"], tenant=p["tenant"],
                **p["kw"])
        deadline = time.monotonic() \
            + self._wait_budget_s(p["deadline_s"])
        out = [r.result(max(0.001, deadline - time.monotonic()))
               for r in reqs]
        elapsed = time.perf_counter() - t0
        n_tokens = sum(len(ids) for ids in out)
        tps = n_tokens / elapsed if elapsed > 0 else 0.0
        self._record_generate(n_tokens, elapsed)
        result = {"generated_tokens": out,
                  "tokens_per_second": round(tps, 2)}
        if self._engine.flight is not None:
            # Per-request latency attribution, one breakdown per
            # prompt in order — the server also folds the first into
            # the X-Kfx-Timing response header.
            flight = self._engine.flight
            result["timing"] = [flight.timing(r) for r in reqs]
        return result

    def generate_stream(self, body: Dict[str, Any]
                        ) -> Iterator[bytes]:
        """SSE token streaming (docs/serving.md "Request plane").
        Validates and SUBMITS synchronously — ValueError /
        EngineOverloaded raise here, before any bytes stream, so the
        server still answers a clean 400/503 — then returns an
        iterator of SSE events:

            data: {"index": i, "token": t}\\n\\n      per token
            data: {"done": true, "n_tokens": N, ...}\\n\\n

        ``stream_skip`` (the router's mid-stream recovery knob)
        suppresses the first N deterministically-regenerated tokens
        and starts the client-visible ``index`` at N, so a resumed
        stream concatenates byte-identical with the events the dead
        replica already delivered. A mid-stream engine failure emits
        an ``event: error`` frame and ends the stream."""
        p = self._parse_generate(body)
        if len(p["prompts"]) != 1:
            raise ValueError("streaming serves exactly one prompt "
                             "per request")
        skip = body.get("stream_skip", 0)
        if isinstance(skip, bool) or not isinstance(skip, int) \
                or skip < 0:
            raise ValueError("stream_skip must be an int >= 0")
        budget_s = self._wait_budget_s(p["deadline_s"])
        # The request's own deadline is absolute; the default budget
        # is for a wait without progress.
        idle = p["deadline_s"] is None
        # A re-dispatched stream whose generation migrated HERE
        # attaches to the adopted request: tokens that traveled with
        # the pages replay first (their indices continue the donor's
        # engine order, so stream_skip dedups exactly), then the
        # adoption queue delivers receiver-generated tokens live.
        entry = self._claim_resume(self._resume_key_for(p))
        if entry is not None:
            return self._stream_events(entry["req"], entry["q"], skip,
                                       budget_s, idle,
                                       prefix=entry["imported"])
        q: "_queue.Queue[Optional[int]]" = _queue.Queue()
        req = self._engine.submit(
            p["prompts"][0], stop_token=p["stop"],
            adapter=p["adapter"], model=p["model"], qos=p["qos"],
            deadline_s=p["deadline_s"], tenant=p["tenant"],
            meter_skip=skip, on_token=q.put, **p["kw"])
        return self._stream_events(req, q, skip, budget_s, idle)

    @staticmethod
    def _sse(obj: Dict[str, Any], event: str = "") -> bytes:
        head = f"event: {event}\n" if event else ""
        return (head + "data: " + json.dumps(obj)
                + "\n\n").encode("utf-8")

    def _stream_events(self, req, q, skip: int, budget_s: float,
                       idle: bool, prefix: int = 0) -> Iterator[bytes]:
        """``idle``: ``budget_s`` bounds each wait without a token
        (the default budget: what the router's per-read timeout sees,
        so a long generation that keeps delivering is not starved);
        otherwise it bounds the whole stream (the request's own
        ``deadline_s``: the engine and the client agree on ONE
        clock)."""
        t0 = time.perf_counter()
        deadline = time.monotonic() + budget_s
        seen = 0
        # Adopted generations (kv_import): req.tokens[:prefix] were
        # produced before the queue attached — replay them by engine
        # index, honoring the same skip window.
        for i in range(prefix):
            if i >= skip:
                yield self._sse({"index": i,
                                 "token": int(req.tokens[i])})
            seen = i + 1
        while True:
            try:
                tok = q.get(timeout=min(
                    0.25, max(0.001, deadline - time.monotonic())))
            except _queue.Empty:
                if time.monotonic() >= deadline:
                    yield self._sse(
                        {"error": "engine did not complete the "
                                  f"request within {budget_s}s",
                         "code": 503}, event="error")
                    return
                continue
            if tok is None:
                break
            if idle:
                deadline = time.monotonic() + budget_s
            if seen >= skip:
                yield self._sse({"index": seen, "token": tok})
            seen += 1
        if req.error is not None:
            from .engine import EngineOverloaded, RequestMigrated
            if isinstance(req.error, RequestMigrated):
                # Mid-stream migration: sever instead of erroring.
                # The server's SSE pump turns an iterator exception
                # into a hard connection cut — exactly the truncated
                # stream the router's mid-SSE recovery retries on;
                # its re-dispatched body (stream_skip = tokens
                # already relayed) then claims the adopted
                # generation on the peer and the client's stream
                # concatenates byte-identical.
                raise ConnectionResetError(str(req.error))
            code = 503 if isinstance(req.error, EngineOverloaded) \
                else 500
            yield self._sse({"error": str(req.error), "code": code},
                            event="error")
            return
        # Drain the race: tokens notified between the last get and
        # the sentinel are already in req.tokens — emit any the loop
        # has not streamed yet (exact once: seen tracks engine order).
        for i in range(seen, len(req.tokens)):
            if i >= skip:
                yield self._sse({"index": i, "token": req.tokens[i]})
            seen = i + 1
        elapsed = time.perf_counter() - t0
        n = len(req.tokens)
        self._record_generate(n, elapsed)
        tps = n / elapsed if elapsed > 0 else 0.0
        done = {"done": True, "n_tokens": n,
                "tokens_per_second": round(tps, 2)}
        if self._engine.flight is not None:
            done["timing"] = self._engine.flight.timing(req)
        yield self._sse(done)
