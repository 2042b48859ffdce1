"""InferenceService operator: reconciles serving resources onto local
model-server processes behind a traffic router.

Reference shape (SURVEY.md §2.1/§3 CS3): KFServing controller → Knative
Service per component → pods with storage-initializer + server, Istio
splitting default/canary traffic, KPA scaling on concurrency. Here:

  * each revision (default / canary) runs ``minReplicas`` supervised
    server subprocesses (independent respawn — one replica dying must not
    restart the others, unlike a training gang);
  * a Router per InferenceService does the Istio duty: percentage canary
    split + round-robin over live replicas;
  * readiness = the server's /v1/models/{name} probe; status conditions
    PredictorReady/Ready and status.url follow it;
  * minReplicas=0 scale-to-zero: the router's cold-request hook re-spawns
    a replica on demand (Knative activator-lite);
  * self-healing: a LIVENESS probe distinct from readiness (/healthz
    reporting a wedged decode loop -> SIGKILL + respawn, counted as
    kfx_replica_restarts_total{reason="wedged"}), crash-loop backoff on
    replica exits (reason="crashed"), and drain-before-kill on every
    PLANNED kill — scale-in and revision respawn POST /drain and wait a
    bounded window (spec drainWindowSeconds) so in-flight requests
    finish or re-dispatch instead of dying with the process
    (serving.drain span + kfx_serving_drain_seconds).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Dict, List, Optional, Tuple

from .. import chaos
from ..api.serving import (
    ISVC_EXPLAINER_READY,
    ISVC_PREDICTOR_READY,
    ISVC_READY,
    ISVC_TRANSFORMER_READY,
    InferenceService,
)
from ..core.controller import Controller, Result
from ..core.store import Conflict, NotFound, ResourceStore
from ..obs import trace as obs_trace
from ..obs.metrics import default_registry
from ..serving.autoscaler import (
    COLD_START_CHAOS_POINT,
    PROGRESSING,
    ROLLBACK_ANNOTATION,
    ROLLED_BACK,
    ConcurrencyAutoscaler,
    Decision,
    RolloutPlan,
    SLOWindow,
    autoscaler_config_from_spec,
    chaos_skip_decision,
    revision_slo_state,
    rollout_spec_from_dict,
)
from ..serving.router import Router
from ..utils.net import free_port
from ..utils.proc import inject_pythonpath

@dataclasses.dataclass
class _Replica:
    proc: subprocess.Popen
    port: int
    ready: bool = False
    # Consecutive liveness-probe failures (/healthz answering
    # "wedged"): distinct from readiness — a wedged decode loop keeps
    # answering readiness probes forever.
    live_fails: int = 0


class _Revision:
    """Supervised replica set for one component revision of one
    InferenceService: a predictor revision (default/canary) or an
    inference-graph component (transformer/explainer, serving/graph.py)."""

    def __init__(self, name: str, model_name: str, model_dir: str,
                 workdir: str, batcher: Optional[dict],
                 device: str = "default", role: str = "predictor",
                 graph: Optional[dict] = None,
                 container: Optional[dict] = None,
                 speculative: Optional[dict] = None,
                 quantization: Optional[dict] = None,
                 prefill_chunk: Optional[int] = None,
                 adapters: Optional[dict] = None,
                 models: Optional[dict] = None,
                 qos_default: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 rate_limits: Optional[dict] = None,
                 lm_role: str = "mixed",
                 kv_offload_pages: Optional[int] = None):
        self.name = name
        self.model_name = model_name
        self.model_dir = model_dir
        self.workdir = workdir
        self.batcher = batcher
        self.device = device
        self.role = role
        self.graph = graph or {}
        # Speculative-decode spec ({draftLayers, proposeTokens,
        # enabled}, api/serving.py) — exported to the replica as the
        # KFX_LM_SPEC_* knobs the LMPredictor reads; classifier
        # frameworks ignore them.
        self.speculative = speculative
        # Quantization spec ({weights, kv}, api/serving.py) — exported
        # as the KFX_LM_QUANT / KFX_LM_KV_QUANT knobs the LMPredictor
        # reads at load; classifier frameworks ignore them.
        self.quantization = quantization
        # spec.<rev>.prefillChunkTokens (api/serving.py) — exported as
        # KFX_LM_PREFILL_CHUNK; None leaves the predictor's default.
        self.prefill_chunk = prefill_chunk
        # Multi-tenant LoRA adapters ({artifacts, default, slots, rank,
        # fallback}, api/serving.py) — exported as the KFX_LM_ADAPTER*
        # knobs the LMPredictor reads at load; classifier frameworks
        # ignore them.
        self.adapters = adapters
        # Multi-model weight pool ({artifacts, default, slots,
        # idleSeconds}, api/serving.py) — exported as the
        # KFX_LM_MODELS / KFX_LM_MODEL_DEFAULT / KFX_LM_WEIGHT_SLOTS /
        # KFX_LM_WEIGHT_IDLE_S knobs the LMPredictor reads at load.
        # Scale-from-zero for a pooled model is a weight SWAP on a
        # warm replica, not a process spawn — the replica handles it
        # on admission and records it on the same cold-start
        # histogram (mode="swap" vs this controller's mode="spawn").
        self.models = models
        # Request plane (spec.<rev>.qosDefault / deadlineMs /
        # rateLimits, api/serving.py) — exported as KFX_LM_QOS_DEFAULT
        # / KFX_LM_DEADLINE_MS / KFX_LM_RATE_LIMITS; None leaves the
        # predictor's defaults (interactive, no deadline, no limits).
        self.qos_default = qos_default
        self.deadline_ms = deadline_ms
        self.rate_limits = rate_limits
        # KV transfer plane (spec.<rev>.role / kvOffloadPages,
        # api/serving.py): the disaggregation tier this revision's
        # replicas serve ("prefill" ships finished prompts' pages to
        # the decode tier, "decode" receives them, "mixed" does both
        # phases locally) and the host-RAM offload capacity. Exported
        # as KFX_LM_ROLE / KFX_LM_KV_OFFLOAD_PAGES; the decode-peer
        # URL set is NOT env — ports change on respawn, so the
        # controller pushes it to live replicas via :kvpeers instead.
        self.lm_role = lm_role
        self.kv_offload_pages = kv_offload_pages
        # Last :kvpeers payload acked per replica port (push dedup).
        self.kv_peers_pushed: Dict[int, bytes] = {}
        # KFServing custom-predictor parity: a user-provided container
        # command serves the port instead of a framework server. The
        # command sees KFX_PORT / KFX_MODEL_NAME (and $(KFX_PORT)-style
        # references expand, k8s container semantics).
        self.container = container
        self.replicas: List[_Replica] = []
        self.restarts = 0
        self.spawn_error = ""  # last custom-container launch failure
        # Crash-loop backoff: each reap that finds dead replicas doubles
        # the respawn delay (0.5s .. 30s); a replica reaching readiness
        # resets it. last_crashes is the per-reap dead count the
        # controller reads to attribute kfx_replica_restarts_total.
        self.backoff_s = 0.0
        self.backoff_until = 0.0
        self.last_crashes = 0
        self.last_dead: List[tuple] = []  # (pid, port) per reaped corpse
        # Decode-engine load/state projections (autoscaler queue-depth
        # signal, `kfx top`'s KV%/SKIP%/ACC%/Q columns) — refreshed
        # each reconcile from the CENTRAL telemetry store (the one
        # scraper polls every replica's /metrics; the operator owns no
        # private polling loop).
        self.engine_queue = 0.0
        self.engine_kv_pages = 0.0
        self.engine_kv_free = 0.0
        self.engine_spec_rate: Optional[float] = None
        self.engine_quant: Optional[str] = None
        # Adapter-slot pool (multi-tenant LoRA): total/free HBM slots
        # summed across replicas — `kfx top`'s ADPT column; zero on
        # classifier or base-only LM revisions.
        self.engine_adapter_slots = 0.0
        self.engine_adapter_free = 0.0
        # Weight-slot pool (multi-model): total/free HBM checkpoint
        # slots summed across replicas and the per-model residency map
        # — `kfx top`'s MODELS column and status.pooledModels; empty
        # on classifier or single-model revisions.
        self.engine_weight_slots = 0.0
        self.engine_weight_free = 0.0
        self.engine_pooled: Dict[str, bool] = {}
        # Prefix-reuse token totals summed across replicas — the
        # revision-level prefill-skipped fraction for `kfx top`'s
        # SKIP% column (the per-replica caches compose into a fleet
        # cache under the router's prefix-affinity map).
        self.engine_prefix_reused = 0.0
        self.engine_prompt_tokens = 0.0
        # Per-QoS-class in-flight slot split (request plane) — `kfx
        # top`'s I/B column; None on classifier revisions (no
        # kfx_lm_class_active series at all).
        self.engine_active_interactive: Optional[float] = None
        self.engine_active_batch: Optional[float] = None
        # KV transfer plane: cumulative migrations (all reasons,
        # summed across replicas) for `kfx top`'s MIG column, and
        # host-RAM offload tier residency in pages.
        self.engine_migrations = 0.0
        self.engine_offload_pages = 0.0

    @property
    def engine_kv_util(self):
        """Fraction of the revision's KV pages in use (None when no
        decode engine answered — classifier revisions)."""
        if self.engine_kv_pages <= 0:
            return None
        return 1.0 - self.engine_kv_free / self.engine_kv_pages

    @property
    def engine_prefill_skip(self):
        """Fraction of admitted prompt tokens served from cached
        prefix pages across this revision's replicas (None before any
        prompt traffic or on classifier revisions)."""
        if self.engine_prompt_tokens <= 0:
            return None
        return self.engine_prefix_reused / self.engine_prompt_tokens

    def spawn(self) -> None:
        port = free_port()
        if self.container is not None:
            from ..runtime.gang import expand_k8s_refs

            env = inject_pythonpath(dict(os.environ))
            # Span env BEFORE the container's own: a stale inherited
            # KFX_WORKDIR/KFX_COMPONENT must not misroute this
            # replica's span log, but an explicit container env wins.
            self._span_env(env)
            for e in self.container.get("env") or []:
                env[str(e.get("name"))] = str(e.get("value"))
            env["KFX_PORT"] = env["PORT"] = str(port)
            env["KFX_MODEL_NAME"] = self.model_name
            argv = [expand_k8s_refs(a, env)
                    for a in (list(self.container.get("command") or [])
                              + list(self.container.get("args") or []))]
            os.makedirs(self.workdir, exist_ok=True)
            log_path = os.path.join(
                self.workdir, f"{self.name}-{len(self.replicas)}.log")
            with open(log_path, "ab") as logf:
                try:
                    proc = subprocess.Popen(argv, env=env, stdout=logf,
                                            stderr=subprocess.STDOUT)
                except OSError as e:
                    # A typo'd binary must surface as a status/event,
                    # not a reconcile crash-retry loop.
                    logf.write(f"spawn failed: {e}\n".encode())
                    self.spawn_error = f"{argv[:1]}: {e}"
                    return
            self.spawn_error = ""
            self.replicas.append(_Replica(proc=proc, port=port))
            return
        if self.role == "predictor":
            argv = [sys.executable, "-m", "kubeflow_tpu.serving.server",
                    f"--model-dir={self.model_dir}",
                    f"--name={self.model_name}",
                    f"--port={port}", f"--device={self.device}"]
            if self.batcher:
                argv += [
                    f"--max-batch-size={self.batcher.get('maxBatchSize', 32)}",
                    "--batcher-max-latency-ms="
                    f"{self.batcher.get('maxLatencyMs', 2.0)}",
                    "--batcher-reply-timeout-s="
                    f"{self.batcher.get('replyTimeoutS', 60.0)}"]
        else:
            argv = [sys.executable, "-m", "kubeflow_tpu.serving.graph",
                    self.role, f"--name={self.model_name}",
                    f"--port={port}",
                    f"--predictor-url={self.graph['predictor_url']}"]
            if self.role == "transformer" and self.graph.get("module"):
                argv.append(f"--module={self.graph['module']}")
            if self.role == "explainer":
                argv += [f"--method={self.graph.get('method', 'occlusion')}",
                         "--feature-groups="
                         f"{self.graph.get('featureGroups', 16)}",
                         f"--baseline={self.graph.get('baseline', 0.0)}"]
        os.makedirs(self.workdir, exist_ok=True)
        env = inject_pythonpath(dict(os.environ))
        self._span_env(env)
        self._spec_env(env)
        self._quant_env(env)
        self._prefill_env(env)
        self._adapter_env(env)
        self._models_env(env)
        self._request_plane_env(env)
        self._kv_env(env)
        logf = open(os.path.join(
            self.workdir, f"{self.name}-{len(self.replicas)}.log"), "ab")
        proc = subprocess.Popen(argv, env=env, stdout=logf,
                                stderr=subprocess.STDOUT)
        logf.close()
        self.replicas.append(_Replica(proc=proc, port=port))

    def _spec_env(self, env: dict) -> None:
        """spec.<rev>.speculative -> the LMPredictor's KFX_LM_SPEC_*
        env knobs. Only explicit fields are exported (the predictor
        owns the defaults); ``enabled: false`` exports KFX_LM_SPEC=0 —
        the manifest-level escape hatch."""
        sp = self.speculative
        if sp is None or self.role != "predictor":
            return
        if sp.get("enabled") is False:
            env["KFX_LM_SPEC"] = "0"
        if sp.get("draftLayers") is not None:
            env["KFX_LM_SPEC_LAYERS"] = str(int(sp["draftLayers"]))
        if sp.get("proposeTokens") is not None:
            env["KFX_LM_SPEC_TOKENS"] = str(int(sp["proposeTokens"]))

    def _prefill_env(self, env: dict) -> None:
        """spec.<rev>.prefillChunkTokens -> KFX_LM_PREFILL_CHUNK (the
        chunked-prefill decode-stall bound, docs/serving.md). Only an
        explicit field is exported — the predictor owns the default;
        0 is the manifest-level monolithic-prefill escape hatch."""
        if self.prefill_chunk is None or self.role != "predictor":
            return
        env["KFX_LM_PREFILL_CHUNK"] = str(int(self.prefill_chunk))

    def _adapter_env(self, env: dict) -> None:
        """spec.<rev>.adapters -> the LMPredictor's multi-tenant LoRA
        knobs: the artifacts map rides as JSON (KFX_LM_ADAPTERS), the
        optional default/slots/rank/fallback knobs export only when
        explicit (the predictor owns the defaults)."""
        ad = self.adapters
        if ad is None or self.role != "predictor":
            return
        env["KFX_LM_ADAPTERS"] = json.dumps(ad.get("artifacts") or {})
        if ad.get("default") is not None:
            env["KFX_LM_ADAPTER_DEFAULT"] = str(ad["default"])
        if ad.get("slots") is not None:
            env["KFX_LM_ADAPTER_SLOTS"] = str(int(ad["slots"]))
        if ad.get("rank") is not None:
            env["KFX_LM_ADAPTER_RANK"] = str(int(ad["rank"]))
        if ad.get("fallback") is not None:
            env["KFX_LM_ADAPTER_FALLBACK"] = str(ad["fallback"])

    def _models_env(self, env: dict) -> None:
        """spec.<rev>.models -> the LMPredictor's multi-model weight
        pool knobs: the artifacts map rides as JSON (KFX_LM_MODELS)
        with the default model's name; slots/idleSeconds export only
        when explicit (the predictor owns the defaults)."""
        md = self.models
        if md is None or self.role != "predictor":
            return
        env["KFX_LM_MODELS"] = json.dumps(md.get("artifacts") or {})
        env["KFX_LM_MODEL_DEFAULT"] = str(md.get("default") or "")
        if md.get("slots") is not None:
            env["KFX_LM_WEIGHT_SLOTS"] = str(int(md["slots"]))
        if md.get("idleSeconds") is not None:
            env["KFX_LM_WEIGHT_IDLE_S"] = str(float(md["idleSeconds"]))

    def _request_plane_env(self, env: dict) -> None:
        """spec.<rev>.qosDefault / deadlineMs / rateLimits -> the
        LMPredictor's request-plane knobs (QoS class default, the
        deadline-aware admission default, per-tenant token rate
        limits). Only explicit fields export — the predictor owns the
        defaults; classifier frameworks ignore them."""
        if self.role != "predictor":
            return
        if self.qos_default is not None:
            env["KFX_LM_QOS_DEFAULT"] = str(self.qos_default)
        if self.deadline_ms is not None:
            env["KFX_LM_DEADLINE_MS"] = str(float(self.deadline_ms))
        if self.rate_limits is not None:
            env["KFX_LM_RATE_LIMITS"] = json.dumps(self.rate_limits)

    def _kv_env(self, env: dict) -> None:
        """spec.<rev>.role / kvOffloadPages -> the LMPredictor's
        KV-transfer-plane knobs (disaggregation tier + host-RAM
        offload capacity). Only explicit fields export — "mixed" is
        the predictor's own default; classifier frameworks ignore
        them."""
        if self.role != "predictor":
            return
        if self.lm_role and self.lm_role != "mixed":
            env["KFX_LM_ROLE"] = str(self.lm_role)
        if self.kv_offload_pages is not None:
            env["KFX_LM_KV_OFFLOAD_PAGES"] = \
                str(int(self.kv_offload_pages))

    def _quant_env(self, env: dict) -> None:
        """spec.<rev>.quantization -> the LMPredictor's quantization
        env knobs. ``weights: int8`` quantizes an f32 export at load
        (or keeps an int8 export as-is); ``weights: f32`` is the
        manifest-level escape hatch that dequantizes an int8 export;
        ``kv: int8`` switches the engine's paged KV pools to int8."""
        q = self.quantization
        if q is None or self.role != "predictor":
            return
        w = q.get("weights")
        if w == "int8":
            env["KFX_LM_QUANT"] = "int8"
        elif w == "f32":
            env["KFX_LM_QUANT"] = "0"
        k = q.get("kv")
        if k == "int8":
            env["KFX_LM_KV_QUANT"] = "int8"
        elif k == "f32":
            env["KFX_LM_KV_QUANT"] = "0"

    def _span_env(self, env: dict) -> None:
        """Point the replica's span log (obs.trace auto-sink) at this
        revision's workdir, labelled by revision + replica ordinal —
        the model-server leg of the `kfx trace` timeline. Assigned
        unconditionally: a value inherited from the operator's own
        environment is stale, never authoritative."""
        env["KFX_WORKDIR"] = self.workdir
        env["KFX_COMPONENT"] = f"{self.name}-{len(self.replicas)}"

    def reap_and_respawn(self, want: int) -> None:
        """Keep `want` replicas alive; dead ones are replaced
        individually, behind a crash-loop backoff: every reap that
        finds corpses doubles the respawn delay (0.5s up to 30s, reset
        when a replica next reaches readiness), so a replica dying at
        startup burns a bounded spawn rate instead of fork-bombing the
        host. The controller reads ``last_crashes`` to count
        kfx_replica_restarts_total{reason="crashed"}."""
        alive = []
        crashed = 0
        dead = []
        for r in self.replicas:
            if r.proc.poll() is None:
                alive.append(r)
            else:
                crashed += 1
                self.restarts += 1
                dead.append((getattr(r.proc, "pid", 0), r.port))
        self.replicas = alive
        self.last_crashes = crashed
        # (pid, port) of this reap's corpses — what the controller's
        # crash-postmortem path matches against the flight-snapshot
        # files the replicas left in the workdir.
        self.last_dead = dead
        now = time.monotonic()
        if crashed:
            self.backoff_s = min(max(self.backoff_s * 2, 0.5), 30.0)
            self.backoff_until = now + self.backoff_s
        if now >= self.backoff_until:
            while len(self.replicas) < want:
                before = len(self.replicas)
                self.spawn()
                if len(self.replicas) == before:
                    break  # launch failed (spawn_error set); retry later
        while len(self.replicas) > want:
            r = self.replicas.pop()
            r.proc.terminate()

    def probe(self) -> int:
        """Refresh readiness; returns number of ready replicas."""
        n = 0
        for r in self.replicas:
            if not r.ready:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{r.port}/v1/models/"
                            f"{self.model_name}", timeout=1.0) as resp:
                        r.ready = json.load(resp).get("ready", False)
                except urllib.error.HTTPError:
                    # A custom server answered HTTP but doesn't speak
                    # the V1 readiness route: it is up — its protocol
                    # is its own business (KFServing probes the port).
                    r.ready = self.container is not None
                except (OSError, ValueError):
                    r.ready = False
            if r.ready:
                n += 1
        return n

    def endpoints(self) -> List[str]:
        return [f"127.0.0.1:{r.port}" for r in self.replicas if r.ready]

    def teardown(self) -> None:
        """Stop every replica and REAP it: a replica holds its chip
        until the process is gone, and whatever the plane starts next
        needs that chip."""
        for r in self.replicas:
            if r.proc.poll() is None:
                r.proc.terminate()
        deadline = time.time() + 3
        for r in self.replicas:
            while r.proc.poll() is None and time.time() < deadline:
                time.sleep(0.05)
            if r.proc.poll() is None:
                r.proc.kill()
                r.proc.wait()
        self.replicas.clear()


class _RolloutRuntime:
    """In-memory half of one InferenceService's canary rollout: the
    traffic plan plus the SLO delta window over the canary's router
    metrics. Durable state (percent/phase/rolled-back annotation) lives
    on the resource so a plane restart resumes, not restarts."""

    def __init__(self, spec_dict: dict, plan: RolloutPlan):
        self.spec_dict = spec_dict
        self.plan = plan
        self.window = SLOWindow()
        self.last_obs: Dict[str, object] = {}


class _IsvcRuntime:
    def __init__(self):
        self.router: Optional[Router] = None
        self.revisions: Dict[str, _Revision] = {}
        # A cold request arrived while no replica was live; resolved to a
        # per-revision flag at the next reconcile.
        self.cold_pending = False
        self.cold_hit: Dict[str, bool] = {}
        # Last spawn failure surfaced per revision (event dedup).
        self.reported_spawn_error: Dict[str, str] = {}
        # KPA loop per predictor revision (serving/autoscaler.py).
        self.autoscalers: Dict[str, ConcurrencyAutoscaler] = {}
        self.autoscaling_status: Dict[str, Dict] = {}
        # wall-clock start of an in-flight scale-from-zero, per revision
        # (closed into an autoscale.cold_start span at first readiness).
        self.cold_started: Dict[str, float] = {}
        self.rollout: Optional[_RolloutRuntime] = None
        self.rollout_status: Optional[Dict] = None
        # Scheduler-arbitration event dedup.
        self.reported_scale_block = ""


class InferenceServiceController(Controller):
    KIND = "InferenceService"
    RESYNC_PERIOD = 1.0

    # Liveness (distinct from readiness): consecutive wedged /healthz
    # verdicts before a replica is killed for restart. Two probes one
    # reconcile apart filter a single slow-dispatch blip without
    # stretching the restart window.
    LIVENESS_FAILS = 2
    # Bounded drain-before-kill window when the spec carries no
    # drainWindowSeconds.
    DEFAULT_DRAIN_WINDOW_S = 10.0

    def __init__(self, store: ResourceStore, home: str):
        super().__init__(store)
        self.home = home
        self._lock = threading.Lock()
        self._runtimes: Dict[str, _IsvcRuntime] = {}
        # Set by the control plane: the cluster gang scheduler. Serving
        # replica deltas are admitted through it as elastic serving
        # reservations (one replica == one chip), so bursty inference
        # preempts low-priority training and returns chips on scale-in.
        self.scheduler = None
        # Set by the control plane: the central telemetry store
        # (obs/tsdb.py). Engine status sampling and rollout SLO windows
        # read scraped history from here instead of polling replicas.
        self.telemetry = None

    def _reg(self):
        return self.metrics if self.metrics is not None \
            else default_registry()

    # -- lifecycle ----------------------------------------------------------
    def on_delete(self, obj) -> None:
        self._teardown(obj.key)

    def _teardown(self, key: str) -> None:
        with self._lock:
            rt = self._runtimes.pop(key, None)
        if self.scheduler is not None:
            ns, _, name = key.partition("/")
            self.scheduler.resize_serving(name, ns, 0)
        if rt is None:
            return
        for rev in rt.revisions.values():
            rev.teardown()
        if rt.router is not None:
            rt.router.stop()

    def shutdown(self) -> None:
        with self._lock:
            keys = list(self._runtimes)
        for k in keys:
            self._teardown(k)

    # -- reconcile ----------------------------------------------------------
    def reconcile(self, key: str) -> Optional[Result]:
        isvc = self.get_resource(key)
        if isvc is None:
            self._teardown(key)
            return None
        assert isinstance(isvc, InferenceService)

        with self._lock:
            rt = self._runtimes.get(key)
            if rt is None:
                rt = _IsvcRuntime()
                self._runtimes[key] = rt

        if rt.router is None:
            rt.router = Router(metrics=self._reg(), name=isvc.name,
                               namespace=isvc.namespace).start()
            ctrl, k = self, key

            def cold():
                with ctrl._lock:
                    r = ctrl._runtimes.get(k)
                if r is not None:
                    r.cold_pending = True
                ctrl.queue.add(k)

            rt.router.on_cold_request = cold
            self.record_event(isvc, "Normal", "RouterStarted",
                              f"router on 127.0.0.1:{rt.router.port}")

        # Resolve a pending cold request to the first minReplicas=0
        # revision that exists (the set the router would route to).
        if rt.cold_pending:
            for rev_name in ("default", "canary"):
                spec = isvc.revision_spec(rev_name)
                if spec is not None and int(spec.get("minReplicas", 1)) == 0:
                    rt.cold_hit[rev_name] = True
                    # The cold request counts as this revision's traffic;
                    # otherwise a slow model load could out-idle the
                    # scale-down window before the first request lands.
                    getattr(rt.router, rev_name).last_request_time = \
                        time.monotonic()
                    # Cold-start clock: closed into an
                    # autoscale.cold_start span (+ histogram) when the
                    # spawned replica first probes ready. A request that
                    # 503'd just before the replica turned ready is not
                    # a cold start — re-arming here would emit a bogus
                    # 0s span on the very next probe. A pooled revision
                    # with a warm replica never arms this clock at all:
                    # its cold path is a weight SWAP the replica itself
                    # closes into the same span/histogram (mode="swap",
                    # serving/weights.py) — process spawn, measured
                    # here as mode="spawn", is the fallback when no
                    # replica is alive to swap into.
                    rev = rt.revisions.get(rev_name)
                    if rev is None or not any(r.ready for r in rev.replicas):
                        rt.cold_started.setdefault(rev_name, time.time())
                    # Chaos: delay the scale-from-zero spawn — the
                    # activator lagging its cold request.
                    chaos.maybe_delay(COLD_START_CHAOS_POINT, default_s=0.5,
                                      target=f"{key}/{rev_name}")
                    break
            rt.cold_pending = False

        all_ready = True
        reg = self._reg()
        now_mono = time.monotonic()
        # PASS 1 — plan: ensure each predictor revision exists and
        # compute its desired replica count (activator floor + the KPA
        # loop in serving/autoscaler.py). Nothing spawns yet: the chip
        # delta across BOTH revisions is admitted through the scheduler
        # as one elastic serving reservation first.
        plans: Dict[str, Tuple[int, int]] = {}  # rev -> (floor, desired)
        for rev_name in ("default", "canary"):
            spec = isvc.revision_spec(rev_name)
            rev = rt.revisions.get(rev_name)
            if spec is None:
                if rev is not None:
                    rev.teardown()
                    del rt.revisions[rev_name]
                rt.autoscalers.pop(rev_name, None)
                rt.autoscaling_status.pop(rev_name, None)
                continue
            container = (spec.get("containers") or [None])[0]
            if container is not None:
                # Custom predictor: the user command owns model loading;
                # there is no storage URI to initialize.
                model_dir = ""
            else:
                model_dir = _resolve_storage_uri(
                    spec_storage_uri(spec),
                    os.path.join(self.home, "storage-cache"))
            batcher = spec.get("batcher")
            device = str(spec.get("device", "default"))
            speculative = spec.get("speculative")
            quantization = spec.get("quantization")
            prefill_chunk = spec.get("prefillChunkTokens")
            adapters = spec.get("adapters")
            models = spec.get("models")
            qos_default = spec.get("qosDefault")
            deadline_ms = spec.get("deadlineMs")
            rate_limits = spec.get("rateLimits")
            lm_role = str(spec.get("role", "mixed"))
            kv_offload_pages = spec.get("kvOffloadPages")
            if rev is None or rev.model_dir != model_dir \
                    or rev.device != device or rev.batcher != batcher \
                    or rev.container != container \
                    or rev.speculative != speculative \
                    or rev.quantization != quantization \
                    or rev.prefill_chunk != prefill_chunk \
                    or rev.adapters != adapters \
                    or rev.models != models \
                    or rev.qos_default != qos_default \
                    or rev.deadline_ms != deadline_ms \
                    or rev.rate_limits != rate_limits \
                    or rev.lm_role != lm_role \
                    or rev.kv_offload_pages != kv_offload_pages:
                if rev is not None:
                    # Revision respawn (model/device/batcher/spec-env
                    # change): drop the doomed replicas from the router
                    # FIRST, then drain them within the bounded window
                    # before the kill — in-flight requests finish or
                    # re-dispatch; none die with the old revision.
                    getattr(rt.router, rev_name).set_endpoints([])
                    self._drain_revision(isvc, rev_name, rev, spec, reg)
                    rev.teardown()
                prior_restarts = rev.restarts if rev is not None else 0
                rev = _Revision(
                    name=rev_name,
                    model_name=isvc.name,
                    model_dir=model_dir,
                    workdir=os.path.join(self.home, "serving",
                                         key.replace("/", "_")),
                    batcher=batcher,
                    device=device,
                    container=container,
                    speculative=speculative,
                    quantization=quantization,
                    prefill_chunk=prefill_chunk,
                    adapters=adapters,
                    models=models,
                    qos_default=qos_default,
                    deadline_ms=deadline_ms,
                    rate_limits=rate_limits,
                    lm_role=lm_role,
                    kv_offload_pages=kv_offload_pages,
                )
                # The restart tally is cumulative per revision NAME
                # (matching kfx_replica_restarts_total's label): a
                # planned spec change must not erase the history the
                # `kfx top` RESTARTS column shows.
                rev.restarts = prior_restarts
                rt.revisions[rev_name] = rev
                self.record_event(isvc, "Normal", "RevisionCreated",
                                  f"{rev_name} -> "
                                  f"{model_dir or 'custom container'}")
                # Seed the restart family (both reasons, zero samples)
                # so `scrape_metrics --require` holds before the first
                # failure.
                for reason in ("crashed", "wedged"):
                    self._count_restarts(isvc, rev_name, 0, reason, reg)
            want = int(spec.get("minReplicas", 1))
            if want == 0 and rt.cold_hit.get(rev_name):
                # Activator: scale from zero on traffic — and back to zero
                # once THIS revision's backend set has been idle for the
                # window (Knative KPA scale-down analogue; router-wide
                # traffic must not keep an untrafficked revision alive).
                # The idle clock only counts against a replica that
                # reached readiness: killing one mid-load would flap
                # forever under slow model loads.
                backend_set = getattr(rt.router, rev_name)
                idle_s = float(spec.get("scaleToZeroIdleSeconds", 60.0))
                idle = time.monotonic() - backend_set.last_request_time
                has_ready = any(r.ready for r in rev.replicas)
                if idle_s > 0 and has_ready and idle >= idle_s:
                    rt.cold_hit[rev_name] = False
                    rt.cold_started.pop(rev_name, None)
                    # Remove the revision from the router BEFORE killing
                    # its replicas: a request racing the scale-down must
                    # take the cold 503+activator path, not hit a dead
                    # backend.
                    backend_set.set_endpoints([])
                else:
                    want = 1
            # The spec-guaranteed floor (minReplicas, or the activator's 1
            # for a traffic-woken zero-scale revision): readiness is
            # judged against this, never against autoscaler targets.
            base_want = want
            plans[rev_name] = (base_want,
                               self._autoscale(key, isvc, rt, rev_name,
                                               rev, spec, base_want,
                                               now_mono, reg))

        # Chip arbitration (sched/scheduler.py): one elastic serving
        # reservation covers the sum of both revisions' targets. Growth
        # takes free capacity, then preempts strictly-lower-priority
        # training; shrink returns chips to the queue. Without a wired
        # scheduler (standalone controllers) every plan is granted.
        total_want = sum(d for _, d in plans.values())
        granted_total = total_want
        if self.scheduler is not None:
            granted_total = self.scheduler.resize_serving(
                isvc.name, isvc.namespace, total_want,
                priority=isvc.scheduling_priority())
            if granted_total < total_want:
                msg = (f"granted {granted_total}/{total_want} chip(s); "
                       f"waiting for capacity")
                if rt.reported_scale_block != msg:
                    rt.reported_scale_block = msg
                    self.record_event(isvc, "Warning", "ScaleBlocked", msg)
            elif rt.reported_scale_block:
                rt.reported_scale_block = ""
                self.record_event(
                    isvc, "Normal", "ScaleGranted",
                    f"serving reservation of {total_want} chip(s) granted")
        # Allocate granted chips: default first (it guarantees the
        # spec's floor traffic), the canary takes the remainder.
        remaining = granted_total
        grants: Dict[str, int] = {}
        for rev_name in ("default", "canary"):
            if rev_name not in plans:
                continue
            grants[rev_name] = min(plans[rev_name][1], remaining)
            remaining -= grants[rev_name]

        # PASS 2 — actuate: spawn/reap to the granted counts, probe
        # readiness, close cold-start spans.
        for rev_name, rev in list(rt.revisions.items()):
            if rev_name not in plans:
                continue
            base_want, desired = plans[rev_name]
            want = grants[rev_name]
            backend_set = getattr(rt.router, rev_name)
            if want < len(rev.replicas):
                # Scale-down ordering (same rule as scale-to-zero above):
                # drop the doomed replicas from the router BEFORE killing
                # them, or a racing request 502s against a dead port —
                # then DRAIN them within the bounded window so requests
                # already inside finish (or re-dispatch retriably)
                # instead of dying with the process.
                backend_set.set_endpoints(
                    [f"127.0.0.1:{r.port}"
                     for r in rev.replicas[:want] if r.ready])
                doomed = rev.replicas[want:]
                # Migrate-before-kill (KV transfer plane): each doomed
                # replica pushes its in-flight generations' pages to a
                # surviving peer FIRST, so scale-in moves decode work
                # byte-identically instead of shedding it into the
                # drain's retriable-503 recompute path. A failed
                # transfer is a degrade, not a loss — the drain below
                # still covers those requests.
                self._migrate_replicas(
                    isvc, rev_name, doomed,
                    [f"http://127.0.0.1:{r.port}"
                     for r in rev.replicas[:want] if r.ready],
                    "scale_in", reg)
                self._drain_replicas(
                    isvc, rev_name, doomed,
                    self._drain_window_s(isvc.revision_spec(rev_name)),
                    reg)
                # Terminate the DRAINED replicas explicitly, not by
                # count: reap's pop-while-over-want could otherwise
                # keep a drained (one-way, permanently 503ing) replica
                # in the fleet if a kept replica crashed in this same
                # pass and filled the scale-down quota with its corpse.
                del rev.replicas[want:]
                for r in doomed:
                    if r.proc.poll() is None:
                        r.proc.terminate()
            self._maybe_kill_replica(isvc, rev_name, rev)
            rev.reap_and_respawn(want)
            if rev.last_crashes:
                self._count_restarts(isvc, rev_name, rev.last_crashes,
                                     "crashed", reg)
                self.record_event(
                    isvc, "Warning", "ReplicaCrashed",
                    f"{rev_name}: {rev.last_crashes} replica(s) exited; "
                    f"respawn backoff {rev.backoff_s:.1f}s")
                # Crash-reap forensics: the corpse can't answer HTTP,
                # but its /healthz-refreshed flight-snapshot file may
                # survive in the workdir — bundle that instead.
                for pid, port in rev.last_dead:
                    self._capture_postmortem(isvc, rev_name, rev, reg,
                                             reason="crashed",
                                             port=port, pid=pid)
            reg.gauge(
                "kfx_autoscaler_replicas",
                "Replica processes running per revision (spawned, "
                "including those still loading).",
            ).set(len(rev.replicas), namespace=isvc.namespace, isvc=isvc.name,
                  revision=rev_name)
            if rev.spawn_error:
                # Launch failure (e.g. typo'd custom command): surface
                # once per distinct error; the respawn loop keeps
                # retrying (CrashLoopBackOff-style) without crashing
                # the reconcile.
                if rt.reported_spawn_error.get(rev_name) != rev.spawn_error:
                    rt.reported_spawn_error[rev_name] = rev.spawn_error
                    self.record_event(isvc, "Warning", "SpawnFailed",
                                      f"{rev_name}: {rev.spawn_error}")
            loading = [r for r in rev.replicas if not r.ready]
            ready = rev.probe()
            if any(r.ready for r in loading):
                # A replica spawned since the last crash REACHED
                # readiness: that ends the crash loop, so the next
                # crash backs off from 0.5s again. (An already-ready
                # sibling staying up must NOT reset it, or a
                # crash-looping replica next to one healthy peer would
                # respawn at the floor rate forever.)
                rev.backoff_s = 0.0
            if ready > 0 and rev_name in rt.cold_started:
                self._finish_cold_start(isvc, rt, rev_name, reg)
            self._probe_liveness(isvc, rev_name, rev, reg)
            # Readiness is judged against the spec's guarantee (base
            # replicas), not the autoscaler's transient target — a burst
            # must not flip a healthy, serving ISVC to NotReady while
            # extra replicas warm up.
            if ready < max(base_want, 1) and base_want > 0:
                all_ready = False

        # Inference-graph components (SURVEY.md §2.1 KFServing row, §3
        # CS3): transformer chained in front of the predictor, explainer
        # on :explain — each a supervised single-role replica set the
        # router routes by path/header (serving/graph.py).
        graph_ready: Dict[str, Optional[bool]] = {}
        for comp in ("transformer", "explainer"):
            spec = isvc.component_spec(comp)
            rev = rt.revisions.get(comp)
            backend_set = getattr(rt.router, comp)
            if spec is None:
                setattr(rt.router, f"{comp}_configured", False)
                if rev is not None:
                    backend_set.set_endpoints([])
                    rev.teardown()
                    del rt.revisions[comp]
                graph_ready[comp] = None  # drop any stale condition
                continue
            module = str(spec.get("module", ""))
            if "://" in module:
                # storage-initializer the hook file too — a single file,
                # not an export directory
                from ..serving.storage import fetch_file

                module = fetch_file(
                    module, os.path.join(self.home, "storage-cache"))
            graph = {
                "predictor_url": f"http://127.0.0.1:{rt.router.port}",
                "module": module,
                "method": str(spec.get("method", "occlusion")),
                "featureGroups": int(spec.get("featureGroups", 16)),
                "baseline": float(spec.get("baseline", 0.0)),
            }
            if rev is None or rev.graph != graph:
                if rev is not None:
                    rev.teardown()
                rev = _Revision(
                    name=comp, model_name=isvc.name, model_dir="",
                    workdir=os.path.join(self.home, "serving",
                                         key.replace("/", "_")),
                    batcher=None, role=comp, graph=graph)
                rt.revisions[comp] = rev
                self.record_event(isvc, "Normal", "ComponentCreated",
                                  f"{comp} component")
            want = max(1, int(spec.get("minReplicas", 1)))
            rev.reap_and_respawn(want)
            ready = rev.probe()
            backend_set.set_endpoints(rev.endpoints())
            setattr(rt.router, f"{comp}_configured", True)
            # Readiness against the spec's floor, same rule as the
            # predictor revisions above.
            graph_ready[comp] = ready >= want
            if ready < want:
                all_ready = False

        # Router wiring + traffic split. With a spec.rollout the canary
        # percent is CONTROLLER-OWNED: it steps up while the canary's
        # SLO holds and snaps to 0 on breach (_reconcile_rollout);
        # otherwise the static spec split applies.
        default_rev = rt.revisions.get("default")
        canary_rev = rt.revisions.get("canary")
        if default_rev is not None:
            rt.router.default.set_endpoints(default_rev.endpoints())
            # Default-adapter traffic must derive the same affinity
            # root the engine resolves (router._affinity_from_body).
            rt.router.default_adapter = str(
                (default_rev.adapters or {}).get("default") or "")
        if canary_rev is not None:
            rt.router.canary.set_endpoints(canary_rev.endpoints())
            rt.router.canary_percent = self._reconcile_rollout(isvc, rt, reg)
        else:
            rt.router.canary_percent = 0
            rt.rollout = None
            rt.rollout_status = None

        # KV transfer plane: point every prefill-tier replica at the
        # CURRENT decode-tier URL set (ports change on respawn, so
        # this is per-reconcile state, not spawn-time env).
        self._sync_kv_peers(isvc, rt)

        self._sync_status(isvc, rt, all_ready, graph_ready)
        return Result(requeue=True, requeue_after=0.25) if not all_ready \
            else None

    # -- autoscaling ---------------------------------------------------------
    def _autoscale(self, key: str, isvc: InferenceService,
                   rt: _IsvcRuntime, rev_name: str, rev: _Revision,
                   spec: dict, base_want: int, now_mono: float,
                   reg) -> int:
        """One revision's KPA cycle: sample the router's peak in-flight
        concurrency (+ decode-engine queue depth), feed the autoscaler,
        and return the desired replica count in [floor, maxReplicas].
        The ``autoscale.decide`` chaos point skips (or stalls) the
        decision, holding the current replica count for a cycle."""
        backend_set = getattr(rt.router, rev_name)
        cfg = autoscaler_config_from_spec(spec, base_want)
        asc = rt.autoscalers.get(rev_name)
        if asc is None:
            asc = rt.autoscalers[rev_name] = ConcurrencyAutoscaler(cfg)
        else:
            asc.reconfigure(cfg)
        if base_want == 0:
            # The activator owns the zero state: either this revision
            # was never traffic-woken, or its idle window just expired
            # (cold_hit cleared above). Stale samples from the drained
            # burst must not resurrect it — the next cold request
            # restarts the loop from scratch.
            asc.reset()
            rt.autoscaling_status[rev_name] = {
                "desired": 0, "target": cfg.target_concurrency,
                "panic": False, "reason": "scale-to-zero",
                "restarts": rev.restarts}
            reg.gauge(
                "kfx_autoscaler_desired_replicas",
                "Autoscaler target replicas per revision.",
            ).set(0, namespace=isvc.namespace, isvc=isvc.name,
                  revision=rev_name)
            return 0
        peak = backend_set.take_peak_concurrency()
        queue_depth = self._sample_engine(isvc, rev_name, rev)
        queue_depth += self._tier_pressure(isvc, rev_name, rev, cfg)
        asc.observe(now_mono, peak, queue_depth)
        reg.gauge(
            "kfx_router_peak_concurrency",
            "Peak in-flight concurrency per revision since the last "
            "autoscaler sample (the KPA load signal).",
        ).set(peak, namespace=isvc.namespace, isvc=isvc.name,
              revision=rev_name)
        current = len(rev.replicas)
        if cfg.max_replicas <= max(base_want, 1) and base_want >= 1:
            # Autoscaling disabled: the floor IS the target.
            decision = Decision(desired=base_want, panic=False, load=peak,
                                reason="static")
        elif chaos_skip_decision(f"{key}/{rev_name}"):
            # A skipped cycle freezes the AUTOSCALER, not the spec: the
            # floor still applies, or an injected cycle could hold a
            # revision below minReplicas (e.g. never replace a crashed
            # replica, or never answer a cold request).
            decision = Decision(desired=max(current, base_want),
                                panic=False, load=peak,
                                reason="chaos-skipped")
        else:
            decision = asc.desired(now_mono, current, base_want)
        reg.gauge(
            "kfx_autoscaler_desired_replicas",
            "Autoscaler target replicas per revision.",
        ).set(decision.desired, namespace=isvc.namespace,
              isvc=isvc.name, revision=rev_name)
        reg.gauge(
            "kfx_autoscaler_panic",
            "1 while the revision's autoscaler is in panic (burst) mode.",
        ).set(1 if decision.panic else 0, namespace=isvc.namespace,
              isvc=isvc.name, revision=rev_name)
        status = {
            "desired": decision.desired,
            "target": cfg.target_concurrency,
            "panic": decision.panic,
            "reason": decision.reason,
            # Cumulative replica restarts (crashes + wedge kills) —
            # `kfx top`'s RESTARTS column, same number the
            # kfx_replica_restarts_total family counts.
            "restarts": rev.restarts,
        }
        kv_util = rev.engine_kv_util
        if kv_util is not None:
            # Paged-KV pool utilization (token-weighted load — the
            # occupancy signal the dense slot count used to hide):
            # surfaced in `kfx top`'s per-isvc table.
            status["kvUtil"] = round(kv_util, 3)
        skip = rev.engine_prefill_skip
        if skip is not None:
            # Fraction of prompt tokens the revision served from
            # cached prefix pages — `kfx top`'s SKIP% column, the
            # revision-level view of the fleet number prefix-affinity
            # routing moves (docs/serving.md).
            status["prefillSkip"] = round(skip, 3)
        if rev.engine_spec_rate is not None:
            # Trailing-window draft acceptance (replica mean) —
            # `kfx top`'s ACC% column: the live signal for whether
            # speculative decoding is paying for its draft.
            status["specAcceptRate"] = round(rev.engine_spec_rate, 3)
        if rev.engine_quant is not None:
            # Engine quantization mode ("w8", "kv8", "w8+kv8", "d8",
            # "f32") — `kfx top`'s Q column.
            status["quant"] = rev.engine_quant
        if rev.engine_adapter_slots > 0:
            # Adapter-slot pool "pinned/total" (multi-tenant LoRA) —
            # `kfx top`'s ADPT column; absent on base-only revisions.
            used = max(0, int(rev.engine_adapter_slots
                              - rev.engine_adapter_free))
            status["adapters"] = \
                f"{used}/{int(rev.engine_adapter_slots)}"
        if rev.engine_weight_slots > 0:
            # Weight-slot pool "loaded/total" (multi-model) — `kfx
            # top`'s MODELS column; absent on single-model revisions.
            loaded = sum(1 for v in rev.engine_pooled.values() if v)
            status["models"] = \
                f"{loaded}/{int(rev.engine_weight_slots)}"
        if rev.engine_active_interactive is not None:
            # In-flight slot split "interactive/batch" (request-plane
            # QoS classes) — `kfx top`'s I/B column; absent on
            # classifier revisions.
            status["classes"] = (
                f"{int(rev.engine_active_interactive)}/"
                f"{int(rev.engine_active_batch or 0)}")
        # Disaggregation tier — `kfx top`'s ROLE column (P/D/M).
        status["role"] = rev.lm_role
        if rev.engine_migrations > 0:
            # Cumulative KV migrations out of this revision's replicas
            # (disagg handoffs + drain/scale-in/rebalance moves) —
            # `kfx top`'s MIG column.
            status["migrations"] = int(rev.engine_migrations)
        if rev.engine_offload_pages > 0:
            # Host-RAM offload tier residency (pages currently parked
            # off-HBM across replicas).
            status["offloadPages"] = int(rev.engine_offload_pages)
        rt.autoscaling_status[rev_name] = status
        return decision.desired

    def _tier_pressure(self, isvc: InferenceService, rev_name: str,
                       rev: _Revision, cfg) -> float:
        """Disaggregation-tier load shaping (DistServe-style): the two
        tiers saturate on DIFFERENT resources, so each converts its own
        signal into extra unmet-concurrency pressure on top of the
        shared queue-depth sample. The prefill tier is arrival-bound —
        a rising admission-to-first-prefill queue wait (the
        kfx_lm_queue_wait_seconds histogram read as a trailing mean)
        converts to pressure against the spec's per-replica target.
        The decode tier is residency-bound — token-weighted KV
        occupancy past the 85% headroom line converts likewise, so
        the tier scales out BEFORE the pool starts evicting live
        prefixes. Mixed revisions add nothing: peak concurrency +
        queue depth already cover both phases there."""
        if rev.lm_role == "decode":
            util = rev.engine_kv_util
            if util is None or util <= 0.85:
                return 0.0
            return ((util - 0.85) / 0.15) * cfg.target_concurrency \
                * max(1, len(rev.replicas))
        if rev.lm_role == "prefill" and self.telemetry is not None:
            sel = {"namespace": isvc.namespace, "isvc": isvc.name,
                   "revision": rev_name}
            waited = self.telemetry.query(
                "kfx_lm_queue_wait_seconds_sum", fn="delta",
                labels=sel, since_s=30.0).value
            n = self.telemetry.query(
                "kfx_lm_queue_wait_seconds_count", fn="delta",
                labels=sel, since_s=30.0).value
            if not waited or not n:
                return 0.0
            mean_wait = waited / n
            if mean_wait <= 0.1:
                return 0.0
            # One per-replica target of pressure per second of mean
            # queue wait past the 100ms grace: admitted work sitting
            # in the queue needs replicas regardless of how few
            # requests are in flight at the sample instant.
            return (mean_wait - 0.1) * cfg.target_concurrency \
                * max(1, len(rev.replicas))
        return 0.0

    # -- self-healing --------------------------------------------------------
    def _count_restarts(self, isvc: InferenceService, rev_name: str,
                        n: int, reason: str, reg) -> None:
        reg.counter(
            "kfx_replica_restarts_total",
            "Serving replica restarts by revision and reason "
            "(crashed = process exited, wedged = liveness kill).",
        ).inc(n, namespace=isvc.namespace, isvc=isvc.name,
              revision=rev_name, reason=reason)

    def _probe_liveness(self, isvc: InferenceService, rev_name: str,
                        rev: _Revision, reg) -> None:
        """Liveness, distinct from readiness: /healthz aggregates the
        decode-loop heartbeat, so a replica whose loop is wedged (stale
        progress with slots active) answers 503 "wedged" while its
        readiness route still says fine. After LIVENESS_FAILS
        consecutive verdicts the replica is SIGKILLed — a wedged loop
        cannot drain, so there is nothing to save — and the normal reap
        path respawns it next reconcile (no crash backoff: a wedge kill
        is the operator's own doing, not a crash loop)."""
        for r in list(rev.replicas):
            if not r.ready:
                continue  # still loading: not probed for liveness yet
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{r.port}/healthz",
                        timeout=1.0) as resp:
                    body = json.load(resp)
            except urllib.error.HTTPError as e:
                try:
                    body = json.load(e)
                except ValueError:
                    body = {}
            except (OSError, ValueError):
                # Connection-level failure = the process is dying or
                # dead — the crash path's business, not a wedge.
                continue
            if body.get("status") != "wedged":
                r.live_fails = 0
                continue
            r.live_fails += 1
            if r.live_fails < self.LIVENESS_FAILS:
                continue
            rev.replicas.remove(r)
            # Forensics BEFORE the SIGKILL: the wedged loop has stopped
            # appending, but the replica's HTTP threads still answer —
            # /debug/flight is exactly the state that would otherwise
            # die with the process.
            self._capture_postmortem(isvc, rev_name, rev, reg,
                                     reason="wedged", port=r.port,
                                     pid=r.proc.pid)
            if r.proc.poll() is None:
                r.proc.kill()
            rev.restarts += 1
            self._count_restarts(isvc, rev_name, 1, "wedged", reg)
            self.record_event(
                isvc, "Warning", "ReplicaWedged",
                f"{rev_name} replica :{r.port} decode loop stalled "
                f"({json.dumps(body.get('models') or {})}); killed for "
                "restart")
            self.queue.add(isvc.key)

    def _capture_postmortem(self, isvc: InferenceService, rev_name: str,
                            rev: _Revision, reg, reason: str,
                            port: int, pid: Optional[int]) -> None:
        """Bundle a dying replica's forensic state into
        ``<rev.workdir>/postmortem/<ts>-<pid>/`` (what `kfx postmortem`
        lists and renders): the flight ring + recent requests (fetched
        over HTTP for a wedged-but-answering replica, read from the
        /healthz-refreshed snapshot file when the corpse already
        exited), the replica's span JSONL tail, and the central TSDB's
        window of that replica's scraped series. Records a
        ``ReplicaPostmortem`` event with the path and counts
        kfx_postmortems_total{reason}. Best-effort throughout — a
        failed capture must never block the kill/respawn path."""
        flight = requests_doc = None
        if reason == "wedged":
            for path, into in (("/debug/flight", "flight"),
                               ("/debug/requests", "requests")):
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}{path}",
                            timeout=2.0) as resp:
                        doc = json.load(resp)
                except (OSError, ValueError):
                    doc = None
                if into == "flight":
                    flight = doc
                else:
                    requests_doc = doc
        if flight is None and pid is not None:
            # The snapshot file the server piggybacks on /healthz —
            # the only flight source a crashed corpse leaves behind.
            for snap in sorted(glob.glob(os.path.join(
                    rev.workdir, "flight", f"*-{pid}.json"))):
                try:
                    with open(snap) as f:
                        flight = json.load(f)
                    break
                except (OSError, ValueError):
                    continue
        if flight is None:
            return  # nothing recorded and no corpse file: no bundle
        ts = time.strftime("%Y%m%d-%H%M%S")
        bundle = os.path.join(rev.workdir, "postmortem", f"{ts}-{pid}")
        try:
            os.makedirs(bundle, exist_ok=True)
            with open(os.path.join(bundle, "flight.json"), "w") as f:
                json.dump(flight, f, indent=1)
            if requests_doc is not None:
                with open(os.path.join(bundle, "requests.json"),
                          "w") as f:
                    json.dump(requests_doc, f, indent=1)
            # Span tail: the replica's own JSONL sink(s), last 200
            # records — enough to see the final dispatches without
            # copying a soak's worth of spans.
            tail: List[str] = []
            for sp in sorted(glob.glob(os.path.join(
                    rev.workdir, "spans", f"*-{pid}.jsonl"))):
                try:
                    with open(sp) as f:
                        tail.extend(f.readlines()[-200:])
                except OSError:
                    continue
            if tail:
                with open(os.path.join(bundle, "spans.tail.jsonl"),
                          "w") as f:
                    f.writelines(tail[-200:])
            if self.telemetry is not None:
                window = self.telemetry.window(
                    {"instance": f"127.0.0.1:{port}"}, since_s=120.0)
                with open(os.path.join(bundle, "tsdb.json"), "w") as f:
                    json.dump(window, f)
            with open(os.path.join(bundle, "meta.json"), "w") as f:
                json.dump({"reason": reason, "pid": pid, "port": port,
                           "revision": rev_name,
                           "namespace": isvc.namespace,
                           "isvc": isvc.name,
                           "captured_at": time.time()}, f, indent=1)
        except OSError:
            return
        reg.counter(
            "kfx_postmortems_total",
            "Postmortem bundles captured for dying replicas, by "
            "reason (wedged|crashed).").inc(
                1, namespace=isvc.namespace, isvc=isvc.name,
                revision=rev_name, reason=reason)
        self.record_event(
            isvc, "Warning", "ReplicaPostmortem",
            f"{rev_name} replica :{port} ({reason}): flight ring + "
            f"span tail + tsdb window captured at {bundle}")

    def _maybe_kill_replica(self, isvc: InferenceService, rev_name: str,
                            rev: _Revision) -> None:
        """Chaos point ``replica.kill``: SIGKILL a serving replica
        mid-request (docs/chaos.md) — the deterministic probe for the
        whole recovery story: the router re-dispatches the replica's
        in-flight generates to a healthy peer, the reap path counts a
        crashed restart and respawns."""
        for r in list(rev.replicas):
            inj = chaos.draw(
                "replica.kill",
                target=f"{isvc.namespace}/{isvc.name}/{rev_name}/"
                       f"{r.port}")
            if inj is None:
                continue
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode == "delay":
                continue
            if r.proc.poll() is None:
                r.proc.kill()

    def _drain_window_s(self, spec: Optional[dict]) -> float:
        try:
            return float((spec or {}).get("drainWindowSeconds",
                                          self.DEFAULT_DRAIN_WINDOW_S))
        except (TypeError, ValueError):
            return self.DEFAULT_DRAIN_WINDOW_S

    def _drain_replica(self, isvc: InferenceService, rev_name: str,
                       r: _Replica, window_s: float, reg) -> None:
        """Drain-before-kill: ask the replica to stop admitting and
        finish in-flight work within the bounded window, so a PLANNED
        kill (scale-in, revision respawn) never takes a request down
        with it. The replica sheds its queue with a retriable 503 (the
        router re-dispatches those to surviving replicas) and finishes
        the slots already decoding. The interval lands on the trace
        waterfall as a ``serving.drain`` span and in the
        kfx_serving_drain_seconds histogram."""
        t0 = time.time()
        drained = False
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{r.port}/drain?wait_s={window_s:g}",
                data=b"", method="POST")
            with urllib.request.urlopen(
                    req, timeout=window_s + 2.0) as resp:
                drained = bool(json.load(resp).get("drained", False))
        except (OSError, ValueError):
            pass  # dead or unresponsive: nothing left to drain
        duration = max(time.time() - t0, 0.0)
        obs_trace.record_span(
            "serving.drain", ts=t0, duration=duration,
            trace_id=obs_trace.trace_of(isvc),
            parent_id=obs_trace.span_of(isvc),
            namespace=isvc.namespace, isvc=isvc.name, revision=rev_name,
            port=str(r.port), drained="1" if drained else "0")
        reg.histogram(
            "kfx_serving_drain_seconds",
            "Drain-before-kill duration: drain request to empty engine "
            "or window expiry.").observe(
                duration, namespace=isvc.namespace, isvc=isvc.name,
                revision=rev_name)
        self.record_event(
            isvc, "Normal", "ReplicaDrained",
            f"{rev_name} replica :{r.port} drained in {duration:.2f}s"
            + ("" if drained else " (window expired with work left)"))

    def _drain_replicas(self, isvc: InferenceService, rev_name: str,
                        replicas: List[_Replica], window_s: float,
                        reg) -> None:
        """Drain several doomed replicas CONCURRENTLY: the drains share
        one window instead of stacking N of them, so a multi-replica
        scale-in stalls this controller's reconcile loop for at most
        ~window_s, not N x window_s."""
        ready = [r for r in replicas if r.ready]
        if not ready:
            return
        if len(ready) == 1:
            self._drain_replica(isvc, rev_name, ready[0], window_s, reg)
            return
        threads = [threading.Thread(
            target=self._drain_replica,
            args=(isvc, rev_name, r, window_s, reg)) for r in ready]
        for t in threads:
            t.start()
        for t in threads:
            t.join(window_s + 5.0)

    def _migrate_replicas(self, isvc: InferenceService, rev_name: str,
                          doomed: List[_Replica], survivors: List[str],
                          reason: str, reg) -> None:
        """Migrate-before-kill: POST ``:migrate`` to each doomed
        replica, pointing it at a surviving peer (round-robin), so a
        planned kill moves in-flight KV pages instead of recomputing
        them. Best-effort by design: an unreachable replica or a
        refused transfer falls through to the drain + seeded
        re-dispatch recovery that already guarantees zero lost
        requests."""
        if not survivors:
            return
        for i, r in enumerate(doomed):
            if not r.ready:
                continue
            peer = survivors[i % len(survivors)]
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{r.port}/v1/models/{isvc.name}"
                    f":migrate?peer={urllib.parse.quote(peer, safe='')}"
                    f"&reason={reason}", data=b"", method="POST")
                with urllib.request.urlopen(req, timeout=10.0) as resp:
                    stats = json.load(resp)
            except (OSError, ValueError):
                continue
            moved = int(stats.get("moved", 0) or 0)
            if moved:
                self.record_event(
                    isvc, "Normal", "KVMigrated",
                    f"{rev_name} replica :{r.port} moved {moved} "
                    f"request(s) / {int(stats.get('pages', 0) or 0)} "
                    f"page(s) to {peer} before {reason}")

    def _sync_kv_peers(self, isvc: InferenceService,
                       rt: _IsvcRuntime) -> None:
        """Point every READY prefill-tier replica at the current
        decode-tier URL set (all ready replicas of decode-role
        predictor revisions of this InferenceService). Pushed only
        when the set changed for that replica; a failed push retries
        next reconcile — until then the replica's handoff degrades to
        decoding locally."""
        decode = sorted(
            f"http://127.0.0.1:{r.port}"
            for rev in rt.revisions.values()
            if rev.role == "predictor" and rev.lm_role == "decode"
            for r in rev.replicas if r.ready)
        payload = json.dumps(decode).encode()
        for rev in rt.revisions.values():
            if rev.role != "predictor" or rev.lm_role != "prefill":
                continue
            live = set()
            for r in rev.replicas:
                live.add(r.port)
                if not r.ready or \
                        rev.kv_peers_pushed.get(r.port) == payload:
                    continue
                try:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{r.port}/v1/models/"
                        f"{isvc.name}:kvpeers", data=payload,
                        method="POST",
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=2.0):
                        pass
                except (OSError, ValueError):
                    continue
                rev.kv_peers_pushed[r.port] = payload
            for port in [p for p in rev.kv_peers_pushed
                         if p not in live]:
                del rev.kv_peers_pushed[port]  # respawned replica

    def _drain_revision(self, isvc: InferenceService, rev_name: str,
                        rev: _Revision, spec: Optional[dict],
                        reg) -> None:
        """Drain every ready replica of a revision about to be torn
        down (the respawn-on-spec-change path — quant/spec env changes
        and storage/device/batcher edits all land here)."""
        self._drain_replicas(isvc, rev_name, rev.replicas,
                             self._drain_window_s(spec), reg)

    def _sample_engine(self, isvc: InferenceService, rev_name: str,
                       rev: _Revision) -> float:
        """Decode-engine load/state for one revision, read from the
        CENTRAL telemetry store (obs/tsdb.py) — the scraper already
        polls every replica's /metrics and stamps namespace/isvc/
        revision, so the operator's status sampling is a label lookup,
        not its own HTTP polling loop (the pre-telemetry sampler
        urllib'd every replica's ?format=json block per reconcile).
        Returns the summed engine queue depth (the autoscaler's unmet-
        concurrency signal); classifier revisions simply have no
        kfx_lm_* series and read as zeros. Without a wired telemetry
        store (standalone controllers) the projections stay at their
        last values."""
        t = self.telemetry
        if t is None:
            return rev.engine_queue
        sel = {"namespace": isvc.namespace, "isvc": isvc.name,
               "revision": rev_name}
        # LIVE-state reads only: a respawned replica's replaced
        # generation keeps its dying per-instance gauges in the store
        # until GC, and summing two generations of the same slot would
        # double the queue/KV signal (spurious scale-ups).
        fresh_s = 10.0

        def total(family: str) -> float:
            return float(sum(
                v for _, v in t.latest_samples(family, sel,
                                               max_age_s=fresh_s)))

        rev.engine_queue = total("kfx_lm_queue_depth")
        rev.engine_kv_pages = total("kfx_lm_kv_pages")
        rev.engine_kv_free = total("kfx_lm_kv_pages_free")
        rev.engine_prefix_reused = total("kfx_lm_prefix_tokens_reused")
        rev.engine_prompt_tokens = total("kfx_lm_prompt_tokens_admitted")
        rev.engine_adapter_slots = total("kfx_lm_adapter_slots")
        rev.engine_adapter_free = total("kfx_lm_adapter_slots_free")
        # Weight-slot pool (multi-model): capacity/headroom for the
        # MODELS column, and the per-model residency map (the pooled
        # label rides the 0/1 gauge) for status.pooledModels —
        # "pooled but unloaded" is an explicit False, never absence.
        rev.engine_weight_slots = total("kfx_lm_weight_slots")
        rev.engine_weight_free = total("kfx_lm_weight_slots_free")
        pooled: Dict[str, bool] = {}
        for lab, v in t.latest_samples("kfx_lm_weight_model_loaded",
                                       sel, max_age_s=fresh_s):
            m = lab.get("pooled", "")
            if m:
                pooled[m] = bool(v) or pooled.get(m, False)
        rev.engine_pooled = pooled
        # KV transfer plane: cumulative migrations (all reasons) for
        # `kfx top`'s MIG column, host-RAM offload residency for the
        # status block.
        rev.engine_migrations = total("kfx_lm_kv_migrations_total")
        rev.engine_offload_pages = total("kfx_lm_kv_offload_pages")
        # Per-QoS-class in-flight split (`kfx top`'s I/B column): the
        # qos label rides the one family, so split by label value.
        # The engine exports both classes even at zero, so ANY sample
        # means "this revision has a request plane" (classifier
        # revisions have none and keep the None -> no I/B column).
        class_samples = t.latest_samples("kfx_lm_class_active", sel,
                                         max_age_s=fresh_s)
        if class_samples:
            by_class = {"interactive": 0.0, "batch": 0.0}
            for lab, v in class_samples:
                q = lab.get("qos", "")
                if q in by_class:
                    by_class[q] += v
            rev.engine_active_interactive = by_class["interactive"]
            rev.engine_active_batch = by_class["batch"]
        else:
            rev.engine_active_interactive = None
            rev.engine_active_batch = None
        rates = [v for _, v in
                 t.latest_samples("kfx_lm_spec_accept_rate", sel,
                                  max_age_s=fresh_s)]
        rev.engine_spec_rate = (sum(rates) / len(rates)) if rates else None
        modes = t.latest_samples("kfx_lm_quant_mode", sel,
                                 max_age_s=fresh_s)
        if modes:
            from ..serving.engine import quant_mode_string

            lab = modes[0][0]
            rev.engine_quant = quant_mode_string(
                lab.get("weights", "f32"), lab.get("kv", "f32"))
        else:
            rev.engine_quant = None
        return rev.engine_queue

    def scrape_targets(self):
        """The central scraper's discovery hook: every READY predictor
        replica's /metrics endpoint, labelled with the fleet identity
        the telemetry queries filter on. Loading replicas have no HTTP
        listener yet and graph components speak their own protocol —
        neither is a target."""
        out = []
        with self._lock:
            runtimes = dict(self._runtimes)
        for key, rt in runtimes.items():
            ns, _, name = key.partition("/")
            for rev_name, rev in list(rt.revisions.items()):
                if rev.role != "predictor":
                    continue
                for r in list(rev.replicas):
                    if not r.ready:
                        continue
                    out.append((
                        {"namespace": ns, "isvc": name,
                         "revision": rev_name,
                         "instance": f"127.0.0.1:{r.port}"},
                        f"http://127.0.0.1:{r.port}/metrics"))
        return out

    def _finish_cold_start(self, isvc: InferenceService, rt: _IsvcRuntime,
                           rev_name: str, reg) -> None:
        """Close a scale-from-zero window: the cold request arrived at
        ``cold_started[rev]`` and the revision just probed ready. The
        interval lands on the `kfx trace` waterfall as an
        ``autoscale.cold_start`` span under the service's admission
        span, and in the cold-start histogram."""
        started = rt.cold_started.pop(rev_name)
        duration = max(time.time() - started, 0.0)
        obs_trace.record_span(
            "autoscale.cold_start", ts=started, duration=duration,
            trace_id=obs_trace.trace_of(isvc),
            parent_id=obs_trace.span_of(isvc),
            namespace=isvc.namespace, isvc=isvc.name,
            revision=rev_name)
        # mode label: this controller path measures a process SPAWN;
        # a weight-pool replica closes its artifact-load swaps into
        # the same family as mode="swap" (serving/weights.py), so one
        # histogram answers "how much faster is swap than respawn".
        reg.histogram(
            "kfx_autoscaler_cold_start_seconds",
            "Scale-from-zero latency: cold request to first ready "
            "replica.",
        ).observe(duration, namespace=isvc.namespace,
                  isvc=isvc.name, revision=rev_name, mode="spawn")
        self.record_event(isvc, "Normal", "ColdStart",
                          f"{rev_name} scaled from zero in {duration:.2f}s")

    # -- canary rollout ------------------------------------------------------
    def _reconcile_rollout(self, isvc: InferenceService,
                           rt: _IsvcRuntime, reg) -> int:
        """The rollout state machine's impure shell: (re)build the plan
        from spec + durable status, advance it on its interval with the
        canary's windowed SLO numbers, persist phase/percent to status,
        and annotate + event a rollback. Returns the percent the router
        must apply."""
        spec_dict = isvc.rollout_spec()
        if not spec_dict:
            rt.rollout = None
            rt.rollout_status = None
            return isvc.canary_traffic_percent_split()
        now = time.monotonic()
        ro = rt.rollout
        if ro is None or ro.spec_dict != spec_dict:
            st = isvc.status.get("rollout") or {}
            percent, phase = 0, PROGRESSING
            if st.get("spec") == spec_dict:
                # Same rollout config as the durable status: resume it
                # (a plane restart must not re-traffic a rolled-back
                # canary).
                percent = int(st.get("percent", 0))
                phase = str(st.get("phase", PROGRESSING))
            elif ROLLBACK_ANNOTATION in isvc.metadata.annotations:
                # Spec changed: a NEW rollout attempt — clear the old
                # verdict so `kfx get` doesn't show a stale rollback.
                self._update_annotation(isvc, ROLLBACK_ANNOTATION, None)
            ro = rt.rollout = _RolloutRuntime(
                spec_dict,
                RolloutPlan(rollout_spec_from_dict(spec_dict), now,
                            percent=percent, phase=phase))
            # Re-base the SLO window at activation so pre-rollout
            # traffic never pollutes the first interval's delta.
            ro.window.advance(*revision_slo_state(
                self.telemetry, isvc.namespace, isvc.name, "canary"))
        plan = ro.plan
        if plan.due(now):
            p99, err_rate, n = ro.window.advance(
                *revision_slo_state(
                    self.telemetry, isvc.namespace, isvc.name, "canary"))
            tick = plan.tick(now, p99, err_rate, n)
            ro.last_obs = {
                "p99Ms": round(p99 * 1000.0, 1) if p99 is not None else None,
                "errorRate": round(err_rate, 4),
                "observed": n,
            }
            if tick.event is not None:
                etype, reason, message = tick.event
                self.record_event(isvc, etype, reason, message)
                if reason == "RolloutRolledBack":
                    ro.last_obs["reason"] = message
                    reg.counter(
                        "kfx_rollout_rollbacks_total",
                        "Automatic canary rollbacks on SLO breach.",
                    ).inc(1, namespace=isvc.namespace, isvc=isvc.name)
        if plan.phase == ROLLED_BACK and \
                ROLLBACK_ANNOTATION not in isvc.metadata.annotations:
            # Durable verdict; retried next reconcile on write conflict.
            self._update_annotation(
                isvc, ROLLBACK_ANNOTATION,
                (ro.last_obs or {}).get("reason") or "SLO breach")
        reg.gauge(
            "kfx_rollout_canary_percent",
            "Canary traffic percent the rollout controller applies.",
        ).set(plan.percent, namespace=isvc.namespace, isvc=isvc.name)
        rt.rollout_status = {"percent": plan.percent, "phase": plan.phase,
                             "spec": spec_dict, **ro.last_obs}
        return plan.percent

    def _update_annotation(self, isvc: InferenceService, key: str,
                           value: Optional[str]) -> None:
        fresh = self.get_resource(isvc.key)
        if fresh is None:
            return
        if value is None:
            fresh.metadata.annotations.pop(key, None)
        else:
            fresh.metadata.annotations[key] = value
        try:
            self.store.update(fresh)
            isvc.metadata.annotations = fresh.metadata.annotations
        except (Conflict, NotFound):
            self.queue.add(isvc.key)

    def _sync_status(self, isvc: InferenceService, rt: _IsvcRuntime,
                     all_ready: bool,
                     graph_ready: Optional[Dict[str, bool]] = None) -> None:
        fresh = self.get_resource(isvc.key)
        if fresh is None:
            return
        isvc = fresh
        url = f"http://127.0.0.1:{rt.router.port}"
        ready_counts = {name: len(rev.endpoints())
                        for name, rev in rt.revisions.items()}
        # Total spawned replicas alongside ready ones (KFServing's
        # component status carries both): the autoscaler's DECISION is
        # observable the moment it spawns, even while a new replica is
        # still loading its model.
        replica_counts = {name: len(rev.replicas)
                          for name, rev in rt.revisions.items()}
        changed = False
        if isvc.status.get("url") != url:
            isvc.status["url"] = url
            changed = True
        if isvc.status.get("readyReplicas") != ready_counts:
            isvc.status["readyReplicas"] = ready_counts
            changed = True
        if isvc.status.get("replicas") != replica_counts:
            isvc.status["replicas"] = replica_counts
            changed = True
        # Autoscaler + rollout projections: what `kfx top` / `kfx
        # rollout` render, and the durable state a restarted plane
        # resumes the rollout from.
        autoscaling = dict(rt.autoscaling_status)
        if autoscaling and isvc.status.get("autoscaling") != autoscaling:
            isvc.status["autoscaling"] = autoscaling
            changed = True
        # Weight-pool residency per revision ({model: loaded?} over
        # the FULL pooled set) — what `kfx get isvc` renders; "pooled
        # but unloaded" (False) means servable after one weight swap.
        pooled = {name: dict(rev.engine_pooled)
                  for name, rev in rt.revisions.items()
                  if rev.engine_pooled}
        if pooled:
            if isvc.status.get("pooledModels") != pooled:
                isvc.status["pooledModels"] = pooled
                changed = True
        elif "pooledModels" in isvc.status:
            del isvc.status["pooledModels"]
            changed = True
        if rt.rollout_status is None:
            if "rollout" in isvc.status:
                del isvc.status["rollout"]
                changed = True
        elif isvc.status.get("rollout") != rt.rollout_status:
            isvc.status["rollout"] = dict(rt.rollout_status)
            changed = True
        status = "True" if all_ready else "False"
        for ctype in (ISVC_PREDICTOR_READY, ISVC_READY):
            if not isvc.has_condition(ctype, status):
                isvc.set_condition(ctype, status,
                                   "RevisionsReady" if all_ready
                                   else "RevisionsNotReady", "")
                changed = True
        comp_conditions = {"transformer": ISVC_TRANSFORMER_READY,
                           "explainer": ISVC_EXPLAINER_READY}
        for comp, ok in (graph_ready or {}).items():
            ctype = comp_conditions[comp]
            if ok is None:
                # Component removed from the spec: its condition must not
                # linger at a stale True.
                conds = isvc.status.get("conditions", [])
                kept = [c for c in conds if c.get("type") != ctype]
                if len(kept) != len(conds):
                    isvc.status["conditions"] = kept
                    changed = True
                continue
            cstat = "True" if ok else "False"
            if not isvc.has_condition(ctype, cstat):
                isvc.set_condition(ctype, cstat,
                                   "ComponentReady" if ok
                                   else "ComponentNotReady", "")
                changed = True
        if changed:
            try:
                self.store.update_status(isvc)
            except (Conflict, NotFound):
                self.queue.add(isvc.key)

    # -- helpers ------------------------------------------------------------
    def router_url(self, key: str) -> Optional[str]:
        with self._lock:
            rt = self._runtimes.get(key)
        return None if rt is None or rt.router is None else \
            f"http://127.0.0.1:{rt.router.port}"


def spec_storage_uri(spec: dict) -> str:
    for fw in ("jax", "sklearn", "xgboost", "pytorch", "tensorflow", "onnx",
               "triton"):
        if fw in spec:
            return str(spec[fw].get("storageUri", ""))
    return str(spec.get("storageUri", ""))


def _resolve_storage_uri(uri: str, cache_dir: str) -> str:
    """Storage-initializer equivalent (serving/storage.py): resolve a URI
    to a local export dir, downloading remote schemes into the cache."""
    from ..serving.storage import initialize

    return initialize(uri, cache_dir)


def serving_controllers(store: ResourceStore, home: str) -> List[Controller]:
    return [InferenceServiceController(store, home)]
