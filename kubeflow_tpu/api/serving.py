"""Serving resource: InferenceService — KFServing API parity.

Shape follows the reference KFServing v1beta1-era API (SURVEY.md §2.1):
predictor/transformer/explainer components, framework-specific predictor
specs (here: ``jax``/``sklearn``/``xgboost``/``pytorch``/``custom``),
``storageUri`` model loading, default+canary traffic split
(``canaryTrafficPercent``), and min/max replica autoscaling knobs.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .base import Resource, ValidationError, register

ISVC_READY = "Ready"
ISVC_PREDICTOR_READY = "PredictorReady"
ISVC_TRANSFORMER_READY = "TransformerReady"
ISVC_EXPLAINER_READY = "ExplainerReady"
ISVC_FAILED = "Failed"

# Accepted predictor frameworks. Servers exist for jax (serving/server.py),
# pytorch (TorchScript, serving/torch_server.py), tensorflow (SavedModel,
# serving/tf_server.py), sklearn (joblib, serving/sklearn_server.py) and
# the LM export (:generate). xgboost / onnx / triton match the reference
# API surface but are NOT serveable in this environment — those runtimes
# are not installed and there is no network to fetch them (SURVEY.md
# §0.1); applying one fails at revision startup with a clear server-side
# error rather than at validation, so the same manifest works on an
# environment that has them.
PREDICTOR_FRAMEWORKS = ["jax", "sklearn", "xgboost", "pytorch", "tensorflow",
                        "onnx", "triton", "custom"]
COMPONENTS = ["predictor", "transformer", "explainer"]
EXPLAINER_METHODS = ["occlusion"]


@register
class InferenceService(Resource):
    KIND = "InferenceService"
    API_VERSION = "serving.kubeflow.org/v1beta1"
    PLURAL = "inferenceservices"

    # -- spec accessors ----------------------------------------------------
    def component_spec(self, component: str) -> Optional[Dict[str, Any]]:
        return self.spec.get(component)

    def predictor(self) -> Dict[str, Any]:
        return self.spec.get("predictor") or {}

    def predictor_framework(self) -> str:
        p = self.predictor()
        for fw in PREDICTOR_FRAMEWORKS:
            if fw in p:
                return fw
        if p.get("containers"):
            return "custom"
        return ""

    def predictor_config(self) -> Dict[str, Any]:
        fw = self.predictor_framework()
        if fw == "custom":
            return self.predictor().get("containers", [{}])[0]
        return self.predictor().get(fw) or {}

    def storage_uri(self) -> str:
        return str(self.predictor_config().get("storageUri", ""))

    def canary_traffic_percent(self) -> int:
        return int(self.predictor().get("canaryTrafficPercent", 100))

    def min_replicas(self) -> int:
        return int(self.predictor().get("minReplicas", 1))

    def max_replicas(self) -> int:
        return int(self.predictor().get("maxReplicas", max(1, self.min_replicas())))

    def scale_target_concurrency(self) -> int:
        # Knative KPA-style: target in-flight requests per replica.
        return int(self.predictor().get("scaleTarget", 8))

    def batcher(self) -> Optional[Dict[str, Any]]:
        """Micro-batching config: {maxBatchSize, maxLatencyMs} (KFServing
        batcher annotation equivalent, promoted to a first-class field)."""
        return self.predictor().get("batcher")

    # -- revisions (default / canary) --------------------------------------
    def revision_spec(self, revision: str) -> Optional[Dict[str, Any]]:
        """Predictor-shaped spec for a revision: "default" is
        spec.predictor, "canary" is the optional spec.canary (the
        v1alpha2-era default+canary split)."""
        if revision == "default":
            return self.predictor() or None
        if revision == "canary":
            return self.spec.get("canary") or None
        raise KeyError(f"unknown revision {revision!r}")

    def canary_traffic_percent_split(self) -> int:
        """Percent of traffic routed to the canary revision. Accepted at
        spec level (v1alpha2 shape) or inside predictor; defaults to 0 —
        a new canary takes no traffic until promoted."""
        if self.spec.get("canary") is None:
            return 0
        v = self.spec.get("canaryTrafficPercent",
                          self.predictor().get("canaryTrafficPercent", 0))
        return int(v)

    def rollout_spec(self) -> Optional[Dict[str, Any]]:
        """spec.rollout: the automatic canary rollout controller's
        config — traffic steps up by ``stepPercent`` every
        ``intervalSeconds`` while the canary's windowed SLO
        (``sloP99Ms`` / ``sloErrorRate``) holds, and rolls back to the
        default revision on breach. Requires a canary revision; when
        present the controller owns the traffic percent and
        ``canaryTrafficPercent`` is ignored."""
        return self.spec.get("rollout")

    def scheduling_priority(self) -> int:
        """Chip-arbitration priority of this service's serving
        reservation (sched/scheduler.py): ``spec.schedulingPriority``,
        else the ``kubeflow.org/priority`` annotation, else 5 — above
        default-priority (0) training, so bursty inference preempts
        background work but a priority>=5 training job holds its chips."""
        v = self.spec.get("schedulingPriority")
        if v is None:
            v = self.metadata.annotations.get("kubeflow.org/priority")
        try:
            return int(v) if v is not None else 5
        except (TypeError, ValueError):
            return 5

    def validate(self) -> None:
        super().validate()
        if not self.predictor():
            raise ValidationError("spec.predictor", "required")
        fw = self.predictor_framework()
        if not fw:
            raise ValidationError(
                "spec.predictor",
                f"one of {PREDICTOR_FRAMEWORKS} (or containers) required")
        if fw != "custom" and not self.storage_uri():
            raise ValidationError(f"spec.predictor.{fw}.storageUri", "required")
        if fw == "custom" and not self.predictor_config().get("command"):
            raise ValidationError(
                "spec.predictor.containers[0].command",
                "required for a custom predictor")
        pct = self.canary_traffic_percent()
        if not 0 <= pct <= 100:
            raise ValidationError("spec.predictor.canaryTrafficPercent",
                                  "must be in [0, 100]")
        if self.spec.get("canary") is not None:
            split = self.canary_traffic_percent_split()
            if not 0 <= split <= 100:
                raise ValidationError("spec.canaryTrafficPercent",
                                      "must be in [0, 100]")
        if self.min_replicas() < 0 or self.max_replicas() < self.min_replicas():
            raise ValidationError("spec.predictor.minReplicas/maxReplicas",
                                  "0 <= min <= max required")
        for rev in ("predictor", "canary"):
            rspec = self.spec.get(rev)
            if rspec is None:
                continue
            for field, lo in (("targetConcurrency", 0.0),
                              ("stableWindowSeconds", 0.0),
                              ("scaleDownWindowSeconds", 0.0),
                              ("panicWindowSeconds", 0.0),
                              ("panicThreshold", 1.0),
                              ("maxScaleUpRate", 1.0)):
                v = rspec.get(field)
                if v is None:
                    continue
                try:
                    ok = float(v) > lo and not isinstance(v, bool)
                except (TypeError, ValueError):
                    ok = False
                if not ok:
                    raise ValidationError(f"spec.{rev}.{field}",
                                          f"must be a number > {lo:g}")
            # Drain-before-kill window: >= 0 (0 = kill immediately, the
            # explicit escape hatch), bool-as-number rejected like the
            # autoscaling knobs above.
            dw = rspec.get("drainWindowSeconds")
            if dw is not None:
                try:
                    ok = float(dw) >= 0.0 and not isinstance(dw, bool)
                except (TypeError, ValueError):
                    ok = False
                if not ok:
                    raise ValidationError(
                        f"spec.{rev}.drainWindowSeconds",
                        "must be a number >= 0")
            # Chunked-prefill bound (tokens; the engine rounds up to a
            # whole number of KV pages): integer >= 0, 0 = monolithic
            # prefill. `prefillChunkTokens: true` must be a 400 at
            # apply, not chunk size 1 at revision startup.
            pc = rspec.get("prefillChunkTokens")
            if pc is not None and (isinstance(pc, bool)
                                   or not isinstance(pc, int)
                                   or pc < 0):
                raise ValidationError(
                    f"spec.{rev}.prefillChunkTokens",
                    "must be an integer >= 0 (0 = monolithic prefill)")
            # KV transfer plane (docs/serving.md "KV as a fleet
            # resource"): the replica's disaggregation tier and the
            # host-RAM offload capacity in pages (0 = off).
            role = rspec.get("role")
            if role is not None and role not in ("prefill", "decode",
                                                 "mixed"):
                raise ValidationError(
                    f"spec.{rev}.role",
                    f"{role!r} not one of prefill/decode/mixed")
            op = rspec.get("kvOffloadPages")
            if op is not None and (isinstance(op, bool)
                                   or not isinstance(op, int)
                                   or op < 0):
                raise ValidationError(
                    f"spec.{rev}.kvOffloadPages",
                    "must be an integer >= 0 (0 = no host offload)")
        sp = self.spec.get("schedulingPriority")
        if sp is not None and (isinstance(sp, bool)
                               or not isinstance(sp, int)):
            raise ValidationError("spec.schedulingPriority",
                                  "must be an integer")
        ro = self.rollout_spec()
        if ro is not None:
            if self.spec.get("canary") is None:
                raise ValidationError(
                    "spec.rollout", "requires a spec.canary revision")
            step = ro.get("stepPercent", 10)
            maxp = ro.get("maxPercent", 100)
            if not (isinstance(step, int) and not isinstance(step, bool)
                    and 0 < step <= 100):
                raise ValidationError("spec.rollout.stepPercent",
                                      "must be an integer in [1, 100]")
            if not (isinstance(maxp, int) and not isinstance(maxp, bool)
                    and 0 < maxp <= 100):
                raise ValidationError("spec.rollout.maxPercent",
                                      "must be an integer in [1, 100]")
            for field in ("intervalSeconds", "sloP99Ms", "sloErrorRate",
                          "minRequests"):
                v = ro.get(field)
                if v is None:
                    continue
                try:
                    fv = float(v)
                except (TypeError, ValueError):
                    raise ValidationError(f"spec.rollout.{field}",
                                          "must be a number")
                if fv < 0 or isinstance(v, bool):
                    raise ValidationError(f"spec.rollout.{field}",
                                          "must be >= 0")
            er = ro.get("sloErrorRate")
            if er is not None and float(er) > 1.0:
                raise ValidationError("spec.rollout.sloErrorRate",
                                      "a rate in [0, 1]")
        for rev in ("predictor", "canary"):
            spec = self.spec.get(rev)
            if spec is not None:
                dev = str(spec.get("device", "default"))
                if dev not in ("default", "cpu"):
                    raise ValidationError(
                        f"spec.{rev}.device",
                        f"{dev!r} not one of default/cpu")
                sp = spec.get("speculative")
                if sp is not None:
                    if not isinstance(sp, dict):
                        raise ValidationError(
                            f"spec.{rev}.speculative",
                            "must be an object "
                            "{draftLayers, proposeTokens}")
                    for field in ("draftLayers", "proposeTokens"):
                        v = sp.get(field)
                        if v is None:
                            continue
                        # bool subclasses int: `draftLayers: true` must
                        # be a 400 at apply, not layer count 1 at
                        # revision startup.
                        if isinstance(v, bool) or not isinstance(v, int) \
                                or v < 1:
                            raise ValidationError(
                                f"spec.{rev}.speculative.{field}",
                                "must be an integer >= 1")
                    en = sp.get("enabled")
                    if en is not None and not isinstance(en, bool):
                        raise ValidationError(
                            f"spec.{rev}.speculative.enabled",
                            "must be a boolean")
                ad = spec.get("adapters")
                if ad is not None:
                    if not isinstance(ad, dict):
                        raise ValidationError(
                            f"spec.{rev}.adapters",
                            "must be an object {artifacts, default, "
                            "slots, rank, fallback}")
                    arts = ad.get("artifacts")
                    if not isinstance(arts, dict) or not arts:
                        raise ValidationError(
                            f"spec.{rev}.adapters.artifacts",
                            "must be a non-empty object "
                            "{name: artifact URI}")
                    for aname, uri in arts.items():
                        if not str(aname) or not isinstance(uri, str) \
                                or not uri:
                            raise ValidationError(
                                f"spec.{rev}.adapters."
                                f"artifacts[{aname!r}]",
                                "artifact URI must be a non-empty "
                                "string")
                    dflt = ad.get("default")
                    if dflt is not None and (
                            not isinstance(dflt, str)
                            or (dflt and dflt not in arts)):
                        raise ValidationError(
                            f"spec.{rev}.adapters.default",
                            "must name one of adapters.artifacts "
                            "(or '' for the base model)")
                    # bool subclasses int: `slots: true` must be a 400
                    # at apply, not slot count 1 at revision startup.
                    for field in ("slots", "rank"):
                        v = ad.get(field)
                        if v is not None and (isinstance(v, bool)
                                              or not isinstance(v, int)
                                              or v < 1):
                            raise ValidationError(
                                f"spec.{rev}.adapters.{field}",
                                "must be an integer >= 1")
                    fb = ad.get("fallback")
                    if fb is not None and fb not in ("base", "error"):
                        raise ValidationError(
                            f"spec.{rev}.adapters.fallback",
                            "'base' (degrade to base-only) or "
                            "'error' (503 + Retry-After)")
                md = spec.get("models")
                if md is not None:
                    if not isinstance(md, dict):
                        raise ValidationError(
                            f"spec.{rev}.models",
                            "must be an object {artifacts, default, "
                            "slots, idleSeconds}")
                    arts = md.get("artifacts")
                    if not isinstance(arts, dict) or not arts:
                        raise ValidationError(
                            f"spec.{rev}.models.artifacts",
                            "must be a non-empty object "
                            "{name: LM export URI}")
                    for mname, uri in arts.items():
                        if not str(mname) or not isinstance(uri, str) \
                                or not uri:
                            raise ValidationError(
                                f"spec.{rev}.models."
                                f"artifacts[{mname!r}]",
                                "export URI must be a non-empty "
                                "string")
                    dflt = md.get("default")
                    if not isinstance(dflt, str) or dflt not in arts:
                        raise ValidationError(
                            f"spec.{rev}.models.default",
                            "must name one of models.artifacts (the "
                            "resident model the revision's storageUri "
                            "loads)")
                    # bool subclasses int: `slots: true` must be a 400
                    # at apply, not slot count 1 at revision startup.
                    sl = md.get("slots")
                    if sl is not None and (isinstance(sl, bool)
                                           or not isinstance(sl, int)
                                           or sl < 1):
                        raise ValidationError(
                            f"spec.{rev}.models.slots",
                            "must be an integer >= 1")
                    idle = md.get("idleSeconds")
                    if idle is not None:
                        try:
                            ok = (not isinstance(idle, bool)
                                  and float(idle) >= 0)
                        except (TypeError, ValueError):
                            ok = False
                        if not ok:
                            raise ValidationError(
                                f"spec.{rev}.models.idleSeconds",
                                "must be a number >= 0 (0 = never "
                                "evict on idle)")
                    # A weight pool excludes the per-request planes
                    # that assume ONE set of weights per replica:
                    # adapter factors pair with specific base weights,
                    # and KV pages moved between tiers would decode
                    # under a different model.
                    if ad is not None:
                        raise ValidationError(
                            f"spec.{rev}.models",
                            "incompatible with spec.adapters (LoRA "
                            "factors pair with one base model)")
                    if str(spec.get("role", "mixed")) != "mixed":
                        raise ValidationError(
                            f"spec.{rev}.models",
                            "requires role 'mixed' (KV pages moved "
                            "between tiers would decode under a "
                            "different model's weights)")
                q = spec.get("quantization")
                if q is not None:
                    if not isinstance(q, dict):
                        raise ValidationError(
                            f"spec.{rev}.quantization",
                            "must be an object {weights, kv}")
                    for field in ("weights", "kv"):
                        v = q.get(field)
                        if v is None:
                            continue
                        # `weights: true` (a bool) or `weights: 8`
                        # (an int) must be a 400 at apply, not a
                        # stringified surprise at revision startup.
                        if isinstance(v, bool) or \
                                not isinstance(v, str) or \
                                v not in ("int8", "f32"):
                            raise ValidationError(
                                f"spec.{rev}.quantization.{field}",
                                "must be 'int8' or 'f32'")
                # Request plane (docs/serving.md): per-revision QoS
                # default, admission deadline default, and per-tenant
                # token rate limits.
                qd = spec.get("qosDefault")
                if qd is not None and qd not in ("interactive",
                                                 "batch"):
                    raise ValidationError(
                        f"spec.{rev}.qosDefault",
                        "must be 'interactive' or 'batch'")
                dm = spec.get("deadlineMs")
                if dm is not None:
                    try:
                        ok = float(dm) > 0 and not isinstance(dm, bool)
                    except (TypeError, ValueError):
                        ok = False
                    if not ok:
                        raise ValidationError(
                            f"spec.{rev}.deadlineMs",
                            "must be a number > 0 (milliseconds)")
                rl = spec.get("rateLimits")
                if rl is not None:
                    if not isinstance(rl, dict) or not rl:
                        raise ValidationError(
                            f"spec.{rev}.rateLimits",
                            "must be a non-empty object "
                            "{tenant: tokens per second}")
                    for tenant, rate in rl.items():
                        try:
                            ok = (not isinstance(rate, bool)
                                  and float(rate) > 0)
                        except (TypeError, ValueError):
                            ok = False
                        if not str(tenant) or not ok:
                            raise ValidationError(
                                f"spec.{rev}.rateLimits[{tenant!r}]",
                                "must be a number > 0 "
                                "(tokens per second)")
        tr = self.spec.get("transformer")
        if tr is not None and not tr.get("module"):
            raise ValidationError(
                "spec.transformer.module",
                "required: python file providing preprocess()/postprocess()")
        ex = self.spec.get("explainer")
        if ex is not None:
            method = str(ex.get("method", "occlusion"))
            if method not in EXPLAINER_METHODS:
                raise ValidationError(
                    "spec.explainer.method",
                    f"{method!r} not one of {EXPLAINER_METHODS}")
