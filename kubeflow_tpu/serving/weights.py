"""Many models over one chip pool: an HBM weight pool with refcounted
LRU paging — scale-from-zero as a measured weight SWAP, not a process
spawn (ServerlessLLM, OSDI'24-shaped; S-LoRA's slot multiplexing
generalized from LoRA factors to whole checkpoints).

The paper's "millions of users" means a heavy tail of models, most of
them cold, and today every isvc revision pays a full replica process
for its weights. ``WeightPool`` lets ONE ``LMPredictor`` process host
several small models time-sharing the chips:

  * one HBM slot per resident model, each holding a full versioned
    export (serving/lm_server.py ``load_lm`` — v1 f32, v2 int8 and
    load-time-quantized artifacts all admissible; every loaded tree is
    normalized to the POOL's precision so the one compiled executable
    fits them all),
  * BlockManager-style host bookkeeping exactly like ``AdapterPool``
    (free list, per-slot refcounts, name->slot map, LRU order): a model
    pages in on first use, is pinned while requests wear it, and is
    evicted LRU when the pool wants room — eviction of an idle model's
    slot IS the new scale-to-zero,
  * per-request model selection rides the engine's existing dispatch:
    the compiled decode/prefill functions take ``params`` as a traced
    ARGUMENT, so same-shaped models share one AOT executable with zero
    recompiles — a swap is one ``device_put``, and dispatch groups
    batch rows by weight slot (serving/engine.py ``_decode_once``).

Storage note: the ISSUE sketch says "``[n_slots, ...]`` per-tensor
stacks" by analogy with the adapter pool, but full checkpoints are
multi-MB-to-GB trees — literally stacking them would copy the WHOLE
pool on every swap (``stack.at[slot].set`` rebuilds the stacked
buffer) and gain nothing at dispatch (a whole batch group wears one
model; there is no per-row gather inside the matmul). The pool
therefore keeps a list of per-slot device trees: swap = one
``device_put`` of that model's tree, dispatch = passing the slot's
tree by reference. HBM cost is identical; churn cost is one model, not
n_slots.

Slot lifecycle (docs/serving.md "Weights as a fleet resource"):

    free ──acquire(miss)──> loaded+pinned ──release──> loaded+idle
      ^                                                    │
      └──────── evict (LRU / idle sweep / operator) ───────┘

Eviction is refcount-aware against BOTH in-flight requests (ref>0
slots are never victims — a pinned pool raises ``WeightSlotError``,
which requeues like KV-page pressure) and the prefix cache: every load
gets a fresh GENERATION, the engine roots that model's prefix chains
at ``name@generation``, and eviction fires ``on_evict`` so the engine
drops the chains — a stale prefix hit can never pair with freshly
swapped-in weights, even for the same model name reloaded into the
same slot.

Every swap-in is measured where the activator's cold path used to be:
the ``kfx_lm_weight_swap_seconds`` histogram, an
``autoscale.cold_start`` span and a
``kfx_autoscaler_cold_start_seconds{mode="swap"}`` observation — the
central scraper stamps namespace/isvc/revision, so swap cold starts
land on the SAME fleet histogram as the operator's ``mode="spawn"``
process respawns, and swap against respawn is one query. The
``weights.load`` chaos point (docs/chaos.md) injects a delayed/failed
artifact read during the swap.

jax imports stay inside methods — the model server imports this module
on its error-classification path (via engine) before any device exists.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import chaos
from ..obs import trace as obs_trace
from .engine import WeightLoadError, WeightSlotError

# Help strings shared with the operator's spawn-path observations —
# ONE family, one doc row, two `mode` label values.
COLD_START_DOC = ("Scale-from-zero latency: cold request to first "
                  "ready replica.")
SWAP_DOC = ("Weight swap-in latency: artifact load + quant "
            "normalization + device transfer into an HBM slot.")


def _tree_leaves_with_path(tree, prefix=""):
    """(path, leaf) pairs in deterministic key order — msgpack trees
    are plain nested dicts, so no jax import is needed to walk them."""
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(_tree_leaves_with_path(tree[k], f"{prefix}/{k}"))
    else:
        out.append((prefix, tree))
    return out


class WeightPool:
    """HBM weight slots over one engine: per-slot device param trees
    plus BlockManager-style host bookkeeping (free list, per-slot
    refcounts, name->slot map, LRU order, per-load generations) and
    lazy paging from the versioned artifact store (``sources``:
    name -> LM export dir).

    All mutation happens on the engine's decode-loop thread (same
    single-writer discipline as the KV and adapter pools)."""

    def __init__(self, cfg, template, n_slots: int,
                 sources: Dict[str, str], name: str = "model",
                 registry=None,
                 on_evict: Optional[Callable[[str, bytes], None]] = None):
        if n_slots < 1:
            raise ValueError("weight_slots must be >= 1")
        if not sources:
            raise ValueError("model sources must be a non-empty "
                             "{name: LM export dir} map")
        self.cfg = cfg                    # pool config (fixes precision)
        self.name = name                  # engine/metrics identity
        self.n_slots = int(n_slots)
        self.sources = {str(k): str(v) for k, v in sources.items()}
        self._registry = registry
        self.on_evict = on_evict
        # The executable-sharing contract: every pooled tree must match
        # the engine's resident params leaf-for-leaf (structure, shape,
        # dtype) — the compiled functions were traced against exactly
        # this signature.
        self._sig = [(p, tuple(x.shape), np.dtype(x.dtype))
                     for p, x in _tree_leaves_with_path(template)]
        # -- slot state (decode-loop thread only)
        self._trees: List[Optional[Any]] = [None] * self.n_slots
        self._free: List[int] = list(range(self.n_slots - 1, -1, -1))
        self._by_name: Dict[str, int] = {}
        self._names: List[str] = [""] * self.n_slots
        self._gens: List[int] = [0] * self.n_slots
        self._last_used: List[float] = [0.0] * self.n_slots
        self.ref = np.zeros((self.n_slots,), np.int32)
        # Permanent residency, orthogonal to the request refcount: the
        # engine pins its adopted default model (the tree self.params
        # aliases — the compile template) so neither LRU pressure, the
        # idle sweep nor a donated-death release_all() can evict it.
        self.pinned = np.zeros((self.n_slots,), np.bool_)
        self._lru: "OrderedDict[str, int]" = OrderedDict()
        self._gen_seq = 0
        self.loads = 0
        self.evictions = 0

    # -- metrics -------------------------------------------------------------
    def _reg(self):
        return self._registry() if callable(self._registry) else \
            self._registry

    def _count_eviction(self, reason: str) -> None:
        reg = self._reg()
        if reg is not None:
            reg.counter(
                "kfx_lm_weight_evictions_total",
                "Model weights evicted from HBM pool slots "
                "(LRU pressure, idle scale-to-zero, operator evict).",
            ).inc(1, model=self.name, reason=reason)

    def touch(self) -> None:
        """Seed/refresh every weight-pool metrics family (called from
        the engine's ``_touch_gauges``): slot-capacity gauges for `kfx
        top`'s MODELS column, zero-seeded load/eviction counters and
        swap histogram so a pre-swap ``scrape_metrics --require``
        already sees the families, and the per-model residency gauges
        the operator folds into ``status.pooledModels`` ("pooled but
        unloaded" is an explicit 0, never an absent series)."""
        reg = self._reg()
        if reg is None:
            return
        reg.gauge("kfx_lm_weight_slots",
                  "HBM weight slots (full-checkpoint capacity of the "
                  "multi-model pool).").set(self.n_slots,
                                            model=self.name)
        reg.gauge("kfx_lm_weight_slots_free",
                  "Weight slots not worn by in-flight requests (free "
                  "+ loaded-but-idle LRU candidates; pinned slots "
                  "excluded).").set(self.n_free, model=self.name)
        reg.gauge("kfx_lm_weight_models_loaded",
                  "Models resident in the HBM weight pool.").set(
                      len(self._by_name), model=self.name)
        reg.counter("kfx_lm_weight_loads_total",
                    "Model weights paged into HBM pool slots from the "
                    "artifact store.").inc(0, model=self.name)
        for reason in ("lru", "idle", "explicit"):
            reg.counter(
                "kfx_lm_weight_evictions_total",
                "Model weights evicted from HBM pool slots "
                "(LRU pressure, idle scale-to-zero, operator evict).",
            ).inc(0, model=self.name, reason=reason)
        reg.histogram("kfx_lm_weight_swap_seconds", SWAP_DOC).observe(
            0.0, n=0, model=self.name)
        for m in sorted(self.sources):
            reg.gauge(
                "kfx_lm_weight_model_loaded",
                "Per-model pool residency (1 = weights in an HBM "
                "slot, 0 = pooled but unloaded).").set(
                    1 if m in self._by_name else 0,
                    model=self.name, pooled=m)

    # -- read accessors ------------------------------------------------------
    @property
    def n_free(self) -> int:
        """Slots not holding a LIVE model reference: free-list slots
        plus loaded-but-idle (ref 0) LRU candidates — the headroom the
        ``kfx_lm_weight_slots_free`` gauge reports. Pinned slots are
        never headroom — they cannot be evicted."""
        return len(self._free) + sum(
            1 for s in self._by_name.values()
            if self.ref[s] == 0 and not self.pinned[s])

    def known(self, name: str) -> bool:
        return name in self.sources

    def loaded(self) -> List[str]:
        return sorted(self._by_name)

    def tree(self, slot: int):
        """The slot's device param tree (dispatch passes it by
        reference into the shared compiled functions)."""
        return self._trees[slot]

    def model_name(self, slot: int) -> str:
        return self._names[slot]

    def root(self, slot: int) -> bytes:
        """Prefix-cache chain root for the slot's CURRENT occupant:
        ``name@generation``. A reload (even of the same model into the
        same slot) gets a fresh generation, so chains built against
        evicted weights can never match again."""
        return f"{self._names[slot]}@{self._gens[slot]}".encode()

    def nbytes(self) -> int:
        """Device bytes of every resident tree — the HBM cost of
        hosting the pool, the number ``engine.hbm_bytes()["weights"]``
        reads."""
        total = 0
        for t in self._trees:
            if t is None:
                continue
            for _, x in _tree_leaves_with_path(t):
                total += int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        return total

    # -- slot lifecycle ------------------------------------------------------
    def adopt(self, name: str, params, pin: bool = False) -> int:
        """Install an ALREADY-LOADED device tree into a slot (the
        engine's constructor params — the default model is resident
        from boot, its artifact never re-read). ``pin=True`` marks the
        slot permanently resident (never an eviction victim); the
        request refcount starts at 0 either way, so the first request
        acquires it like any warm hit."""
        if name in self._by_name:
            raise ValueError(f"model {name!r} already pooled")
        if not self._free:
            raise ValueError("no free weight slot to adopt into")
        slot = self._free.pop()
        self._gen_seq += 1
        self._trees[slot] = params
        self._by_name[name] = slot
        self._names[slot] = name
        self._gens[slot] = self._gen_seq
        self._lru[name] = slot
        self._last_used[slot] = time.monotonic()
        self.ref[slot] = 0
        self.pinned[slot] = bool(pin)
        return slot

    def acquire(self, name: str) -> int:
        """Resolve ``name`` to a pinned slot id, paging the artifact in
        on a miss. Raises WeightSlotError (retriable pool pressure:
        every slot is pinned by an in-flight request — requeues like
        KV-page exhaustion) or WeightLoadError (the artifact itself
        failed to load, incl. the ``weights.load`` chaos point — 503 +
        Retry-After; wrong weights are never a degrade option)."""
        slot = self._by_name.get(name)
        if slot is not None:
            self._lru.move_to_end(name)
            self.ref[slot] += 1
            self._last_used[slot] = time.monotonic()
            return slot
        if name not in self.sources:
            raise WeightLoadError(f"unknown model {name!r}")
        if self._free:
            slot = self._free.pop()
        else:
            slot = self._evict_one()
            if slot is None:
                raise WeightSlotError(
                    f"all {self.n_slots} weight slots pinned by "
                    "in-flight requests")
        try:
            self._load_into(name, slot)
        except WeightLoadError:
            self._free.append(slot)
            raise
        self._by_name[name] = slot
        self._names[slot] = name
        self._lru[name] = slot
        self._last_used[slot] = time.monotonic()
        self.ref[slot] = 1
        return slot

    def release(self, slot: int) -> None:
        assert self.ref[slot] > 0, f"release of unpinned slot {slot}"
        self.ref[slot] -= 1
        self._last_used[slot] = time.monotonic()

    def release_all(self) -> None:
        """Drop every in-flight pin (the engine's donated-dispatch
        death path: all requests failed, nothing wears a slot).
        Loaded models stay resident — slot trees are never donated."""
        self.ref[:] = 0

    # -- eviction (scale-to-zero) --------------------------------------------
    def _drop_slot(self, name: str, slot: int, reason: str) -> None:
        root = self.root(slot)
        del self._lru[name]
        del self._by_name[name]
        self._names[slot] = ""
        self._trees[slot] = None          # frees the device buffers
        self.evictions += 1
        self._count_eviction(reason)
        if self.on_evict is not None:
            # Prefix-safety ordering: the engine invalidates this
            # model's prefix chains BEFORE the slot can be refilled —
            # a stale hit can never pair with swapped-in weights.
            self.on_evict(name, root)

    def _evict_one(self) -> Optional[int]:
        for name in list(self._lru):
            slot = self._lru[name]
            if self.ref[slot] == 0 and not self.pinned[slot]:
                self._drop_slot(name, slot, "lru")
                return slot
        return None

    def evict_model(self, name: str) -> bool:
        """Explicit eviction (the operator's scale-to-zero push or a
        drain). Refuses while worn by in-flight requests (they finish
        on the weights they admitted with) or permanently pinned (the
        engine's resident default)."""
        slot = self._by_name.get(name)
        if slot is None or self.ref[slot] > 0 or self.pinned[slot]:
            return False
        self._drop_slot(name, slot, "explicit")
        self._free.append(slot)
        return True

    def evict_idle(self, idle_s: float,
                   keep: str = "") -> List[str]:
        """The replica-side scale-to-zero sweep: evict every ref-0
        model idle longer than ``idle_s`` (except ``keep`` — the
        default model stays warm like minReplicas=1). Returns the
        evicted names."""
        if idle_s <= 0:
            return []
        now = time.monotonic()
        out = []
        for name in list(self._lru):
            slot = self._lru[name]
            if name == keep or self.ref[slot] > 0 \
                    or self.pinned[slot]:
                continue
            if now - self._last_used[slot] >= idle_s:
                self._drop_slot(name, slot, "idle")
                self._free.append(slot)
                out.append(name)
        return out

    @staticmethod
    def _cache_dir() -> str:
        """Download cache for remote artifact schemes (gs/s3/http —
        file:// and bare paths never touch it). The replica process has
        no operator home, so the cache lives under the system tempdir
        unless KFX_LM_STORAGE_CACHE pins it."""
        import os
        import tempfile

        return os.environ.get("KFX_LM_STORAGE_CACHE") or os.path.join(
            tempfile.gettempdir(), "kfx-weight-cache")

    # -- the swap (cold path) ------------------------------------------------
    def _load_into(self, name: str, slot: int) -> None:
        """Page one model's export into ``slot``: artifact load, quant
        normalization to the pool precision, signature validation
        against the engine's resident params, device transfer. Runs on
        the decode-loop thread like a prefill compile; the whole swap
        is timed as the replica-side cold start."""
        t0 = time.perf_counter()
        ts = time.time()
        inj = chaos.draw("weights.load", target=f"{self.name}/{name}")
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                raise WeightLoadError(f"chaos[weights.load]: {name}")
        import jax

        from .lm_server import load_lm
        from .storage import initialize

        try:
            # Same storage-initializer path the revision's own
            # storageUri went through, but LAZY: a pooled model's
            # artifact is fetched at first swap-in, not at replica
            # spawn — the heavy tail of cold models costs nothing
            # until someone asks for one.
            path = initialize(self.sources[name], self._cache_dir())
            cfg, params = load_lm(path)
        except WeightLoadError:
            raise
        except Exception as e:
            raise WeightLoadError(
                f"model {name!r} failed to load from "
                f"{self.sources[name]}: {e}") from e
        params = self._normalize(name, cfg, params)
        self._validate(name, params)
        self._gen_seq += 1
        self._gens[slot] = self._gen_seq
        self._trees[slot] = jax.device_put(params)
        jax.block_until_ready(
            jax.tree_util.tree_leaves(self._trees[slot]))
        self.loads += 1
        dt = time.perf_counter() - t0
        reg = self._reg()
        if reg is not None:
            reg.counter(
                "kfx_lm_weight_loads_total",
                "Model weights paged into HBM pool slots from the "
                "artifact store.").inc(1, model=self.name)
            reg.histogram("kfx_lm_weight_swap_seconds",
                          SWAP_DOC).observe(dt, model=self.name)
            # The headline comparison rides the fleet's OWN cold-start
            # histogram: the central scraper stamps namespace/isvc/
            # revision onto this replica-exported series, landing
            # mode="swap" beside the operator's mode="spawn".
            reg.histogram("kfx_autoscaler_cold_start_seconds",
                          COLD_START_DOC).observe(
                dt, mode="swap", model=self.name)
        obs_trace.record_span("autoscale.cold_start", ts=ts,
                              duration=dt, mode="swap",
                              model=self.name, pooled=name)

    def _normalize(self, name: str, cfg, params):
        """Bring a loaded export to the POOL's precision. The pool has
        ONE precision (cfg.quant) because every slot feeds the same
        compiled executable: an int8 pool quantizes f32 exports at
        load (same per-channel scheme as a quantized export), an f32
        pool expands int8 exports back to dense kernels."""
        want = self.cfg.quant or ""
        got = cfg.quant or ""
        if want == got:
            return params
        if want == "int8":
            from ..models.transformer import quantize_params_int8

            return quantize_params_int8(params)
        from ..models.transformer import dequantize_params_int8

        return dequantize_params_int8(params)

    def _validate(self, name: str, params) -> None:
        got = [(p, tuple(np.shape(x)), np.dtype(
            np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype))
            for p, x in _tree_leaves_with_path(params)]
        if len(got) != len(self._sig):
            raise WeightLoadError(
                f"model {name!r} tree has {len(got)} leaves, pool "
                f"signature has {len(self._sig)} — pooled models must "
                "share the engine's architecture")
        for (gp, gs, gd), (wp, ws, wd) in zip(got, self._sig):
            if gp != wp or gs != ws or gd != wd:
                raise WeightLoadError(
                    f"model {name!r} leaf {gp} ({gs}, {gd}) does not "
                    f"match pool signature {wp} ({ws}, {wd}) — one "
                    "compiled executable serves every slot, so pooled "
                    "models must be shape- and dtype-identical")
