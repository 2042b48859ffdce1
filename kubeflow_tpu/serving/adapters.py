"""Multi-tenant LoRA adapter serving: MANY fine-tunes over ONE base.

"Millions of users" in practice means thousands of cheap fine-tunes of
one base model, not thousands of base deployments. A LoRA fine-tune
(training/lora.py) is a set of rank-r A/B factor pairs on the attention
q/k/v/out and dense-MLP wi/wo projections — a few hundred KB against a
multi-GB base — exported as a small versioned artifact
(serving/export.py ``export_adapter``). This module is the SERVING half
(S-LoRA, Sheng et al. 2023; Punica, Chen et al. MLSys'24):

  * ``AdapterPool`` — an HBM-resident ``[n_layers, n_adapter_slots,
    ...]`` A/B stack per target projection, managed by a
    BlockManager-style allocator (free list + per-slot refcounts + LRU
    paging from the artifact store): an adapter is paged into a slot on
    first use, pinned while requests wear it, and evicted LRU when the
    slot pool wants room — exactly how the engine's KV pages already
    move. The per-adapter ``alpha/rank`` scale is folded into the B
    stack at load time and shorter ranks zero-pad to the pool rank, so
    one stack shape serves heterogeneous artifacts.
  * batched-gather application lives in the MODEL
    (models/transformer.py ``lora_gather_delta``): per-request adapter
    ids ride the existing fused decode/verify dispatch as a [B] int32
    argument, every batch row gathers its own A/B rows, and id -1
    masks the delta to exactly zero — ONE compiled function serves a
    batch where every slot wears a different adapter, and a base-only
    row's output is bit-identical to an adapterless engine's.
  * ``FairQueue`` — per-tenant (per-adapter) admission queues popped
    weighted-round-robin, so one adapter's burst queues behind ITSELF,
    not in front of everyone else: the minority tenant's queue wait
    stays bounded under a majority burst (the tier-1 fairness test).

The engine (serving/engine.py) owns integration: slot lifecycle,
page-pool interaction, the ``engine.adapter_load`` chaos point and the
``kfx_lm_adapter_*`` metric families. docs/serving.md has the
sizing/HBM math.

jax imports stay inside methods — the model server imports this module
on its error-classification path (via engine) before any device exists.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import chaos
from .engine import AdapterLoadError, AdapterSlotError

# Target projections (path suffix under the scanned layer stack) and
# their (d_in, d_out) dims as functions of the config — THE table both
# the pool stacks and the artifact validation are built from. lm_head
# and the embedding are not LoRA targets (gathers / the output head are
# not where fine-tunes live in the S-LoRA recipe); MoE experts are
# excluded at config validation (models/transformer.py).
LORA_TARGETS = ("attn.query", "attn.key", "attn.value", "attn.out",
                "mlp.wi", "mlp.wo")


def lora_target_dims(cfg) -> Dict[str, Tuple[int, int]]:
    """target -> (d_in, d_out) for one TransformerConfig."""
    q = cfg.n_heads * cfg.head_dim
    return {
        "attn.query": (cfg.d_model, q),
        "attn.key": (cfg.d_model, q),
        "attn.value": (cfg.d_model, q),
        "attn.out": (q, cfg.d_model),
        "mlp.wi": (cfg.d_model, 2 * cfg.d_ff),
        "mlp.wo": (cfg.d_ff, cfg.d_model),
    }


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{"attn.query": leaf} -> {"attn": {"query": leaf}} — the nested
    form Block/Attention/DenseFFN consume as the ``lora`` call arg."""
    out: Dict[str, Any] = {}
    for key, leaf in flat.items():
        mod, _, name = key.partition(".")
        out.setdefault(mod, {})[name] = leaf
    return out


def extract_lora(params) -> Dict[str, Dict[str, Any]]:
    """Pluck the train-time LoRA factors out of a (full or LoRA-only)
    param tree: ``layers/attn/query_lora_a`` [L, d_in, r] etc. become
    ``{"attn.query": {"a": ..., "b": ...}, ...}`` — the flat artifact
    form export_adapter writes and AdapterPool loads. Missing targets
    are simply absent (an adapter may touch a subset)."""
    layers = params.get("layers", params) if isinstance(params, dict) \
        else {}
    out: Dict[str, Dict[str, Any]] = {}
    for mod in ("attn", "mlp"):
        node = layers.get(mod)
        if not isinstance(node, dict):
            continue
        for k, v in node.items():
            for suffix, leaf in (("_lora_a", "a"), ("_lora_b", "b")):
                if k.endswith(suffix):
                    out.setdefault(f"{mod}.{k[:-len(suffix)]}", {})[
                        leaf] = v
    return out


def split_lora_tree(params) -> Tuple[Any, Any]:
    """(base, lora) split of a param tree by leaf name: every
    ``*_lora_a``/``*_lora_b`` leaf goes to the lora side (structure
    preserved, empty dicts pruned), everything else to the base."""
    def walk(node):
        if not isinstance(node, dict):
            return node, None
        base, lora = {}, {}
        for k, v in node.items():
            if not isinstance(v, dict) and (
                    k.endswith("_lora_a") or k.endswith("_lora_b")):
                lora[k] = v
                continue
            b, lo = walk(v)
            if not isinstance(v, dict) or (isinstance(b, dict) and b) \
                    or not isinstance(b, dict):
                base[k] = b
            if lo:
                lora[k] = lo
        return base, lora

    return walk(params)


def graft_lora(base, lora):
    """Deep-merge a LoRA leaf tree back into a base param tree — the
    apply-side inverse of ``split_lora_tree`` (the fine-tuner trains
    the small tree and grafts per step; the base is never copied)."""
    if not isinstance(lora, dict):
        return lora
    out = dict(base) if isinstance(base, dict) else {}
    for k, v in lora.items():
        out[k] = graft_lora(out.get(k, {}), v)
    return out


def merge_lora_params(base_params, lora_flat: Dict[str, Dict[str, Any]],
                      rank: int, alpha: float):
    """The DENSE merged-weights oracle: fold ``scale·A·B`` into each
    target kernel (``W' = W + (alpha/rank)·A@B``, f32) and return a
    plain base-shaped tree — what a one-off merged fine-tune deployment
    would serve, and the parity reference the engine's batched-gather
    path is tested against. The input trees are not mutated."""
    import jax.numpy as jnp

    scale = alpha / max(rank, 1)
    out = {k: v for k, v in base_params.items()}
    layers = dict(out["layers"])
    for target, pair in lora_flat.items():
        mod, _, name = target.partition(".")
        node = dict(layers[mod])
        proj = dict(node[name])
        kernel = jnp.asarray(proj["kernel"])
        a = jnp.asarray(pair["a"], jnp.float32)  # [L, d_in, r]
        b = jnp.asarray(pair["b"], jnp.float32)  # [L, r, d_out]
        L, d_in = a.shape[0], a.shape[1]
        d_out = b.shape[2]
        delta = jnp.einsum("ldr,lro->ldo", a, b) * scale
        flat = kernel.astype(jnp.float32).reshape(L, d_in, d_out)
        proj["kernel"] = (flat + delta).reshape(kernel.shape).astype(
            kernel.dtype)
        node[name] = proj
        layers[mod] = node
    out["layers"] = layers
    return out


def random_lora_flat(cfg, rank: int, seed: int = 0,
                     std: float = 0.02) -> Dict[str, Dict[str, Any]]:
    """A synthetic full-target adapter (both factors random normal, so
    it actually changes the model — a fresh fine-tune's B is zero and
    would be invisible): tests use these where a real
    fine-tune would be wasted compile time."""
    rng = np.random.default_rng(seed)
    L = cfg.n_layers
    out = {}
    for target, (d_in, d_out) in lora_target_dims(cfg).items():
        out[target] = {
            "a": rng.normal(0.0, std, (L, d_in, rank)).astype(
                np.float32),
            "b": rng.normal(0.0, std, (L, rank, d_out)).astype(
                np.float32),
        }
    return out


class _WRRBand:
    """One weighted-round-robin rotation over per-tenant FIFO queues
    (the FairQueue building block; a FairQueue holds one band per QoS
    class). The rotation serves up to ``weights[tenant]`` (default 1)
    requests per visit before moving on, so a trickling tenant's next
    request is at most one rotation away instead of behind another
    tenant's whole burst."""

    def __init__(self, weights: Dict[str, int]):
        self._qs: "OrderedDict[str, deque]" = OrderedDict()
        self._weights = weights
        self._rr: deque = deque()   # tenant rotation
        self._credit = 0

    def push(self, req) -> None:
        tenant = getattr(req, "adapter", "") or ""
        q = self._qs.get(tenant)
        if q is None:
            q = self._qs[tenant] = deque()
            self._rr.append(tenant)
        q.append(req)

    def pop(self):
        for _ in range(len(self._rr)):
            tenant = self._rr[0]
            q = self._qs.get(tenant)
            if not q:
                self._rr.rotate(-1)
                self._credit = 0
                continue
            if self._credit <= 0:
                self._credit = max(1, int(self._weights.get(tenant, 1)))
            self._credit -= 1
            req = q.popleft()
            if self._credit <= 0 or not q:
                self._rr.rotate(-1)
                self._credit = 0
            return req
        return None

    def shed_newest(self):
        """Remove and return the NEWEST queued request (None when
        empty): sheds cost the least-progressed work, so the oldest
        queued requests keep their place."""
        victim, vq = None, None
        for q in self._qs.values():
            if q and (victim is None
                      or q[-1].t_enqueue > victim.t_enqueue):
                victim, vq = q[-1], q
        if vq is not None:
            vq.pop()
        return victim

    def drain(self) -> List[Any]:
        out: List[Any] = []
        for q in self._qs.values():
            out.extend(q)
            q.clear()
        self._credit = 0
        return out


class FairQueue:
    """Per-tenant FIFO queues with weighted round-robin pop, split
    into QoS class bands. The tenant key is the request's adapter
    name ("" = base traffic); the band is the request's ``qos`` class.
    Pop order: the ``push_front`` recompute-continuation lane (preempt
    requeues — absolute priority, preserving the engine's oldest-first
    progress guarantee), then the ``interactive`` band's WRR rotation,
    then ``batch`` — a batch flood queues strictly behind interactive
    traffic, and ``shed_batch`` makes batch the first class shed under
    pool pressure. Not thread-safe — the engine serializes access
    under its condition lock, exactly as it did the plain deque."""

    def __init__(self, weights: Optional[Dict[str, int]] = None):
        self._weights = dict(weights or {})
        self._front: deque = deque()
        self._bands = {"interactive": _WRRBand(self._weights),
                       "batch": _WRRBand(self._weights)}
        self._len = 0

    def __len__(self) -> int:
        return self._len

    def push(self, req) -> None:
        cls = getattr(req, "qos", "") or "interactive"
        self._bands.get(cls, self._bands["interactive"]).push(req)
        self._len += 1

    def push_front(self, req) -> None:
        self._front.appendleft(req)
        self._len += 1

    def pop(self):
        """Next request (None when empty): front lane, then
        interactive WRR, then batch WRR."""
        if self._front:
            self._len -= 1
            return self._front.popleft()
        for cls in ("interactive", "batch"):
            req = self._bands[cls].pop()
            if req is not None:
                self._len -= 1
                return req
        return None

    def shed_batch(self, n: int) -> List[Any]:
        """Remove up to ``n`` queued BATCH-class requests (newest
        first) to make room under queue pressure; the caller fails
        them with the shed-load contract. Never touches interactive
        requests or the recompute front lane."""
        out = []
        while len(out) < n:
            victim = self._bands["batch"].shed_newest()
            if victim is None:
                break
            self._len -= 1
            out.append(victim)
        return out

    def drain_all(self) -> List[Any]:
        """Every queued request (front lane first), clearing the
        queue — the drain()/close() bulk-fail path."""
        out = list(self._front)
        self._front.clear()
        out.extend(self._bands["interactive"].drain())
        out.extend(self._bands["batch"].drain())
        self._len = 0
        return out


class AdapterPool:
    """HBM-resident adapter slots over one base model: per-target
    stacked A/B device buffers (``tree`` — the nested ``lora`` call
    arg, leaves ``[n_layers, n_slots, ...]``) plus BlockManager-style
    host bookkeeping (free list, per-slot refcounts, name->slot map,
    LRU order) and lazy paging from the artifact store (``sources``:
    name -> artifact URI). Speculative engines get ``draft_tree`` — the
    same adapters truncated to the draft's layer count, maintained at
    load time so the fused step never slices per dispatch.

    All mutation happens on the engine's decode-loop thread (same
    single-writer discipline as the KV pool)."""

    def __init__(self, cfg, n_slots: int, sources: Dict[str, str],
                 rank: int = 0, draft_layers: int = 0,
                 name: str = "model", registry=None):
        import jax.numpy as jnp

        if n_slots < 1:
            raise ValueError("adapter_slots must be >= 1")
        if not sources:
            raise ValueError("adapter sources must be a non-empty "
                             "{name: artifact URI} map")
        self.cfg = cfg
        self.name = name
        self.n_slots = int(n_slots)
        self.sources = {str(k): str(v) for k, v in sources.items()}
        self._registry = registry
        if rank <= 0:
            # Auto-rank: the pool's stack rank is the max declared by
            # the configured artifacts (cheap config.json peeks — a
            # misconfigured URI should fail revision startup loudly,
            # not the first request that needs it).
            from .export import peek_adapter_rank

            rank = max(peek_adapter_rank(uri)
                       for uri in self.sources.values())
        self.rank = int(rank)
        L = cfg.n_layers
        self.draft_layers = int(draft_layers)
        flat = {}
        dflat = {}
        for target, (d_in, d_out) in lora_target_dims(cfg).items():
            flat[target] = {
                "a": jnp.zeros((L, self.n_slots, d_in, self.rank),
                               jnp.float32),
                "b": jnp.zeros((L, self.n_slots, self.rank, d_out),
                               jnp.float32),
            }
            if self.draft_layers:
                dflat[target] = {
                    "a": jnp.zeros((self.draft_layers, self.n_slots,
                                    d_in, self.rank), jnp.float32),
                    "b": jnp.zeros((self.draft_layers, self.n_slots,
                                    self.rank, d_out), jnp.float32),
                }
        self.tree = _nest(flat)
        self.draft_tree = _nest(dflat) if self.draft_layers else {}
        # -- host bookkeeping (decode-loop thread only)
        self._free: List[int] = list(range(self.n_slots - 1, -1, -1))
        self._by_name: Dict[str, int] = {}
        self._names: List[str] = [""] * self.n_slots
        self.ref = np.zeros((self.n_slots,), np.int32)
        self._lru: "OrderedDict[str, int]" = OrderedDict()
        self.loads = 0
        self.evictions = 0

    # -- metrics -------------------------------------------------------------
    def _count(self, family: str, doc: str) -> None:
        reg = self._registry() if callable(self._registry) else \
            self._registry
        if reg is not None:
            reg.counter(family, doc).inc(1, model=self.name)

    @property
    def n_free(self) -> int:
        """Slots not holding a LIVE adapter reference: free-list slots
        plus loaded-but-idle (ref 0) LRU candidates — the headroom the
        ``kfx_lm_adapter_slots_free`` gauge reports."""
        return len(self._free) + sum(
            1 for s in self._by_name.values() if self.ref[s] == 0)

    def known(self, name: str) -> bool:
        return name in self.sources

    def loaded(self) -> List[str]:
        return sorted(self._by_name)

    # -- slot lifecycle ------------------------------------------------------
    def acquire(self, name: str) -> int:
        """Resolve ``name`` to a pinned slot id, paging the artifact in
        on a miss. Raises AdapterSlotError (a retriable pool-pressure
        overload: every slot is pinned by an in-flight request) or
        AdapterLoadError (the artifact itself failed to load, incl. the
        ``engine.adapter_load`` chaos point — the engine applies its
        fallback knob)."""
        slot = self._by_name.get(name)
        if slot is not None:
            self._lru.move_to_end(name)
            self.ref[slot] += 1
            return slot
        if name not in self.sources:
            raise AdapterLoadError(f"unknown adapter {name!r}")
        if self._free:
            slot = self._free.pop()
        else:
            slot = self._evict_one()
            if slot is None:
                raise AdapterSlotError(
                    f"all {self.n_slots} adapter slots pinned by "
                    "in-flight requests")
        try:
            self._load_into(name, slot)
        except AdapterLoadError:
            self._free.append(slot)
            raise
        self._by_name[name] = slot
        self._names[slot] = name
        self._lru[name] = slot
        self.ref[slot] = 1
        return slot

    def release(self, slot: int) -> None:
        assert self.ref[slot] > 0, f"release of unpinned slot {slot}"
        self.ref[slot] -= 1

    def release_all(self) -> None:
        """Drop every in-flight pin (the engine's donated-dispatch
        death path: all requests failed, nothing wears a slot).
        Loaded adapters stay resident — the stacks are never donated,
        so their content is intact."""
        self.ref[:] = 0

    def _evict_one(self) -> Optional[int]:
        for name in list(self._lru):
            slot = self._lru[name]
            if self.ref[slot] == 0:
                del self._lru[name]
                del self._by_name[name]
                self._names[slot] = ""
                self.evictions += 1
                self._count(
                    "kfx_lm_adapter_evictions_total",
                    "Adapters evicted from HBM slots (LRU paging).")
                return slot
        return None

    def _load_into(self, name: str, slot: int) -> None:
        """Page one artifact into ``slot``: load + validate the flat
        A/B tree, fold alpha/rank into B, zero-pad rank, and scatter
        into the device stacks (and the truncated draft stacks). Cold
        path — runs on the decode-loop thread like a prefill compile,
        bounded by artifact size (a few hundred KB/adapter)."""
        inj = chaos.draw("engine.adapter_load",
                         target=f"{self.name}/{name}")
        if inj is not None:
            if inj.delay > 0:
                import time as _time

                _time.sleep(inj.delay)
            if inj.mode != "delay":
                raise AdapterLoadError(
                    f"chaos[engine.adapter_load]: {name}")
        from .export import load_adapter

        try:
            meta, flat = load_adapter(self.sources[name])
        except AdapterLoadError:
            raise
        except Exception as e:
            raise AdapterLoadError(
                f"adapter {name!r} failed to load from "
                f"{self.sources[name]}: {e}") from e
        rank = int(meta.get("rank", 0))
        alpha = float(meta.get("alpha", rank))
        if rank < 1 or rank > self.rank:
            raise AdapterLoadError(
                f"adapter {name!r} rank {rank} not in [1, {self.rank}] "
                "(the pool's stack rank — set adapters.rank or "
                "re-export)")
        dims = lora_target_dims(self.cfg)
        scale = alpha / rank
        import jax.numpy as jnp

        L = self.cfg.n_layers
        for target, pair in flat.items():
            if target not in dims:
                raise AdapterLoadError(
                    f"adapter {name!r} carries unknown target "
                    f"{target!r}")
            d_in, d_out = dims[target]
            a = np.asarray(pair["a"], np.float32)
            b = np.asarray(pair["b"], np.float32) * scale
            if a.shape != (L, d_in, rank) or b.shape != (L, rank, d_out):
                raise AdapterLoadError(
                    f"adapter {name!r} target {target} shapes "
                    f"{a.shape}/{b.shape} do not fit base "
                    f"({L}, {d_in}, r)/{(L, rank, d_out)}")
            if rank < self.rank:  # zero-pad to the pool rank
                a = np.concatenate(
                    [a, np.zeros((L, d_in, self.rank - rank),
                                 np.float32)], axis=2)
                b = np.concatenate(
                    [b, np.zeros((L, self.rank - rank, d_out),
                                 np.float32)], axis=1)
            mod, _, leaf = target.partition(".")
            entry = self.tree[mod][leaf]
            entry["a"] = entry["a"].at[:, slot].set(jnp.asarray(a))
            entry["b"] = entry["b"].at[:, slot].set(jnp.asarray(b))
            if self.draft_layers:
                dentry = self.draft_tree[mod][leaf]
                dentry["a"] = dentry["a"].at[:, slot].set(
                    jnp.asarray(a[:self.draft_layers]))
                dentry["b"] = dentry["b"].at[:, slot].set(
                    jnp.asarray(b[:self.draft_layers]))
        self.loads += 1
        self._count("kfx_lm_adapter_loads_total",
                    "Adapters paged into HBM slots from the artifact "
                    "store.")

    def nbytes(self) -> int:
        """Device bytes of the adapter stacks (target + draft) — the
        HBM cost of serving n_slots adapters over one base."""
        import jax

        return int(sum(
            int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
            for x in jax.tree_util.tree_leaves(
                [self.tree, self.draft_tree])))
