"""Continuous-batching LM decode engine: paged KV cache + in-flight
admission (Orca-style iteration-level scheduling, OSDI'22; vLLM's
PagedAttention block manager, SOSP'23, in the TPU-friendly fixed-shape
form) with content-hashed shared-prefix reuse.

The one-shot path (models/generate.LMGenerator) is run-to-completion:
each request owns the whole device for its prefill + scan decode, so
concurrent single-prompt traffic serializes and aggregate throughput
collapses to ~1/B of the batched number. The engine's first cut (PR 5)
owned ``n_slots`` dense KV rows of ``max_seq_len`` each — worst-case
HBM paid per slot regardless of actual request length, which is what
capped ``n_slots``. This engine instead owns ONE global pool of
``kv_pages`` fixed-size KV pages (``kv_page_size`` tokens each,
batch-independent — models/transformer.py ``_decode_attend``) plus a
per-slot **block table** mapping logical cache blocks to physical
pages:

  * pages are allocated at prefill and chunk boundaries, so a request
    only ever holds pages for tokens it has actually produced;
  * **admission is gated on free pages, not free slots** — ``n_slots``
    is just the max concurrency (a [B, vocab] logits row per slot),
    so it can rise far past the dense layout's HBM-bound count;
  * retirement returns pages to the free list copy-free (freed pages'
    position ids are invalidated in one batched scatter before reuse,
    so a recycled page can never leak stale KV into a new request);
  * a content-hashed **prefix cache** keeps retired-but-hot prompt
    pages: a new request whose prompt starts with a cached prefix
    points its block table at the refcounted read-only pages and skips
    that much prefill entirely (a partially-filled boundary page is
    shared via device copy-on-write); cache pages are reclaimed LRU
    when the pool needs them back.

The hot compiled inventory (one AOT table, populated by ``warm()`` —
"exactly two hot functions" stopped being true at PR 10):

  * ``prefill`` — one compile per power-of-two prompt-TAIL bucket;
    writes the unmatched prompt tokens through the slot's block table
    straight into the pool (no row copy) plus the last real token's
    logits. Chunked admission (below) dispatches these SAME
    executables at chunk-size buckets, so chunking adds at most one
    new compile (the chunk bucket itself).
  * ``decode_chunk`` — ONE compile; chunked ``lax.scan`` advancing
    every active slot. Dispatched only by draft-less engines.
  * the fused speculative step — ONE compile REPLACING decode_chunk
    when a draft is configured (``draft_layers > 0``): propose +
    multi-token verify + accept + rollback + draft catch-up in one
    dispatch per iteration.
  * the draft prefill — one compile per FULL-prompt bucket
    (speculative engines only; the draft shares no prefix cache).

Cold helpers (page-invalidate per pool, the COW page-copy, the
kv-quant chaos crush) compile once each.

Chunked prefill (``prefill_chunk_tokens > 0``): a long prompt no
longer stalls every active decode slot for its full prefill — the
head-of-line blocking iteration-level schedulers exist to kill.
Admission places the request in a slot WITHOUT dispatching; the slot
holds its pages and a **prefill cursor**, and each engine iteration
runs at most ONE page-multiple prompt-chunk dispatch (oldest cursor
first) before the normal decode/fused-spec step, so the per-iteration
decode stall is bounded by ``prefill_chunk_tokens`` instead of by
prompt length (measured by the ``kfx_lm_decode_stall_seconds``
histogram; chunk dispatches count ``kfx_lm_prefill_chunks_total``).
Each chunk writes the same tokens at the same dense-equivalent
locations the monolithic prefill would (attention masks by cached
position id, so a chunk's window attends its own tokens causally and
everything earlier through the block table), and the final chunk
lands the last real token's logits — greedy output stays
byte-identical to ``LMGenerator``. Chunked admission
composes with prefix-cache hits (the cursor starts at the matched
tail), preemption-by-recompute (a mid-prefill slot is a valid victim:
pages freed, request re-queued whole), drain (a prefilling slot is
in-flight work and finishes), and the draft pool (the draft's
full-prompt prefill runs once at cursor completion — draft-depth
cheap). Fully-covered prompt pages register into the prefix cache as
each chunk completes, so same-prefix admissions later in a wave still
share.

Exactness: attention masks by cached *position id* (-1 = empty), never
by cache location, and decode writes land at the DENSE-EQUIVALENT
location (prompt bucket + step), so greedy decode stays byte-identical
to the one-shot ``LMGenerator`` (models/generate.py, the oracle
tests/test_engine.py asserts against). When the pool
runs dry mid-decode the youngest slot is preempted and re-queued as a
recompute continuation (its pages freed for the older slots); a
request that cannot be placed at all fails with ``PageAllocError``
(an ``EngineOverloaded``), which the model server answers with
503 + Retry-After — bounded queueing, never a crash mid-chunk.

Speculative decoding (``draft_layers > 0``, Leviathan et al. ICML'23):
a layer-truncated DRAFT model (the target's first ``draft_layers``
layers + shared embed/head — same tokenizer, same vocab) proposes
``propose_tokens`` tokens per active slot from its OWN page pool (a
second BlockManager mirroring the target's block geometry), and the
target scores all proposals + the pending token as ONE multi-token
verify window per iteration instead of one dispatch per token — the
weight-streaming-bound small-batch regime reads the full weights once
per k+1 candidate tokens. One fused compiled step per iteration:
draft-propose scan -> target verify -> distribution-preserving accept
-> rejected-tail KV invalidation (cursor rollback + position-id stamp,
no page copies). Greedy acceptance is the temperature->0 limit of the
residual-sampling rule (one-hot target probs), so greedy engine output
stays BYTE-identical to ``LMGenerator`` — the standing
parity contract — and sampled output preserves the target distribution
exactly (accept d_i with min(1, p_i(d)/q_i(d)); on rejection sample
the normalized residual max(p_i - q_i, 0); the bonus token after k
accepts samples p_{k+1} directly, i.e. the q==0 case of the same
rule). Draft-pool exhaustion degrades THAT SLOT to non-speculative
(1 token/iteration through the same verify window) instead of failing
admission; target-pool pressure keeps the preempt-youngest recompute
path, which frees BOTH pools' pages.

Observability: ``kfx_lm_kv_pages`` / ``kfx_lm_kv_pages_free`` gauges,
``kfx_lm_prefix_cache_hits_total`` counter, token-weighted
``kfx_lm_slot_occupancy`` (slot capacity scaled by the pool fraction
active slots hold, distinct pages — an engine with 90% of its pages
free reads as mostly idle even with every slot busy), plus the PR-5
families; speculation adds ``kfx_lm_spec_proposed_total`` /
``kfx_lm_spec_accepted_total`` counters, the trailing-window
``kfx_lm_spec_accept_rate`` gauge and the per-iteration
``engine.verify`` span.
Quantization (PR 11): ``kv_quant="int8"`` stores both pools' K/V
entries as int8 with per-token f32 scale planes beside the pages
(quantize-on-write / dequant-on-gather in ``_decode_attend``) — the
same byte budget holds ~2x (vs bf16; ~3.5x vs f32) the tokens, so
page-gated admission takes proportionally more concurrent requests;
``draft_quant="int8"`` quantizes only the DRAFT's weights (per-channel
int8 via ``quantize_params_int8``), risking nothing but accept rate.
Weight-quantized TARGETS arrive as already-quantized params + a
``cfg.quant="int8"`` knob from the export layer. Quantized paths are
bounded-drift, not byte-exact — the f32 engine remains the parity
oracle, and ``kfx_lm_kv_bytes_per_token`` / ``kfx_lm_quant_mode``
gauges make the mode scrape-visible.

A second page class (PR 46): a configuration with "window" layers
(models/transformer.py: attention over the last ``window`` positions)
keeps those layers' cache leaves in a pool of their own, its pages
indexed by POSITION, with a second ``BlockManager`` and a second block
table a row (``_wmgr``, ``_wtables``), sized for every slot's worst
case: the blocks of a window, one more where it straddles a page, and
those of the tokens a dispatch writes. After every prompt dispatch and
decode chunk a row gives back the pages whose positions all lie behind
its next query's window (``_free_behind_window``; recycled pages'
position ids are invalidated before reuse by that class's own reset
program), so a row of any length holds a window's worth there, and a
window layer's gathered view is the window plus the dispatch's tokens,
whatever ``max_seq_len``. Admission, chunked prefill, the chunk-boundary
page budget, preemption by recompute and release account for both
classes; the prefix cache, speculation and KV offload / migration /
transfer are refused by name. Nothing selects it: the classes follow
from the configuration.

Self-healing (serving-fleet robustness): the loop keeps a progress
**heartbeat** (monotonic iteration counter + last-completed-iteration
timestamp, ``heartbeat()``) so the model server's /healthz is a real
liveness probe — stale progress while slots are active means the loop
is wedged, and the operator restarts the replica; and a one-way
**drain mode** (``drain()``) that stops admitting (EngineDraining ->
503 + Retry-After), resolves queued requests with that same retriable
error (the router re-dispatches them to a healthy replica) and lets
in-flight slots finish — the operator drains before every deliberate
kill (scale-in, revision respawn) so planned churn never loses a
request.

Multi-tenant LoRA adapters (serving/adapters.py, S-LoRA/Punica): an
HBM-resident ``[n_layers, adapter_slots, ...]`` A/B stack pool with a
BlockManager-style allocator (refcounts + LRU paging from the artifact
store), per-request adapter ids gathered into the SAME fused
prefill/decode/verify dispatches (batched-gather LoRA — one compiled
function serves a batch where every slot wears a different adapter;
id -1 = base-only, bit-identical to an adapterless engine), the prefix
cache chain-rooted at the adapter name (cached pages hold ADAPTER KV —
same tokens under different adapters never share a page), and
per-tenant weighted-round-robin admission (FairQueue) so one adapter's
burst queues behind itself. Greedy output with a single adapter is
byte-identical to the dense merged-weights (W + alpha/rank·A·B) oracle
— the one compiled engine IS N merged deployments, at base + stacks
HBM instead of N bases.

Chaos points ``engine.admit``, ``engine.kv_alloc``,
``engine.spec_verify`` (a full-rejection wave: every proposal treated
as rejected for that iteration — throughput falls to the
non-speculative floor, correctness untouched), ``engine.kv_quant``
(int8 KV only: crushes the cached scale planes to the worst case —
quality/accept-rate degrade observably, never a crash or page leak),
``engine.adapter_load`` (forces adapter paging failure — the request
degrades to base-only or sheds 503 + Retry-After per the
``adapters.fallback`` spec knob) and ``engine.wedge`` (stalls the
decode loop with slots active — the deterministic liveness-failure
probe; docs/chaos.md).

jax is imported lazily (inside methods): server.py imports this module
for ``EngineOverloaded`` on its own import path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import threading
import time
from collections import OrderedDict, defaultdict, deque
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from .. import chaos
from ..obs import trace as obs_trace
from ..obs.metrics import MetricsRegistry, default_registry
from . import kvtransfer
from .prefix import chain_hash as _chain_hash

# Admission wait buckets (seconds): a healthy engine admits within one
# chunk (sub-ms..ms on tiny models, tens of ms on big ones); the tail
# is queueing behind a full pool.
QUEUE_WAIT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0)


def quant_mode_string(weights: str, kv: str) -> str:
    """Render the `kfx top` Q-column mode string from the
    ``kfx_lm_quant_mode`` gauge's label values (``int8`` /
    ``draft-int8`` / ``f32``): ONE mapping shared by the engine's
    ``quant_mode`` property and the model server's JSON engine block,
    so the two surfaces cannot drift."""
    parts = []
    if weights == "int8":
        parts.append("w8")
    elif weights == "draft-int8":
        parts.append("d8")
    if kv == "int8":
        parts.append("kv8")
    return "+".join(parts) or "f32"


# Request QoS classes (docs/serving.md "Request plane"): interactive
# traffic is served first and preempted last; batch is the first
# preemption victim and the first class shed under pool pressure.
QOS_CLASSES = frozenset({"interactive", "batch"})


class EngineOverloaded(RuntimeError):
    """Admission queue full — the bounded-queueing replacement for the
    old hard ``max_batch_size`` rejection. The server maps this to
    503 + Retry-After (shed load, don't 400 a well-formed request)."""


class EngineDraining(EngineOverloaded):
    """The engine is in drain mode (operator-initiated shutdown
    preamble): it stops admitting, finishes the slots already decoding,
    and resolves queued requests with THIS error. Subclasses
    EngineOverloaded so the server's shed-load contract applies —
    503 + Retry-After is exactly right: the request is well-formed and
    another replica (or this one's successor) can serve it, which is
    what the router's re-dispatch does."""


class RequestMigrated(EngineOverloaded):
    """The request's KV pages were exported to a peer replica
    (serving/kvtransfer.py) and the peer is already decoding it. The
    server maps this to 503 + a near-zero Retry-After + an
    ``X-Kfx-Migrated`` peer hint; the router's existing bounded
    re-dispatch (seeded recovery) lands on the peer, which attaches
    the re-dispatched body to the adopted in-flight generation by its
    content-derived resume key — byte-identical resume, including
    mid-SSE via the ``stream_skip`` plumbing. If the re-dispatch
    misses the peer (or the adoption expired), the SAME body degrades
    to the plain seeded recompute: migration failure is never a new
    failure mode, only a lost optimization."""

    def __init__(self, msg: str, peer: str = "",
                 retry_after_s: float = 0.05):
        super().__init__(msg)
        self.peer = peer
        self.retry_after_s = retry_after_s


class PageAllocError(EngineOverloaded):
    """KV page pool exhausted (or the ``engine.kv_alloc`` chaos point
    forced the failure) for a request that nothing in flight can
    unblock. Subclasses EngineOverloaded so the server's existing
    shed-load contract (503 + Retry-After) covers it."""


class AdapterSlotError(PageAllocError):
    """Every HBM adapter slot is pinned by an in-flight request —
    pool pressure exactly like KV-page exhaustion (the admission path
    requeues behind in-flight work, and a lone unplaceable request
    fails with the 503 + Retry-After shed contract). Subclassing
    PageAllocError keeps the engine's requeue/preempt handling ONE
    code path for both pools."""


class AdapterLoadError(EngineOverloaded):
    """An adapter artifact failed to page in (unknown name, unreadable
    or mismatched artifact, or the ``engine.adapter_load`` chaos
    point). Per the spec's ``adapters.fallback`` knob the engine
    either degrades the request to base-only (-1) or fails it with
    this error — an EngineOverloaded, so the server answers
    503 + Retry-After and the router re-dispatches."""


class WeightSlotError(PageAllocError):
    """Every HBM weight slot is pinned by an in-flight request — the
    whole-checkpoint analogue of AdapterSlotError (serving/weights.py).
    Pool pressure, not failure: admission requeues behind in-flight
    work, and a lone unplaceable request sheds with the 503 +
    Retry-After contract. Subclassing PageAllocError keeps the
    requeue/preempt handling ONE code path across all three pools
    (KV pages, adapter slots, weight slots)."""


class WeightLoadError(EngineOverloaded):
    """A model's weight artifact failed to page into its HBM slot
    (unknown name, unreadable/mismatched export, or the
    ``weights.load`` chaos point). Unlike adapters there is NO degrade
    option — serving the wrong weights is never an acceptable
    fallback — so the engine always fails the request with this
    error: an EngineOverloaded, so the server answers 503 +
    Retry-After and the router re-dispatches (possibly landing on a
    replica that still holds the model, or retrying the swap past a
    chaos budget)."""


class DeadlineInfeasible(EngineOverloaded):
    """The request's deadline cannot be met — judged BEFORE prefill
    (at enqueue against the trailing queue-wait estimate, or at the
    slot boundary when the deadline has already expired), so an
    infeasible request sheds immediately instead of burning a prefill
    and timing out after. Subclasses EngineOverloaded: the 503 +
    Retry-After shed contract applies, and a client with deadline
    headroom left can retry another replica."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class RateLimited(EngineOverloaded):
    """A tenant exhausted its token-weighted rate budget
    (``rate_limits``, tokens/second of prompt+max_new weight with a
    ``rate_burst_s`` burst allowance): the burst degrades to the
    TENANT's budget, never the fleet's. Subclasses EngineOverloaded —
    503 with a Retry-After derived from the budget deficit."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class Request:
    """One in-flight generation: token budget, sampling knobs, and a
    completion event the submitting thread waits on. ``tokens`` doubles
    as the recompute-continuation state: a preempted request re-enters
    the queue with its generated ids intact and prefills
    prompt+generated on re-admission."""

    __slots__ = ("prompt", "max_new", "temperature", "top_k", "seed",
                 "stop", "adapter", "model", "tokens", "rng", "error",
                 "t_enqueue", "t_admitted", "t_done", "counted",
                 "trace_id", "span_id", "_event", "rid", "events",
                 "t_first", "stall_s", "preempts", "spec_prop",
                 "spec_acc", "_flight", "qos", "deadline", "on_token",
                 "tenant", "meter_skip", "_usage", "it_admitted",
                 "t_prefill_end", "prefill_iters", "slot")

    _rid_counter = itertools.count(1)

    def __init__(self, prompt: List[int], max_new: int, temperature: float,
                 top_k: int, seed: int, stop: int, adapter: str = "",
                 qos: str = "interactive",
                 deadline: Optional[float] = None, model: str = ""):
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.stop = stop              # -1 = no stop token
        self.adapter = adapter        # "" = base model (tenant key)
        self.model = model            # "" = pool default (weight pool)
        # QoS class ("interactive"/"batch"): batch slots are the first
        # preemption victims and the first shed under pool pressure.
        self.qos = qos
        # Absolute monotonic deadline (None = no deadline): checked
        # BEFORE prefill — infeasible requests shed, never time out.
        self.deadline = deadline
        # Streaming sink: called with each generated token id on the
        # LOOP thread when its chunk is handed out (_pay_owed: behind
        # the next chunk's enqueue), then with None at retirement.
        # Preemption-by-recompute never re-fires already-notified
        # tokens — ``tokens`` only grows (recompute re-prefills, it
        # does not re-emit), so a token streams exactly once.
        self.on_token: Optional[Callable[[Optional[int]], None]] = None
        self.tokens: List[int] = []   # generated ids, filled by the loop
        # RNG stream stashed at preemption ([2] uint32); None until
        # then — a fresh admission derives the stream from ``seed``.
        self.rng: Optional[np.ndarray] = None
        # Admission stats (queue wait, prompt tokens, prefix hits)
        # counted once, at the FIRST admission: a requeued preempt —
        # including a mid-prefill one, whose token list is still
        # empty — is recompute, not a new client admission, and
        # ``tokens`` alone cannot tell those apart.
        self.counted = False
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.monotonic()
        # First-admission stamp (queue-wait = t_admitted - t_enqueue;
        # 0.0 until admitted) — what the fairness tests read per
        # TENANT, where the aggregate histogram can't discriminate.
        self.t_admitted = 0.0
        self.t_done = 0.0
        # Captured on the submitting thread so the engine thread's
        # admit/chunk spans join the request's trace tree (the same
        # contract MicroBatcher uses for batcher.flush).
        self.trace_id = obs_trace.current_trace_id()
        self.span_id = obs_trace.current_span_id()
        # Flight-recorder trail: small per-request event list (loop
        # thread appends) + attribution counters folded into a latency
        # breakdown at retirement. ``_flight`` is the engine's recorder
        # (None when recording is disabled — every hook is skipped).
        self.rid = next(Request._rid_counter)
        self.events: List[dict] = []
        self.t_first = 0.0            # first generated token landed
        # The TTFT split (obs/flightrec.py timing): the loop iteration
        # of the first admission, and — stamped at every prompt
        # dispatch until the first token lands — when the LAST prompt
        # chunk was enqueued and how many iterations the prompt took
        # by then. Kept on the request, not searched in ``events``: a
        # trail cut by MAX_EVENTS still has them.
        self.it_admitted = 0
        self.t_prefill_end = 0.0
        self.prefill_iters = 0
        # The slot of the latest admission (-1 before the first): with
        # slot-indexed state, where DecodeEngine.slot_state() finds
        # what the request left behind.
        self.slot = -1
        self.stall_s = 0.0            # stall seconds while active
        self.preempts = 0
        self.spec_prop = 0            # draft tokens proposed for us
        self.spec_acc = 0             # ...and accepted
        self._flight = None
        # Usage metering (serving/metering.py): the billable tenant
        # key (adapter tenant unless the client named one), the ledger
        # to bill against (None = metering off), and how many leading
        # generated tokens were a recovery re-dispatch's regeneration
        # of already-billed output (``stream_skip``) — billed once
        # fleet-wide, by the replica that actually streamed them.
        self.tenant = adapter or "base"
        self.meter_skip = 0
        self._usage = None
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def _notify(self, token: Optional[int]) -> None:
        """Fire the streaming sink (loop thread). A broken sink is
        dropped, never propagated — one disconnected stream must not
        kill the decode loop serving everyone else."""
        cb = self.on_token
        if cb is None:
            return
        try:
            cb(token)
        except Exception:
            self.on_token = None

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self.error = error
        self.t_done = time.monotonic()
        # Retirement-side generated-token billing: every outcome path
        # funnels through here exactly once, ``tokens`` only grows
        # (recompute re-prefills, never re-emits), and only an ADMITTED
        # request billed its prompt — a pre-admission shed retires
        # without a ledger row.
        if self._usage is not None and self.counted:
            self._usage.retire(self.tenant, self.qos,
                               self.adapter or "base",
                               len(self.tokens) - self.meter_skip)
        if self._flight is not None:
            self._flight.event(self, "retire",
                               err=type(error).__name__ if error else None)
            self._flight.retire(self)
        # End-of-stream marker BEFORE the event: a streamer that woke
        # on the sentinel can rely on result() returning immediately.
        self._notify(None)
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"engine did not complete the request within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.tokens


class BlockManager:
    """Host-side page-pool bookkeeping: a free list plus per-page
    refcounts (a page shared by k block tables — slots and/or the
    prefix cache — carries ref k and returns to the free list only
    when the last holder releases it). Freed pages are remembered as
    ``dirty`` until their cached position ids are invalidated on
    device (the engine batches that into one scatter per reuse)."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError("n_pages and page_size must be >= 1")
        self.n_pages = n_pages
        self.page_size = page_size
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self.ref = np.zeros((n_pages,), np.int32)
        self.dirty: set = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages (ref 1 each). All-or-nothing: raises
        PageAllocError without side effects when the free list is
        short (the caller reclaims prefix-cache pages first)."""
        if n > len(self._free):
            raise PageAllocError(
                f"KV page pool exhausted ({len(self._free)} free, "
                f"{n} needed, {self.n_pages} total)")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.ref[p] = 1
        return pages

    def incref(self, page: int) -> None:
        assert self.ref[page] > 0, f"incref of free page {page}"
        self.ref[page] += 1

    def decref(self, pages: Sequence[int]) -> List[int]:
        """Release one reference per page; pages hitting zero return
        to the free list (marked dirty) and are listed back."""
        freed = []
        for p in pages:
            assert self.ref[p] > 0, f"decref of free page {p}"
            self.ref[p] -= 1
            if self.ref[p] == 0:
                self._free.append(p)
                self.dirty.add(p)
                freed.append(p)
        return freed


class _PrefixEntry:
    __slots__ = ("key", "parent", "page", "tokens", "partial",
                 "nchildren", "root")

    def __init__(self, key: bytes, parent: bytes, page: int,
                 tokens: Tuple[int, ...], partial: bool,
                 root: bytes = b""):
        self.key = key          # lru/map key (chain hash; partial: parent)
        self.parent = parent
        self.page = page
        self.tokens = tokens    # partial entries: the page's real tokens
        self.partial = partial
        self.nchildren = 0      # cached entries extending this one
        self.root = root        # chain seed (adapter / model@generation)


class PrefixCache:
    """Content-hashed prompt-page cache over the shared pool.

    Full pages are keyed by the CHAIN hash of their content (page i's
    key folds page i-1's key, so a match is a match of the whole
    prefix, not of one page in isolation). At most one PARTIAL entry
    per parent key remembers a request's last, partially-filled prompt
    page — matched by exact token comparison and shared via device
    copy-on-write (the copy drops everything past the matched tokens,
    so a stale tail can never leak). The cache holds one pool ref per
    entry; eviction is LRU over childless entries whose page no live
    slot still uses (ref == 1)."""

    def __init__(self, manager: BlockManager):
        self.mgr = manager
        self.full: Dict[bytes, _PrefixEntry] = {}
        self.partial: Dict[bytes, _PrefixEntry] = {}
        self._lru: "OrderedDict[Tuple[bool, bytes], _PrefixEntry]" = \
            OrderedDict()
        self.hits = 0
        self.tokens_reused = 0

    def __len__(self) -> int:
        return len(self._lru)

    def _touch(self, e: _PrefixEntry) -> None:
        self._lru.move_to_end((e.partial, e.key))

    def match(self, tokens: Sequence[int], max_reuse: int,
              root: bytes = b""
              ) -> Tuple[List[int], Optional[Tuple[int, int]], int, bytes]:
        """Longest cached prefix of ``tokens`` reusable within
        ``max_reuse`` (the caller caps at len-1: the last prompt token
        must run through the model for its logits). Returns
        (full_pages, cow, matched_tokens, chain_key) where ``cow`` is
        (source_page, n_tokens) when a partial boundary page extends
        the match via copy-on-write. ``root`` seeds the chain: the
        engine passes the request's ADAPTER name, because cached pages
        hold adapter-specific KV (the k/v projections wear the
        adapter) — identical tokens under different adapters must
        never share a page."""
        ps = self.mgr.page_size
        pages: List[int] = []
        key, matched = root, 0
        while matched + ps <= max_reuse:
            nxt = _chain_hash(key, tokens[matched:matched + ps])
            e = self.full.get(nxt)
            if e is None:
                break
            pages.append(e.page)
            key, matched = nxt, matched + ps
            self._touch(e)
        cow = None
        pe = self.partial.get(key)
        if pe is not None:
            # Longest agreeing prefix of the boundary page (the COW
            # copy keeps exactly this many token slots valid).
            cap = min(len(pe.tokens), max_reuse - matched)
            extra = 0
            while extra < cap and \
                    tokens[matched + extra] == pe.tokens[extra]:
                extra += 1
            if extra > 0:
                cow = (pe.page, extra)
                matched += extra
                self._touch(pe)
        return pages, cow, matched, key

    def insert_full(self, parent: bytes, page_tokens: Sequence[int],
                    page: int, root: bytes = b"") -> bytes:
        """Register one full prompt page; returns its chain key. A
        pre-existing identical entry is refreshed, not duplicated."""
        key = _chain_hash(parent, page_tokens)
        e = self.full.get(key)
        if e is not None:
            self._touch(e)
            return key
        e = _PrefixEntry(key, parent, page, (), False, root=root)
        self.mgr.incref(page)
        self.full[key] = e
        self._lru[(False, key)] = e
        pe = self.full.get(parent)
        if pe is not None:
            pe.nchildren += 1
        return key

    def insert_partial(self, parent: bytes, tokens: Sequence[int],
                       page: int, root: bytes = b"") -> None:
        """Register a partially-filled boundary page (first writer
        wins per parent — replacing a hot partial with an equivalent
        one would only churn refcounts)."""
        if not tokens or parent in self.partial:
            return
        e = _PrefixEntry(parent, parent, page, tuple(tokens), True,
                         root=root)
        self.mgr.incref(page)
        self.partial[parent] = e
        self._lru[(True, parent)] = e
        pe = self.full.get(parent)
        if pe is not None:
            pe.nchildren += 1

    def _drop(self, e: _PrefixEntry) -> List[int]:
        del (self.partial if e.partial else self.full)[e.key]
        del self._lru[(e.partial, e.key)]
        pe = self.full.get(e.parent)
        if pe is not None:
            pe.nchildren -= 1
        return self.mgr.decref([e.page])

    def evict_one(self, spill: Optional[Callable[["_PrefixEntry"],
                                                 None]] = None) -> bool:
        """Reclaim the least-recently-used childless entry whose page
        no slot is still reading (pool ref == 1). Returns whether a
        page went back to the free list. ``spill`` sees the entry
        BEFORE it drops — the engine's host-RAM offload demotion
        (DecodeEngine._spill_page) reads the page there; the selection
        rule above is what makes that read refcount-safe."""
        for e in list(self._lru.values()):
            if e.nchildren == 0 and self.mgr.ref[e.page] == 1:
                if spill is not None:
                    spill(e)
                self._drop(e)
                return True
        return False

    def drop_root(self, root: bytes) -> List[int]:
        """Invalidate every chain seeded at ``root`` — the weight
        pool's eviction hook (docs/serving.md "Weights as a fleet
        resource"): a model's cached prompt pages must never survive
        its weight slot, or a stale prefix hit would pair pages
        computed under the OLD weights with a freshly swapped-in tree.
        Pages a live slot still reads keep their slot ref and return
        to the free list when that slot retires (the in-flight request
        admitted under the old generation and keeps its pin)."""
        freed: List[int] = []
        for e in list(self._lru.values()):
            if e.root == root:
                freed += self._drop(e)
        return freed

    def drop_all(self) -> List[int]:
        """Drop every entry, releasing the cache's page refs (pages a
        live slot still reads survive until that slot retires). The
        ``engine.kv_quant`` chaos path uses this: a scale-plane crush
        corrupts CACHED prompt pages too, and cached pages are never
        rewritten while cached — serving them to future admissions
        would extend the injected fault past its budget."""
        freed: List[int] = []
        for e in list(self._lru.values()):
            freed += self._drop(e)
        return freed


# The counter families of the sparse selection (models/latent.py
# COUNTS), of the routed experts (models/experts.py COUNTS), of the
# state-space layers and of the decode chunk's sampler, in those orders,
# with their help texts. The programs count on the device.
_COUNT_FAMILIES = {
    "kfx_lm_sparse_cached_positions_total":
        "Cached positions query tokens could attend, summed over tokens "
        "and sparse-attention layers.",
    "kfx_lm_sparse_attended_positions_total":
        "Locations of the cache view the main attention scored and mixed "
        "(the whole view a query: the selection is a mask over it), summed "
        "over tokens and layers.",
    "kfx_lm_moe_assignments_total":
        "(token, chosen expert) pairs routed, over routed-expert layers.",
    "kfx_lm_moe_assignments_held_total":
        "Routed pairs whose expert this replica holds.",
    "kfx_lm_moe_dispatches_total":
        "Grouped-product dispatches (one a routed-expert layer a model "
        "call).",
    "kfx_lm_moe_max_rows_total":
        "Rows of the fullest held expert, summed over dispatches.",
    "kfx_lm_moe_experts_hit_total":
        "Held experts that received rows (whose matrices the grouped "
        "product reads), summed over dispatches.",
    "kfx_lm_ssm_row_updates_total":
        "Rows whose recurrent state a decode step advanced, summed over "
        "steps and state-space layers.",
    "kfx_lm_ssm_prefill_tokens_total":
        "Prompt tokens run through the chunked scan, summed over "
        "state-space layers.",
    "kfx_lm_state_resets_total":
        "Rows that started from an empty state (a sequence's first "
        "token), summed over state-space layers.",
    "kfx_lm_window_cached_positions_total":
        "Positions query tokens hold as context, summed over tokens and "
        "window layers.",
    "kfx_lm_window_attended_positions_total":
        "Positions of that context inside the window (what a window "
        "layer's query reads), summed over tokens and window layers.",
    "kfx_lm_window_gathered_positions_total":
        "Positions of the window class's pool a window layer's attention "
        "scored for a row (the width of the row's gathered view, or the "
        "whole pool where it attends in place), summed over the rows of "
        "a call and window layers.",
    "kfx_lm_sample_steps_total":
        "Decode steps run (chunks times the chunk's tokens).",
    "kfx_lm_sample_draw_steps_total":
        "Decode steps in which an active row drew its token "
        "(temperature > 0); in the others the sampler took the argmax "
        "and nothing else.",
    "kfx_lm_sample_sort_steps_total":
        "Decode steps in which an active drawing row set a top_k, so "
        "the sampler sorted the vocabulary.",
}


# Of those, the ones with a twin that grows by the decode chunks' counts
# alone (a prompt dispatch hits every expert and holds one row): what a
# decode STEP read, over ``kfx_lm_sample_steps_total`` steps.
_DECODE_TWINS = {
    "kfx_lm_moe_experts_hit_total": "kfx_lm_decode_experts_hit_total",
    "kfx_lm_window_cached_positions_total":
        "kfx_lm_decode_window_cached_positions_total",
    "kfx_lm_window_attended_positions_total":
        "kfx_lm_decode_window_attended_positions_total",
    "kfx_lm_window_gathered_positions_total":
        "kfx_lm_decode_window_gathered_positions_total",
}


class _Handout:
    """What one iteration of the loop owes the world outside it, in the
    order it is paid (``DecodeEngine._pay_owed``): the counts the
    programs made up to its dispatch (``chunks`` of them decode chunks
    or verify windows), then for every row its new tokens to the
    request's sink and, where the row is done, the request's finish,
    then the token counter, the gauges and the iteration's flight
    record (``iteration`` None: tokens handed out ahead of the
    iteration's dispatch, which bring no record)."""

    __slots__ = ("iteration", "chunks", "counts", "rows", "emitted")

    def __init__(self, iteration: Optional[int], chunks: int = 0,
                 counts: Sequence[Any] = ()):
        self.iteration = iteration
        self.chunks = chunks
        self.counts = counts
        self.rows: List[Tuple[Request, List[int], bool]] = []
        self.emitted = 0


class DecodeEngine:
    """Owns the paged KV pool, the block tables, the prefix cache, the
    compiled prefill/decode functions and the decode-loop thread. One
    instance per served LM."""

    def __init__(self, cfg, params, n_slots: int = 8,
                 chunk_tokens: int = 8, max_queue: Optional[int] = None,
                 name: str = "model",
                 registry: Union[MetricsRegistry,
                                 Callable[[], MetricsRegistry],
                                 None] = None,
                 request_timeout_s: float = 50.0,
                 kv_page_size: int = 32,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft_layers: int = 0,
                 propose_tokens: int = 4,
                 draft_kv_pages: Optional[int] = None,
                 kv_quant: str = "",
                 draft_quant: str = "",
                 stall_threshold_s: float = 10.0,
                 prefill_chunk_tokens: int = 0,
                 adapters: Optional[Dict[str, str]] = None,
                 adapter_slots: int = 8,
                 adapter_rank: int = 0,
                 adapter_default: str = "",
                 adapter_fallback: str = "base",
                 tenant_weights: Optional[Dict[str, int]] = None,
                 qos_default: str = "interactive",
                 deadline_default_s: float = 0.0,
                 rate_limits: Optional[Dict[str, float]] = None,
                 rate_burst_s: float = 2.0,
                 role: str = "mixed",
                 kv_peer_send: Optional[Callable[[bytes], str]] = None,
                 kv_offload_pages: int = 0,
                 models: Optional[Dict[str, str]] = None,
                 weight_slots: int = 0,
                 model_default: str = "",
                 model_idle_s: float = 0.0):
        import jax

        from ..models.generate import decode_config
        from ..models.transformer import TransformerLM

        # This process drives a device: its spans and the loop's phase
        # marks go into the profiler's trace too (obs/trace.py).
        obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        if draft_layers < 0:
            raise ValueError("draft_layers must be >= 0 (0 = no "
                             "speculative decoding)")
        if draft_layers > 0 and propose_tokens < 1:
            raise ValueError("propose_tokens must be >= 1")
        base = decode_config(cfg)
        L = base.max_seq_len
        ps = min(int(kv_page_size), L)
        if ps < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {ps}")
        while L % ps:
            # The gathered view must tile max_seq_len exactly; fall
            # back to the largest divisor at or below the request.
            ps -= 1
        self.page_size = ps
        self.n_blocks = L // ps
        # Default pool = the dense layout's HBM (n_slots full rows);
        # shrink kv_pages to cap KV HBM below that — admission then
        # gates on pages, and n_slots is just max concurrency.
        self.n_pages = int(kv_pages) if kv_pages else n_slots * self.n_blocks
        if self.n_pages < self.n_blocks:
            # One request must always be placeable, or the engine
            # could accept traffic it can never serve.
            raise ValueError(
                f"kv_pages {self.n_pages} < blocks per max-length "
                f"request {self.n_blocks}")
        if kv_quant not in ("", "int8"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r} (expected '' or 'int8')")
        if draft_quant not in ("", "int8"):
            raise ValueError(
                f"unknown draft_quant {draft_quant!r} "
                "(expected '' or 'int8')")
        # int8 paged KV (kv_quant="int8"): the pool's K/V entries store
        # as int8 with per-token f32 scale planes beside the pages —
        # models/transformer.py quantize-on-write / dequant-on-gather.
        # Independent of weight quant; both the target and draft pools
        # follow it (the draft cfg derives from self.cfg below).
        # The second page class: the "window" runs' pool, its pages
        # indexed by position. A row holds the blocks of its window,
        # one more where the window straddles a page, and those of the
        # tokens a dispatch writes before the pages behind the window
        # are given back (a prompt chunk, or a whole bucket where the
        # prefill is not chunked): every slot's worst case fits, so
        # this class never turns a row away that the first admits.
        self.window_pages = 0
        if base.has_window_pages:
            wrote = -(-int(prefill_chunk_tokens) // ps) * ps \
                if prefill_chunk_tokens > 0 else max(8, L // 2)
            self.window_pages = n_slots * min(
                self.n_blocks, -(-base.window // ps) + 2
                + -(-max(wrote, chunk_tokens) // ps))
        self.cfg = dataclasses.replace(
            base, kv_page_size=ps, kv_pages=self.n_pages,
            window_pages=self.window_pages,
            kv_quant=kv_quant or base.kv_quant,
            state_slots=n_slots if base.has_slot_state else 0)
        self.name = name
        if prefix_cache is None:
            # On, where the configuration can take it; asked for by
            # name where it cannot, it is refused below.
            prefix_cache = not (base.has_slot_state
                                or base.has_window_pages)
        if base.has_window_pages:
            # What reads or moves a row's pages as ONE table over ONE
            # pool is refused by name; none runs wrong. (Preemption by
            # recompute works: both classes' pages go back, and the
            # row is prefilled again from its first token.)
            for asked, feature, why in (
                    (prefix_cache, "the prefix cache",
                     "a matched page of the first class says nothing "
                     "of the window layers' pages, which a row gives "
                     "back as it moves on (KFX_LM_PREFIX_CACHE=0)"),
                    (draft_layers > 0, "speculative decoding",
                     "a rejected proposal is rolled back in one pool, "
                     "by location"),
                    (role != "mixed" or kv_peer_send is not None
                     or kv_offload_pages > 0,
                     "KV offload, migration and transfer",
                     "they move the pages of one block table")):
                if asked:
                    raise ValueError(
                        f"{feature} cannot take a configuration with a "
                        f"second page class ('window' layers): {why}")
        if base.has_slot_state:
            # A row's state lies in leaves indexed by slot, beside its
            # pages: what takes a row's state to BE its pages is
            # refused by name. (Preemption by recompute works: a
            # sequence's first token starts from zeros: models/ssm.py.)
            for asked, feature, why in (
                    (prefix_cache, "the prefix cache",
                     "a page match says nothing of the state at that "
                     "position (KFX_LM_PREFIX_CACHE=0)"),
                    (draft_layers > 0, "speculative decoding",
                     "a rejected proposal cannot be rolled back out of "
                     "a state"),
                    (bool(adapters), "LoRA adapters",
                     "the adapter stacks have no state-space targets"),
                    (bool(models), "the weight pool",
                     "it has not been driven with state beside pages"),
                    (role != "mixed" or kv_peer_send is not None
                     or kv_offload_pages > 0,
                     "KV offload, migration and transfer",
                     "they move pages, and a slot's state would have to "
                     "move with them")):
                if asked:
                    raise ValueError(
                        f"{feature} cannot take a configuration with "
                        f"slot state ('mamba' layers): {why}")
        if base.layer_pattern or base.kv_lora_rank > 0:
            # What still assumes one run of layers named "layers" or
            # K/V leaves a head: refused by name, none runs wrong.
            # (KV transfer, offload and migration move whole pages
            # leaf by leaf and take any cache tree.)
            for asked, feature, why in (
                    (draft_layers > 0, "speculative decoding",
                     "the draft is the first layers of ONE scanned "
                     "'layers' stack (truncate_layers)"),
                    (bool(adapters), "LoRA adapters",
                     "the adapter stacks target q/k/v/out and mlp "
                     "kernels of the one dense block"),
                    (bool(models), "the weight pool",
                     "it has not been driven with more than one run "
                     "of layers")):
                if asked:
                    raise ValueError(
                        f"{feature} cannot take this configuration "
                        f"(layer_pattern {base.layer_pattern!r}, "
                        f"kv_lora_rank {base.kv_lora_rank}): {why}")
        # What the programs' layers (_counted) and the decode chunk's
        # sampler counted, one tuple a dispatch, on the device until
        # flushed.
        self._counts_pending: List[Any] = []
        # Hand-outs owed (_Handout, oldest first). A decode chunk's
        # tokens land in their requests as soon as the host has them;
        # what the chunk owes beyond that is paid once the NEXT chunk
        # is enqueued, so that it runs while the device works. Nothing
        # here ever waits on a parked loop, and no request in the
        # queue is owed anything (_pay_owed's callers).
        self._owed: Deque[_Handout] = deque()
        self.n_slots = n_slots
        self.chunk_tokens = chunk_tokens
        # Chunked prefill: admit prompt tails in page-multiple chunks,
        # one chunk dispatch per engine iteration, bounding the decode
        # stall a long prompt can inflict. 0 = monolithic (one prefill
        # dispatch per admission, the pre-chunking behavior); any other
        # value rounds UP to a whole number of pages so chunk
        # boundaries and page boundaries coincide.
        if prefill_chunk_tokens < 0:
            raise ValueError("prefill_chunk_tokens must be >= 0 "
                             "(0 = monolithic prefill)")
        if prefill_chunk_tokens:
            prefill_chunk_tokens = -(-int(prefill_chunk_tokens)
                                     // ps) * ps
        self.prefill_chunk_tokens = prefill_chunk_tokens
        if draft_layers >= base.n_layers:
            raise ValueError(
                f"draft_layers {draft_layers} must be < the target's "
                f"n_layers {base.n_layers} (a draft as deep as the "
                "target proposes at the target's cost — no win)")
        self.spec = draft_layers > 0
        self.draft_layers = draft_layers
        self.propose_tokens = propose_tokens
        self.max_queue = max_queue if max_queue is not None else 4 * n_slots
        # Below the router's 60s backend timeout: a queue-starved
        # request fails with a clean engine error, never a router 502.
        self.request_timeout_s = request_timeout_s
        # -- request-plane policy: QoS class default, deadline default
        # and per-tenant token-weighted rate budgets (docs/serving.md
        # "Request plane").
        if qos_default not in QOS_CLASSES:
            raise ValueError(
                f"unknown qos_default {qos_default!r} "
                f"(expected one of {sorted(QOS_CLASSES)})")
        self.qos_default = qos_default
        if deadline_default_s < 0:
            raise ValueError("deadline_default_s must be >= 0 "
                             "(0 = no default deadline)")
        self.deadline_default_s = float(deadline_default_s)
        self.rate_limits = {str(k): float(v)
                            for k, v in (rate_limits or {}).items()}
        for tenant, rate in self.rate_limits.items():
            if rate <= 0:
                raise ValueError(
                    f"rate_limits[{tenant!r}] must be > 0 tokens/s")
        self.rate_burst_s = max(float(rate_burst_s), 0.1)
        # Tenant -> [budget_tokens, last_refill] token buckets (guarded
        # by _cond; overdraw model: a request is admitted while the
        # budget is positive and debits its full prompt+max_new weight,
        # so a burst runs the budget negative and the tenant waits
        # deficit/rate seconds — which is exactly the Retry-After).
        self._rate_buckets: Dict[str, List[float]] = {}
        # Trailing queue-wait estimate (EWMA of first-admission waits):
        # the deadline feasibility check's input — a request whose
        # remaining deadline is under the current queue wait sheds at
        # enqueue instead of burning a prefill.
        self._qwait_ewma = 0.0
        self._registry = registry
        self.model = TransformerLM(self.cfg)
        self.params = jax.device_put(params)
        # Donating the carried device state (cache + logits buffer)
        # makes each chunk update in place on accelerators; on the CPU
        # backend donation is unsupported noise, skip it.
        self._donate = jax.default_backend() != "cpu"

        self.prompt_buckets: List[int] = []
        b = 8
        while b <= max(8, L // 2):
            self.prompt_buckets.append(min(b, L))
            b *= 2

        # -- pool bookkeeping (touched only by the loop thread)
        self._mgr = BlockManager(self.n_pages, ps)
        self._wmgr: Optional[BlockManager] = \
            BlockManager(self.window_pages, ps) if self.window_pages \
            else None
        self._prefix: Optional[PrefixCache] = \
            PrefixCache(self._mgr) if prefix_cache else None
        self._prompt_tokens = 0  # prompt tokens admitted (for skip frac)

        # -- KV transfer plane (serving/kvtransfer.py): the replica's
        # disaggregation role, the peer sender exports ship through,
        # and the host-RAM offload tier cold prefix pages demote into.
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"unknown role {role!r} (expected prefill, decode or "
                "mixed)")
        self.role = role
        self._peer_send = kv_peer_send
        if kv_offload_pages < 0:
            raise ValueError("kv_offload_pages must be >= 0 "
                             "(0 = no host-RAM offload tier)")
        self._offload: Optional[kvtransfer.HostOffloadTier] = \
            kvtransfer.HostOffloadTier(kv_offload_pages) \
            if kv_offload_pages else None
        # rids a prefill-role engine must not (re-)hand off: the
        # transfer is already in flight, or it failed and the slot
        # decodes locally (the mixed fallback). Bounded: cleared
        # wholesale past 4096 entries — a stale rid only costs one
        # redundant skip check, never correctness.
        self._handoff_skip: set = set()
        # Cross-thread control jobs for the loop thread (KV export
        # snapshots, import installs): slot state is loop-thread-only,
        # so other threads post a thunk and wait (_run_on_loop).
        self._control: "deque[Callable[[], None]]" = deque()

        # -- speculative-decode state: a layer-truncated draft sharing
        # the target's tokenizer/vocab/page geometry, proposing from
        # its OWN pool so draft KV never competes with target KV for a
        # page (and a draft shortfall degrades the slot, never the
        # admission).
        if self.spec:
            from ..models.transformer import truncate_layers

            self.draft_n_pages = int(draft_kv_pages) if draft_kv_pages \
                else self.n_pages
            if self.draft_n_pages < 1:
                raise ValueError("draft_kv_pages must be >= 1")
            self.draft_cfg = dataclasses.replace(
                self.cfg, n_layers=draft_layers,
                kv_pages=self.draft_n_pages)
            draft_params = truncate_layers(params, draft_layers)
            if draft_quant == "int8" and self.cfg.quant != "int8":
                # Draft-only weight quantization — the natural first
                # customer (ROADMAP item 2): a wrong draft risks only
                # accept rate, which kfx_lm_spec_accept_rate already
                # measures, while the full-precision target keeps
                # output quality bit-for-bit.
                from ..models.transformer import quantize_params_int8

                self.draft_cfg = dataclasses.replace(
                    self.draft_cfg, quant="int8")
                draft_params = quantize_params_int8(draft_params)
            self.draft_model = TransformerLM(self.draft_cfg)
            self.draft_params = jax.device_put(draft_params)
            self._draft_mgr = BlockManager(self.draft_n_pages, ps)
        else:
            self.draft_n_pages = 0
            self.draft_model = self.draft_params = None
            self._draft_mgr = None
        # Cumulative spec counters (host truth; the registry counters
        # mirror them) + the trailing accept-rate window. The window
        # lock covers the deque: the gauge is read from server threads
        # (on_metrics_attached) while the loop thread appends.
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_degraded = 0
        self._spec_lock = threading.Lock()
        self._spec_window: "deque[Tuple[float, int, int]]" = deque()

        # -- multi-tenant LoRA adapters (serving/adapters.py): an
        # HBM-resident [n_layers, adapter_slots, ...] A/B stack pool
        # with LRU paging from the artifact store; per-request adapter
        # ids gather into the SAME fused dispatches (batched-gather
        # LoRA), id -1 = base-only. Enabled iff ``adapters`` (name ->
        # artifact URI) is non-empty.
        if adapter_fallback not in ("base", "error"):
            raise ValueError(
                f"unknown adapter_fallback {adapter_fallback!r} "
                "(expected 'base' or 'error')")
        self.adapter_fallback = adapter_fallback
        self.adapter_default = adapter_default or ""
        if adapters:
            from .adapters import AdapterPool

            self._apool: Optional["AdapterPool"] = AdapterPool(
                self.cfg, n_slots=adapter_slots, sources=adapters,
                rank=adapter_rank, draft_layers=draft_layers,
                name=name, registry=self._reg)
        else:
            self._apool = None
        if self.adapter_default and (
                self._apool is None
                or not self._apool.known(self.adapter_default)):
            raise ValueError(
                f"adapter_default {self.adapter_default!r} is not a "
                "configured adapter")

        # -- multi-model HBM weight pool (serving/weights.py): several
        # whole checkpoints time-share this engine's chips. The
        # compiled hot functions take ``params`` as a traced ARGUMENT,
        # so same-shaped models share ONE executable — a swap is a
        # device_put, and _decode_once groups batch rows per weight
        # slot. The ctor params are the DEFAULT model, adopted into a
        # permanently-pinned slot (the warm template every compile and
        # readiness check uses).
        self.model_default = model_default or ""
        self.model_idle_s = float(model_idle_s)
        if models:
            if self.spec:
                raise ValueError(
                    "models= (weight pool) is incompatible with "
                    "speculative decoding: the layer-truncated draft "
                    "derives from ONE checkpoint")
            if self._apool is not None:
                raise ValueError(
                    "models= (weight pool) is incompatible with "
                    "adapters=: the LoRA slot pool factors over ONE "
                    "base model")
            if role != "mixed" or kv_peer_send is not None:
                raise ValueError(
                    "models= (weight pool) requires role='mixed' with "
                    "no KV peers: a migrated request's pages would "
                    "decode under the peer's weights")
            if not self.model_default:
                raise ValueError(
                    "model_default must name the engine's resident "
                    "model (one of models=)")
            if self.model_default not in models:
                raise ValueError(
                    f"model_default {self.model_default!r} is not a "
                    "configured model")
            n_wslots = int(weight_slots) if weight_slots else len(models)
            from .weights import WeightPool

            self._wpool: Optional["WeightPool"] = WeightPool(
                self.cfg, params, n_slots=n_wslots, sources=models,
                name=name, registry=self._reg,
                on_evict=self._on_model_evict)
            # The default model is the pool's template: adopted
            # pre-pinned so neither LRU pressure nor the idle sweep
            # can evict the tree self.params (warm/compile signatures)
            # aliases.
            self._default_wid = self._wpool.adopt(
                self.model_default, self.params, pin=True)
        else:
            if weight_slots or self.model_default:
                raise ValueError(
                    "weight_slots/model_default require models= "
                    "(name -> LM export dir)")
            self._wpool = None
            self._default_wid = -1
        self._last_idle_sweep = 0.0  # idle scale-to-zero rate limit

        # -- device state (touched only by the loop thread after start)
        self._cache = self._init_cache()
        # HBM a slot's recurrent state takes whatever its request's
        # length: the leaves indexed by slot (models/ssm.py), all
        # layers. 0 for a configuration whose rows are their pages.
        self.state_bytes_per_slot = sum(
            int(np.prod(x.shape[2:])) * x.shape[0] * x.dtype.itemsize
            for _, x in self._cache_leaves("ssm"))
        self._logbuf = self._init_logbuf()
        self._draft_cache = self._init_cache(draft=True) if self.spec \
            else None
        # -- host slot state (numpy mirrors round-tripped per chunk)
        B = n_slots
        self._tables = np.full((B, self.n_blocks), -1, np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(B)]
        # The window class: a row's table over the window pool, by
        # position, and the first block it may still hold.
        self._wtables = np.full((B, self.n_blocks), -1, np.int32)
        self._wfirst = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)       # next decode position
        self._loc = np.zeros((B,), np.int32)       # next decode write loc
        self._max_loc = np.zeros((B,), np.int32)   # last writable loc
        self._active = np.zeros((B,), np.bool_)
        self._produced = np.zeros((B,), np.int32)
        self._rngs = np.zeros((B, 2), np.uint32)
        self._temp = np.zeros((B,), np.float32)
        self._topk = np.zeros((B,), np.int32)
        self._stop = np.full((B,), -1, np.int32)
        self._max_new = np.zeros((B,), np.int32)
        self._slots: List[Optional[Request]] = [None] * B
        # Per-slot speculative state: the slot's draft block-table row
        # and pages, whether it still speculates (draft-pool shortfall
        # flips it off for the request's lifetime in this slot), and
        # the PENDING token — emitted to the client but not yet in
        # either KV pool; the next verify window writes it first.
        # -1 = no pending token yet (fresh admission samples one from
        # the prefill logits).
        self._draft_tables = np.full((B, self.n_blocks), -1, np.int32)
        self._draft_slot_pages: List[List[int]] = [[] for _ in range(B)]
        self._spec_ok = np.zeros((B,), np.bool_)
        self._pending = np.full((B,), -1, np.int32)
        # Per-slot adapter ids ([B] int32, -1 = base) — gathered into
        # every hot dispatch; the slot holds one AdapterPool reference
        # per id >= 0 for its lifetime.
        self._aids = np.full((B,), -1, np.int32)
        # Per-slot WEIGHT-pool slot ids ([B] int32, -1 = the engine's
        # resident params — non-pool mode). A slot holds one WeightPool
        # reference per id >= 0 for its lifetime; _decode_once groups
        # active slots by wid and dispatches each group with its own
        # param tree through the SAME compiled executable.
        self._wids = np.full((B,), -1, np.int32)
        # Chunked-prefill cursors: slot -> {"req", "full", "n",
        # "next" (absolute index of the next chunk's first token),
        # "key"/"reg_block" (incremental prefix-cache registration
        # state), "bucket", "remaining"}. A slot with a cursor holds
        # its request (``_slots[slot]`` set, so drain/occupancy/
        # heartbeat count it as in-flight) but is NOT ``_active`` —
        # the decode dispatch masks it until the cursor completes.
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        # Per-iteration decode-stall accumulator: seconds of prefill
        # dispatch (monolithic admission or one prompt chunk) active
        # decode slots waited on this iteration — what the
        # kfx_lm_decode_stall_seconds histogram observes.
        self._iter_stall = 0.0
        # Host seconds of the loop thread in the current iteration, by
        # phase (exclusive: a nested phase pauses the one around it),
        # and the stack of open phases as [name, since]. Flushed once
        # an iteration into kfx_lm_engine_host_seconds_total{phase} and
        # kfx_lm_engine_device_wait_seconds_total (loop thread only).
        self._phase_s: Dict[str, float] = defaultdict(float)
        self._phase_stack: List[List[Any]] = []

        # -- compiled executables (AOT, so a background warm populates
        # the same table the admission path reads — no jit-cache games)
        self._exec_lock = threading.Lock()
        self._prefill_exec: Dict[int, Any] = {}
        self._draft_prefill_exec: Dict[int, Any] = {}
        self._decode_exec: Any = None
        self._spec_exec: Any = None
        self._reset_exec: Any = None
        self._draft_reset_exec: Any = None
        self._window_reset_exec: Any = None
        self._copy_exec: Any = None
        self._gather_exec: Any = None
        self._scatter_exec: Any = None
        self._quant_chaos_exec: Any = None
        self._draft_quant_chaos_exec: Any = None

        # -- decode-loop progress heartbeat + drain mode. The heartbeat
        # is what turns /healthz into a real liveness probe: a wedged
        # loop (stuck dispatch, deadlock) leaves ``_last_progress``
        # stale while slots are active, which readiness alone can never
        # see — the HTTP server keeps answering fine.
        self.stall_threshold_s = float(stall_threshold_s)
        self._iterations = 0
        self._last_progress = time.monotonic()
        self._draining = False
        # AOT builds in progress (any thread). A cold prompt bucket
        # compiling INLINE on the loop thread stalls iterations for
        # longer than the threshold on big models, but it is slow, not
        # stuck — and a wedge-kill would just repeat the same compile
        # after respawn. The heartbeat suppresses the wedged verdict
        # while a build runs (a warm-thread build overlapping a real
        # wedge masks detection only until that build finishes).
        self._building = 0

        self._cond = threading.Condition()
        # Per-tenant fair admission (serving/adapters.py FairQueue):
        # requests queue under their adapter name and pop weighted
        # round-robin, so one adapter's burst queues behind itself —
        # the bounded queue, drain and overflow contracts are
        # unchanged (len() is the global depth).
        from .adapters import FairQueue

        self._queue = FairQueue(tenant_weights)
        # The request currently inside _admit (popped from the queue,
        # not yet in a slot): without tracking it, drain()/heartbeat()
        # would read an admitting engine as empty and the operator
        # could kill the replica mid-prefill.
        self._admitting: Optional[Request] = None
        # Flight recorder: one bounded ring of per-iteration state +
        # a recent-requests ring (obs/flightrec.py). Constructed before
        # the loop thread starts so the first iteration can record.
        # KFX_FLIGHT=0 leaves it None and every hook is skipped.
        from ..obs import flightrec as _flightrec

        self.flight = _flightrec.FlightRecorder() \
            if _flightrec.enabled_from_env() else None
        # Per-tenant usage ledger (serving/metering.py): exact prompt/
        # generated token counts by {tenant, qos, adapter}, billed on
        # the admission/retirement funnel. None disables every hook.
        from .metering import TenantLedger

        self.usage: Optional[TenantLedger] = TenantLedger()
        # Cumulative preemption count (loop thread) — mirrored into
        # every flight record so a postmortem can see preemption churn
        # without scraping metrics.
        self._preempts = 0
        self._stopped = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"kfx-engine-{name}")
        self._thread.start()
        self._touch_gauges()

    # -- metrics -------------------------------------------------------------
    def _reg(self) -> MetricsRegistry:
        r = self._registry
        if callable(r):
            return r()
        return r if r is not None else default_registry()

    @property
    def kv_bytes_per_token(self) -> int:
        """KV HBM per cached token: 2 (K+V) x layers x heads x head_dim
        x entry bytes, plus the page's position-id word amortized.
        Under int8 KV the entries are 1 byte each and the per-token K/V
        scale planes add 2 x layers f32 words — ~2x fewer bytes than
        bf16 entries, ~3.5-4x fewer than f32, which is exactly the
        concurrent-admission multiplier at a fixed pool byte budget
        (docs/serving.md HBM accounting)."""
        c = self.cfg
        if c.has_window_pages:
            # Both classes: what a token costs for as long as its row
            # lives, and what it costs while it lies inside the window.
            return sum(self.kv_bytes_per_token_by_class.values())
        if c.kv_lora_rank > 0 or c.has_slot_state:
            # Latent attention: what the leaves hold a token (latent +
            # rotary, the indexer's key, int8 scales), all layers. Slot
            # state: the attention layers' leaves alone.
            return sum(
                int(np.prod(x.shape[3:])) * x.shape[0] * x.dtype.itemsize
                for name, x in self._cache_leaves("attn")
                if name != "cached_pos")
        if c.kv_quant == "int8":
            return (2 * c.n_layers * c.kv_heads * c.head_dim
                    + 2 * c.n_layers * 4 + 4)
        item = np.dtype(c.dtype).itemsize
        return 2 * c.n_layers * c.kv_heads * c.head_dim * item + 4

    def _cache_leaves(self, module: str):
        """(leaf name, spec) of the cache leaves under ``module``
        ("attn": paged; "ssm": indexed by slot)."""
        import jax

        return [(getattr(path[-1], "key", ""), x) for path, x in
                jax.tree_util.tree_flatten_with_path(self._cache_specs())[0]
                if any(getattr(k, "key", "") == module for k in path)]

    @property
    def _window_runs(self) -> frozenset:
        """The runs whose cache leaves are the window class's pool."""
        return frozenset(name for name, kind, _ in self.cfg.layer_runs
                         if kind == "window")

    def _class_leaves(self, window: bool):
        """(leaf name, spec) of the paged leaves of one page class."""
        import jax

        runs = self._window_runs
        return [(getattr(path[-1], "key", ""), x) for path, x in
                jax.tree_util.tree_flatten_with_path(self._cache_specs())[0]
                if (getattr(path[0], "key", "") in runs) == window]

    @functools.cached_property
    def kv_bytes_per_token_by_class(self) -> Dict[str, int]:
        """``kv_bytes_per_token`` of each page class, {"full",
        "window"}: the entries and scales its layers hold a token (the
        position-id words left out, as for the latent cache)."""
        return {cls: sum(
            int(np.prod(x.shape[3:])) * x.shape[0] * x.dtype.itemsize
            for name, x in self._class_leaves(cls == "window")
            if name != "cached_pos") for cls in ("full", "window")}

    def slot_state(self, slot: int) -> Dict[str, np.ndarray]:
        """Host copies of what ``slot`` holds in the leaves indexed by
        slot, every state-space layer in the model's order: ``state``
        [layers, H, P, N] and ``conv`` [layers, taps x width]. A slot
        keeps what its last request left until another takes it (a
        sequence's first token starts from zeros: models/ssm.py).
        Read on the loop thread at an iteration boundary, where no
        dispatch holds the donated cache. What a snapshot of a row's
        state would start from (ROADMAP B-I.5); today the
        benchmark's comparison with its reference reads it."""
        if not self.cfg.has_slot_state:
            raise ValueError(
                f"engine {self.name} holds no slot state (no 'mamba' "
                "layers): a row's state is its pages")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} of {self.n_slots}")

        def read():
            out: Dict[str, List[np.ndarray]] = {}
            for run, kind, _ in self.cfg.layer_runs:
                if kind == "mamba":
                    for name, leaf in self._cache[run]["ssm"].items():
                        out.setdefault(name, []).append(
                            np.asarray(leaf[:, slot]))
            return {k: np.concatenate(v) for k, v in out.items()}

        return self._run_on_loop(read)

    def row_kv(self, slot: int, layer: int) -> Dict[str, np.ndarray]:
        """Host copies of the keys and values the live row in ``slot``
        holds in ``layer``'s pages, as its attention reads them (int8
        entries times their scales): ``positions`` [n] ascending,
        ``key`` and ``value`` [n, heads x head_dim]. A window layer's
        row holds what it has not given back. Read on the loop thread
        at an iteration boundary, like ``slot_state``; today the
        benchmark's comparison with its reference reads it."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} of {self.n_slots}")
        first = 0
        for run, kind, n in self.cfg.layer_runs:
            if first <= layer < first + n:
                break
            first += n
        else:
            raise ValueError(f"layer {layer} of {self.cfg.n_layers}")
        if self.page_size <= 0 or kind == "mamba" \
                or self.cfg.kv_lora_rank > 0:
            raise ValueError(
                f"layer {layer} of engine {self.name} holds no paged "
                "keys and values")

        def read():
            if self._slots[slot] is None:
                raise ValueError(f"no request is live in slot {slot}")
            table = (self._wtables if kind == "window"
                     else self._tables)[slot]
            pages = table[table >= 0]
            leaves = self._cache[run]["attn"]
            take = lambda name: np.asarray(
                leaves[name][layer - first, pages]).astype(np.float32)
            pos = np.asarray(leaves["cached_pos"][layer - first, pages]
                             ).reshape(-1)
            order = np.argsort(pos, kind="stable")[np.sum(pos < 0):]
            out = {"positions": pos[order]}
            for name, scale in (("key", "key_scale"),
                                ("value", "value_scale")):
                x = take("cached_" + name)
                x = x.reshape(-1, x.shape[-1])
                if scale in leaves:
                    x = x * take(scale).reshape(-1, 1)
                out[name] = x[order]
            return out

        return self._run_on_loop(read)

    def _quant_labels(self) -> Tuple[str, str]:
        """(weights, kv) label values for the ``kfx_lm_quant_mode``
        info gauge: ``int8``, ``draft-int8`` (only the speculative
        draft's weights are quantized) or ``f32``."""
        if self.cfg.quant == "int8":
            weights = "int8"
        elif self.spec and self.draft_cfg.quant == "int8":
            weights = "draft-int8"
        else:
            weights = "f32"
        return weights, self.cfg.kv_quant or "f32"

    @property
    def quant_mode(self) -> str:
        """Human-readable quantization mode: "w8" (int8 weights),
        "kv8" (int8 paged KV), "d8" (int8 draft only), joined with
        "+", or "f32" when nothing is quantized — the Q column in
        ``kfx top`` and the ``quant`` field of the server's JSON
        engine block."""
        return quant_mode_string(*self._quant_labels())

    def prefix_stats(self) -> Dict[str, int]:
        """Cumulative prefix-cache counters (zeros while the cache is
        off): prompt tokens admitted and tokens served from cached
        pages. Public surface for per-window deltas (the skipped
        fraction is tokens_reused / prompt_tokens)."""
        reused = self._prefix.tokens_reused if self._prefix is not None \
            else 0
        return {"tokens_reused": reused,
                "prompt_tokens": self._prompt_tokens}

    def spec_stats(self) -> Dict[str, int]:
        """Cumulative speculative-decode counters (zeros with the
        draft off): draft tokens proposed, proposals the target
        accepted, and slots degraded to non-speculative on draft-pool
        shortfall. Public surface for per-window deltas."""
        return {"proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "degraded": self._spec_degraded}

    def adapter_stats(self) -> Dict[str, int]:
        """Cumulative adapter-pool counters (zeros without a pool):
        artifact loads, LRU evictions, slot capacity and free slots.
        Public surface for per-window deltas."""
        if self._apool is None:
            return {"loads": 0, "evictions": 0, "slots": 0, "free": 0}
        return {"loads": self._apool.loads,
                "evictions": self._apool.evictions,
                "slots": self._apool.n_slots,
                "free": self._apool.n_free}

    def weight_stats(self) -> Dict[str, Any]:
        """Cumulative weight-pool counters (zeros without a pool):
        artifact swap-ins, evictions, slot capacity, free slots and
        the resident model names. Public surface for per-window deltas
        and the server's JSON engine block."""
        if self._wpool is None:
            return {"loads": 0, "evictions": 0, "slots": 0, "free": 0,
                    "loaded": []}
        return {"loads": self._wpool.loads,
                "evictions": self._wpool.evictions,
                "slots": self._wpool.n_slots,
                "free": self._wpool.n_free,
                "loaded": self._wpool.loaded()}

    def pooled_models(self) -> Dict[str, bool]:
        """{name: resident?} for every model the pool was configured
        with — the readiness/status surface behind
        ``status.pooledModels`` ("pooled but unloaded" is an explicit
        False, not an unknown name). Empty without a pool."""
        if self._wpool is None:
            return {}
        loaded = set(self._wpool.loaded())
        return {m: (m in loaded)
                for m in sorted(self._wpool.sources)}

    def evict_model(self, name: str) -> bool:
        """Explicitly evict ``name``'s weights from its pool slot (the
        operator's scale-to-zero push, or an admin drain). Runs on the
        decode-loop thread at an iteration boundary — slot state is
        loop-owned, exactly like KV-transfer surgery. False when the
        model is not resident, is worn by in-flight requests, or is
        the pinned default."""
        if self._wpool is None:
            return False
        return bool(self._run_on_loop(
            lambda: self._wpool.evict_model(name)))

    def hbm_bytes(self) -> Dict[str, int]:
        """Measured device-buffer accounting — actual array bytes, not
        estimates, valid on any backend: base weights, target/draft KV
        pools (entries + scale planes + position ids), the draft's
        truncated weights, the adapter stacks and the logits buffer.
        The multi-tenant headline divides ``total`` by a base-only
        engine's: N adapters over ONE base costs base + stacks, vs ~N
        bases for N merged deployments (docs/serving.md)."""
        import jax

        def nbytes(tree) -> int:
            return int(sum(
                int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                for x in jax.tree_util.tree_leaves(tree)))

        window = {k: v for k, v in self._cache.items()
                  if k in self._window_runs}
        out = {
            "params": nbytes(self.params),
            # (both page classes; the window runs' share is reported
            # beside the total below, not added to it)
            "kv_pool": nbytes(self._cache),
            "logits": nbytes(self._logbuf),
            "draft": (nbytes(self.draft_params)
                      + nbytes(self._draft_cache)) if self.spec else 0,
            "adapters": self._apool.nbytes()
            if self._apool is not None else 0,
            # Pooled checkpoints BEYOND the resident default (whose
            # tree aliases self.params and is counted there): the
            # marginal HBM cost of hosting N models on one replica.
            "weights": max(0, self._wpool.nbytes() - nbytes(self.params))
            if self._wpool is not None else 0,
        }
        out["total"] = sum(out.values())
        if window:
            out["kv_pool_window"] = nbytes(window)
        return out

    def _spec_accept_rate(self, window_s: float = 30.0) -> float:
        """Accepted/proposed over the trailing window (0 when idle or
        speculation is off) — a gauge, so a stale burst must decay
        instead of a last-iteration ratio sticking to /metrics."""
        now = time.monotonic()
        with self._spec_lock:
            while self._spec_window and \
                    self._spec_window[0][0] < now - window_s:
                self._spec_window.popleft()
            prop = sum(p for _, p, _ in self._spec_window)
            acc = sum(a for _, _, a in self._spec_window)
        return acc / prop if prop else 0.0

    def _occupancy(self) -> float:
        """Token-weighted occupancy: slot capacity (``n_slots``) scaled
        by the pool fraction active slots' pages actually pin. The old
        slot count read "full" for n_slots tiny requests even with 90%
        of KV HBM free, so the autoscaler over-scaled exactly when
        paging had created headroom. DISTINCT pages: prefix-shared
        pages appear in every sharer's list but pin one physical page
        — double-counting would read "full" exactly when sharing had
        created headroom."""
        held = len({pg for i, r in enumerate(self._slots)
                    if r is not None for pg in self._slot_pages[i]})
        share = held / float(self.n_pages)
        if self._wmgr is not None:   # the fuller class decides
            share = max(share, 1.0 - self._wmgr.n_free
                        / float(self.window_pages))
        return self.n_slots * share

    def _touch_gauges(self) -> None:
        reg = self._reg()
        reg.gauge("kfx_lm_slots",
                  "Decode-engine request slots (max concurrency).").set(
                      self.n_slots, model=self.name)
        reg.gauge("kfx_lm_slot_occupancy",
                  "Token-weighted engine load: slot capacity scaled by "
                  "the KV-page fraction active slots hold.").set(
                      round(self._occupancy(), 4), model=self.name)
        reg.gauge("kfx_lm_queue_depth",
                  "Requests waiting for a decode-engine slot.").set(
                      len(self._queue), model=self.name)
        # Per-QoS-class in-flight split (interactive vs batch slots) —
        # the `kfx top` I/B column's source; set for both classes so
        # the idle value is an explicit 0, not an absent series.
        by_cls = {"interactive": 0, "batch": 0}
        for r in self._slots:
            if r is not None:
                by_cls[r.qos] = by_cls.get(r.qos, 0) + 1
        for cls, cnt in by_cls.items():
            reg.gauge("kfx_lm_class_active",
                      "In-flight engine slots by QoS class "
                      "(interactive/batch).").set(
                          cnt, model=self.name, qos=cls)
        # Request-plane shed counters, seeded (inc 0) so a pre-traffic
        # `scrape_metrics --require` already sees the families.
        for family, help_text in self._SHED_HELP.items():
            reg.counter(family, help_text).inc(0, model=self.name)
        # One pool: the families as ever. Two page classes: each
        # family a series a class, and the pools' bytes beside them.
        by_class = {"": (self.n_pages, self._mgr.n_free)}
        if self._wmgr is not None:
            by_class = {"full": by_class[""],
                        "window": (self.window_pages, self._wmgr.n_free)}
        for cls, (pages, free) in by_class.items():
            labels = dict(model=self.name, **({"class": cls} if cls else {}))
            reg.gauge("kfx_lm_kv_pages",
                      "KV cache pages in the engine's pool.").set(
                          pages, **labels)
            reg.gauge("kfx_lm_kv_pages_free",
                      "KV cache pages on the free list.").set(
                          free, **labels)
        if self._wmgr is not None:
            for cls, per_token in self.kv_bytes_per_token_by_class.items():
                reg.gauge("kfx_lm_kv_pool_bytes",
                          "Bytes of a page class's pool (entries and "
                          "scales).").set(
                              by_class[cls][0] * self.page_size * per_token,
                              model=self.name, **{"class": cls})
            reg.counter("kfx_lm_window_pages_freed_total",
                        "Window-class pages given back because every "
                        "position in them lay behind the row's window."
                        ).inc(0, model=self.name)
        # KV transfer-plane families (serving/kvtransfer.py), seeded
        # so a pre-migration scrape already sees them: migrations by
        # reason, pages shipped/adopted, the host offload tier's
        # occupancy, and the end-to-end transfer timer.
        reg.counter("kfx_lm_kv_migrations_total",
                    "In-flight requests migrated to a peer replica, "
                    "by reason.").inc(0, model=self.name,
                                      reason="drain")
        reg.counter("kfx_lm_kv_pages_transferred_total",
                    "KV pages shipped to or adopted from peer "
                    "replicas.").inc(0, model=self.name)
        reg.gauge("kfx_lm_kv_offload_pages",
                  "Prefix-cache pages held per KV offload tier.").set(
                      len(self._offload)
                      if self._offload is not None else 0,
                      model=self.name, tier="host")
        reg.histogram("kfx_lm_kv_transfer_seconds",
                      "End-to-end KV transfer time (export snapshot "
                      "to peer acknowledgement).",
                      buckets=QUEUE_WAIT_BUCKETS).observe(
                          0.0, n=0, model=self.name)
        # Engine truth, not a derived number: capacity planning
        # reads pool bytes = kv_pages x page_size x this gauge.
        for cls, per_token in (
                self.kv_bytes_per_token_by_class.items()
                if self._wmgr is not None
                else (("", self.kv_bytes_per_token),)):
            reg.gauge("kfx_lm_kv_bytes_per_token",
                      "KV-cache bytes per cached token (entries + "
                      "quantization scales + position id).").set(
                          per_token, model=self.name,
                          **({"class": cls} if cls else {}))
        # What a slot holds beside pages (0 where rows are their pages).
        reg.gauge("kfx_lm_state_bytes_per_slot",
                  "Recurrent-state bytes a slot holds whatever its "
                  "request's length (leaves indexed by slot).").set(
                      self.state_bytes_per_slot, model=self.name)
        reg.gauge("kfx_lm_state_slots_in_use",
                  "Slots whose recurrent state belongs to a request in "
                  "flight.").set(
                      self._active_count() if self.cfg.has_slot_state
                      else 0, model=self.name)
        # Info-style gauge: constant 1, the mode rides the labels (the
        # Prometheus _info idiom) — alerts join on weights/kv instead
        # of parsing a free-form string.
        wmode, kvmode = self._quant_labels()
        reg.gauge("kfx_lm_quant_mode",
                  "Quantization mode info gauge (value is constant 1; "
                  "weights/kv labels carry the mode).").set(
                      1, model=self.name, weights=wmode, kv=kvmode)
        # Seed the hit counter (inc 0) so --require scrapes see the
        # family before the first warm-cache admission.
        reg.counter("kfx_lm_prefix_cache_hits_total",
                    "Admissions that reused cached prefix pages.").inc(
                        0, model=self.name)
        # Prefix-reuse token totals as gauges (engine-host truth): the
        # server's JSON engine block exposes them per replica, and the
        # FLEET-level prefill_skipped_frac = sum(reused)/sum(admitted)
        # across replicas — the number prefix-affinity routing exists
        # to move (docs/serving.md).
        st = self.prefix_stats()
        reg.gauge("kfx_lm_prefix_tokens_reused",
                  "Prompt tokens served from cached prefix pages "
                  "(cumulative).").set(
                      st["tokens_reused"], model=self.name)
        reg.gauge("kfx_lm_prompt_tokens_admitted",
                  "Prompt tokens admitted (cumulative; denominator of "
                  "the prefill-skipped fraction).").set(
                      st["prompt_tokens"], model=self.name)
        # The layers' and the sampler's families exist (at 0) for every
        # configuration; they grow in _flush_counts.
        for family, text in _COUNT_FAMILIES.items():
            reg.counter(family, text).inc(0, model=self.name)
            if family in _DECODE_TWINS:
                reg.counter(_DECODE_TWINS[family], text + " Decode chunks "
                            "alone.").inc(0, model=self.name)
        # Chunked-prefill families, pre-seeded (counter at 0; the
        # histogram family registered with a zero-count observe) so a
        # pre-traffic `scrape_metrics --require` already sees them.
        reg.counter("kfx_lm_prefill_chunks_total",
                    "Prompt-chunk prefill dispatches (chunked "
                    "admission).").inc(0, model=self.name)
        reg.histogram("kfx_lm_decode_stall_seconds",
                      "Seconds active decode slots waited on a prefill "
                      "dispatch, per engine iteration.",
                      buckets=QUEUE_WAIT_BUCKETS).observe(
                          0.0, n=0, model=self.name)
        # Adapter families, seeded iff the engine HAS an adapter pool
        # (their absence marks a base-only engine, the same contract
        # as the speculative families below): slot gauges for `kfx
        # top`'s ADPT column and capacity planning, load/eviction
        # counters for paging churn, the fallback counter for the
        # chaos degrade path, and the per-tenant request counter.
        if self._apool is not None:
            reg.gauge("kfx_lm_adapter_slots",
                      "HBM adapter slots (stacked LoRA A/B capacity)."
                      ).set(self._apool.n_slots, model=self.name)
            reg.gauge("kfx_lm_adapter_slots_free",
                      "Adapter slots not pinned by in-flight requests "
                      "(free + loaded-but-idle LRU candidates).").set(
                          self._apool.n_free, model=self.name)
            reg.counter("kfx_lm_adapter_loads_total",
                        "Adapters paged into HBM slots from the "
                        "artifact store.").inc(0, model=self.name)
            reg.counter("kfx_lm_adapter_evictions_total",
                        "Adapters evicted from HBM slots (LRU paging)."
                        ).inc(0, model=self.name)
            reg.counter("kfx_lm_adapter_fallbacks_total",
                        "Requests degraded to base-only after an "
                        "adapter load failure (adapters.fallback="
                        "base).").inc(0, model=self.name)
            reg.counter("kfx_lm_adapter_requests_total",
                        "Admitted client requests by adapter tenant."
                        ).inc(0, model=self.name, adapter="base")
        # Weight-pool families are seeded iff the engine HAS a pool
        # (their absence marks a single-model engine): slot gauges for
        # `kfx top`'s MODELS column, swap/load/eviction families for
        # the scale-from-zero story, and per-model residency gauges
        # the operator folds into status.pooledModels.
        if self._wpool is not None:
            self._wpool.touch()
        # Speculative families are seeded iff the engine HAS a draft —
        # their absence is the signal (the server's JSON engine block
        # omits spec_accept_rate and `kfx top` renders "-", never a
        # "0%" indistinguishable from a draft accepting nothing).
        if self.spec:
            reg.counter("kfx_lm_spec_proposed_total",
                        "Draft tokens proposed to the verify dispatch."
                        ).inc(0, model=self.name)
            reg.counter("kfx_lm_spec_accepted_total",
                        "Draft proposals the target model accepted."
                        ).inc(0, model=self.name)
            reg.gauge("kfx_lm_spec_accept_rate",
                      "Draft acceptance rate over the trailing 30s "
                      "window (0 when idle).").set(
                          round(self._spec_accept_rate(), 4),
                          model=self.name)
        if self.flight is not None:
            reg.gauge("kfx_lm_flight_ring_records",
                      "Iteration records currently held in the flight "
                      "recorder ring (caps at KFX_FLIGHT_RING).").set(
                          len(self.flight), model=self.name)

    def _active_count(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    # -- liveness / drain ----------------------------------------------------
    def heartbeat(self) -> Dict[str, Any]:
        """Decode-loop progress snapshot (server /healthz liveness
        input): monotonic iteration counter, seconds since the loop
        last completed an iteration, whether there is work the loop
        SHOULD be advancing (active slots or queued requests), and the
        derived ``wedged`` verdict — stale progress while busy. An idle
        engine is never wedged: the loop parks on its condition
        variable, and ``_enqueue`` re-stamps progress at wake so the
        parked interval can't read as a stall."""
        now = time.monotonic()
        with self._cond:
            busy = (self._active_count() > 0 or len(self._queue) > 0
                    or self._admitting is not None or bool(self._owed))
        stalled_s = now - self._last_progress
        compiling = self._building > 0
        return {
            "iterations": self._iterations,
            "stalled_s": round(stalled_s, 3),
            "busy": busy,
            "compiling": compiling,
            "draining": self._draining,
            "wedged": (busy and not compiling
                       and stalled_s > self.stall_threshold_s),
        }

    def drain(self, wait_s: float = 0.0) -> bool:
        """Enter drain mode: stop admitting (submit/generate raise
        EngineDraining -> 503 + Retry-After), resolve every QUEUED
        request with the same retriable error (the router re-dispatches
        them to a healthy replica), and let the slots already decoding
        run to completion. Blocks up to ``wait_s`` for in-flight work
        to finish; returns True when the engine is empty. One-way: the
        operator calls this right before killing the replica."""
        with self._cond:
            self._draining = True
            queued = self._queue.drain_all()
            self._cond.notify_all()
        err = EngineDraining(
            f"engine {self.name} is draining; retry another replica")
        for req in queued:
            req._finish(err)
        self._touch_gauges()
        deadline = time.monotonic() + max(wait_s, 0.0)
        while True:
            with self._cond:
                # A preemption-by-recompute mid-drain re-queues its
                # request, and a request mid-admission is in a slot in
                # all but timing; both are in-flight work, not new
                # admissions, so drain waits for them too, and for the
                # last chunk's tokens to be handed out (_owed).
                empty = (self._active_count() == 0 and not self._queue
                         and self._admitting is None
                         and not self._owed)
            if empty or time.monotonic() >= deadline:
                return empty
            time.sleep(0.02)

    @property
    def draining(self) -> bool:
        return self._draining

    def _maybe_wedge(self) -> None:
        """Chaos point ``engine.wedge``: stall the decode loop with
        slots active (drawn only when there is work, so the budget is
        spent on a stall liveness can actually see). The stall holds
        ``rule.delay`` seconds (default 30) without touching the
        heartbeat — exactly what a stuck device dispatch looks like to
        the rest of the process. ``close()`` still wins: the stall
        polls ``_stopped``."""
        inj = chaos.draw("engine.wedge", target=self.name)
        if inj is None:
            return
        # The stall hits mid-iteration, before the end-of-loop flight
        # append — record the in-flight iteration first so the ring's
        # last entry shows what was on the device when the loop hung
        # (the record a postmortem needs; its ``it`` matches the frozen
        # heartbeat counter). What the last chunk owes goes out ahead
        # of it: its tokens are not held by the stall, and its record
        # precedes this one.
        self._pay_owed(overlapped=False)
        if self.flight is not None:
            self._record_flight()
        stall = inj.delay if inj.delay > 0 else 30.0
        deadline = time.monotonic() + stall
        while time.monotonic() < deadline and not self._stopped:
            time.sleep(0.05)

    # -- cache / compiled functions ------------------------------------------
    def _init_cache(self, draft: bool = False):
        """The empty paged cache pytree (positions -1 = every page
        empty). ``draft=True`` builds the draft model's pool (fewer
        layers, its own page count, same page geometry)."""
        from ..models.transformer import init_cache

        return init_cache(self.draft_cfg if draft else self.cfg)

    def _init_logbuf(self):
        import jax.numpy as jnp

        return jnp.zeros((self.n_slots, self.cfg.vocab_size), np.float32)

    def _cache_specs(self, draft: bool = False):
        import jax

        cache = self._draft_cache if draft else self._cache
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)

    def _lora_tree(self, draft: bool = False):
        """The adapter A/B stack pytree every hot dispatch takes as an
        ARGUMENT (the pool mutates it when paging adapters, so it can
        never be a compile-time constant). Empty dict without a pool —
        a zero-leaf jit arg, so adapterless engines trace the exact
        pre-adapter program."""
        if self._apool is None:
            return {}
        return self._apool.draft_tree if draft else self._apool.tree

    def _lora_specs(self, draft: bool = False):
        import jax

        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self._lora_tree(draft))

    # A model program's block tables: one array [rows, blocks], or,
    # with a second page class, the pair (full, window), each class's
    # table over its own pool. The three below are the spec, the
    # model's keywords (inside a program) and the host's argument.
    def _table_specs(self, rows: int):
        import jax

        one = jax.ShapeDtypeStruct((rows, self.n_blocks), np.int32)
        return (one, one) if self.cfg.has_window_pages else one

    def _tables_kw(self, tables):
        if not self.cfg.has_window_pages:
            return {"block_tables": tables}
        return {"block_tables": tables[0], "window_tables": tables[1]}

    def _tables_arg(self, slot: Optional[int] = None):
        pick = (lambda t: np.ascontiguousarray(t)) if slot is None \
            else (lambda t: np.ascontiguousarray(t[slot])[None, :])
        if self._wmgr is None:
            return pick(self._tables)
        # Both tables as copies: a row's change as soon as the program
        # is enqueued (_free_behind_window; the next prompt chunk's
        # pages), and a backend may read the host's array in place,
        # after the call has returned (the CPU's does: a lone row's
        # prompt chunks, enqueued one behind the other, then read the
        # tables of a later chunk).
        return np.array(pick(self._tables)), np.array(pick(self._wtables))

    @staticmethod
    def _named(fn, what: str):
        """``fn`` under kfx's own name, which ``jax.jit`` gives the
        compiled program: ``jit_run_kfx_<what>`` in a profiler trace,
        in place of one ``jit_run`` for every program. The ``run_``
        stays in front while benchmark/layer_metrics still picks the
        engine's programs by ``jit_run`` and rank (decode_step_ms,
        prefill_chunk_ms, decode_hbm_pct); the readers by name match
        ``kfx_<what>`` with or without it."""
        fn.__name__ = fn.__qualname__ = f"run_kfx_{what}"
        return fn

    def _report_attend(self, program: str, batch: int, window: int,
                       draft: bool = False) -> None:
        """``kfx_lm_attend_positions{model,program}``: the K/V
        positions every query row of a compiled program scores, set
        when the program is built — the whole pool (``kv_pages x
        page_size``) where the model attends it in place, the row's
        gathered view (``max_seq_len``) where it gathers
        (models/transformer.py ``attends_pool_in_place``; the choice
        is per program, made from its shapes). Beside
        ``kfx_lm_kv_pages_free`` it says when the in-place form pays
        for an empty pool: its cost follows the pool's size, not the
        live tokens."""
        from ..models.transformer import attends_pool_in_place, score_bytes

        cfg = self.draft_cfg if draft else self.cfg
        in_place = attends_pool_in_place(
            batch, cfg.max_seq_len, cfg.kv_pages, cfg.kv_page_size,
            score_bytes(cfg, window))
        self._reg().gauge(
            "kfx_lm_attend_positions",
            "K/V positions a query row scores in a compiled program "
            "(the pool's slots in place, max_seq_len gathered).").set(
                cfg.kv_pages * cfg.kv_page_size if in_place
                else cfg.max_seq_len,
                model=self.name, program=program)

    def _report_temp(self, program: str, compiled):
        """``kfx_lm_program_temp_bytes{model,program}``: the scratch
        memory the compiler gave a model program beside its arguments
        and results (``memory_analysis().temp_size_in_bytes``), set
        when the program is built. The pools are arguments aliased to
        results, so a program that writes them in place holds none of
        their bytes here; one that restacks them holds a second copy
        of every pool. Returns ``compiled``."""
        stats = compiled.memory_analysis()
        if stats is not None:
            self._reg().gauge(
                "kfx_lm_program_temp_bytes",
                "Temporary device bytes of a compiled model program "
                "(beside its arguments and results).").set(
                    stats.temp_size_in_bytes, model=self.name,
                    program=program)
        return compiled

    def _build(self, build_fn, *args):
        """Run one AOT build under the ``_building`` marker so the
        liveness heartbeat can tell "slow: compiling" from "stuck".
        The counter is lock-guarded: the background warm thread and
        the loop's on-demand compiles run this concurrently, and an
        unsynchronized +=/-= could lose an update — leaving the flag
        stuck >0 (wedge detection silently disabled) or negative (a
        legitimate inline compile killed as wedged)."""
        with self._exec_lock:
            self._building += 1
        try:
            return build_fn(*args)
        finally:
            with self._exec_lock:
                self._building -= 1

    def _prefill_for(self, P: int):
        """The AOT-compiled prefill executable for prompt-tail bucket P
        (compile-on-demand; the warm thread populates the same table)."""
        with self._exec_lock:
            fn = self._prefill_exec.get(P)
        if fn is not None:
            return fn
        fn = self._build(self._build_prefill, P)
        with self._exec_lock:
            return self._prefill_exec.setdefault(P, fn)

    def _build_prefill(self, P: int):
        import jax
        import jax.numpy as jnp

        model, counted = self.model, self._counted
        mutable = ["cache"] + (["counts"] if counted else [])
        # The leaves indexed by slot are read and written at the
        # prompt's slot; the decode chunk's row i is slot i.
        slot_state = self.cfg.has_slot_state
        tables_kw = self._tables_kw

        def run(params, cache, logbuf, tokens, table, slot, true_len,
                start, lora, aid):
            """tokens [1, P] right-padded prompt TAIL starting at
            absolute position ``start`` (0 for a cache miss; the
            matched prefix length on a hit — earlier positions are
            read from shared pages through the block table). Writes
            land directly in the pool pages ``table`` maps, plus the
            last real token's logits at ``logbuf[slot]``. Pads carry
            position -1: their writes are dropped and they are masked
            out of every attention, so padding never changes the
            numbers (the LMGenerator contract, unchanged). ``aid``
            [1] is the slot's adapter id: prompt KV is ADAPTER KV —
            the k/v projections wear the adapter, which is why the
            prefix cache chains per adapter."""
            pos = jnp.arange(P, dtype=jnp.int32)[None, :]
            pos = jnp.where(pos < true_len, start + pos, -1)
            logits, vars_ = model.apply(
                {"params": params, "cache": cache}, tokens,
                positions=pos, lora=lora,
                adapter_ids=aid, mutable=mutable, **tables_kw(table), **(
                    {"slots": slot[None]} if slot_state else {}))
            last = jax.lax.dynamic_slice_in_dim(
                logits, true_len - 1, 1, axis=1)[0, 0]  # [V]
            logbuf = jax.lax.dynamic_update_slice_in_dim(
                logbuf, last[None, :].astype(logbuf.dtype), slot, axis=0)
            return (vars_["cache"], logbuf) + tuple(
                vars_["counts"][what][0] for what in counted)

        donate = (1, 2) if self._donate else ()
        specs = (
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.params),
            self._cache_specs(),
            jax.ShapeDtypeStruct((self.n_slots, self.cfg.vocab_size),
                                 np.float32),
            jax.ShapeDtypeStruct((1, P), np.int32),
            self._table_specs(1),
            jax.ShapeDtypeStruct((), np.int32),
            jax.ShapeDtypeStruct((), np.int32),
            jax.ShapeDtypeStruct((), np.int32),
            self._lora_specs(),
            jax.ShapeDtypeStruct((1,), np.int32),
        )
        self._report_attend(f"prefill_{P}", 1, P)
        return self._report_temp(f"prefill_{P}", jax.jit(
            self._named(run, f"prefill_{P}"),
            donate_argnums=donate).lower(*specs).compile())

    def _decode(self):
        with self._exec_lock:
            fn = self._decode_exec
        if fn is not None:
            return fn
        fn = self._build(self._build_decode)
        with self._exec_lock:
            if self._decode_exec is None:
                self._decode_exec = fn
            return self._decode_exec

    def _build_decode(self):
        import jax
        import jax.numpy as jnp

        from ..models.generate import sample_needs, sample_rows

        model, k = self.model, self.chunk_tokens
        counted = self._counted
        mutable = ["cache"] + (["counts"] if counted else [])
        tables_kw = self._tables_kw

        def run(params, cache, logbuf, tables, pos, loc, active,
                produced, rngs, temp, topk, stop, max_new, lora, aids):
            def step(carry, _):
                cache, logits, pos, loc, active, produced, rngs = carry
                split = jax.vmap(jax.random.split)(rngs)  # [B, 2, 2]
                next_rngs, sub = split[:, 0], split[:, 1]
                # The shared sampler, per-slot RNG stream AND per-slot
                # client knobs (two requests in one chunk may ask for
                # different temperatures), in the form this step's
                # active rows need: all greedy, the argmax alone.
                tok = sample_rows(logits, sub, temp, topk, active)  # [B]
                draws, sorts = sample_needs(temp, topk, active)
                is_stop = (stop >= 0) & (tok == stop)
                # The stop token itself is never emitted: the slot
                # retires and the request returns the tokens before it.
                emit = active & (~is_stop)
                produced2 = produced + emit.astype(jnp.int32)
                active2 = emit & (produced2 < max_new)
                # Inactive slots feed a masked dummy step: position -1
                # keeps their query row fully masked and location -1
                # drops their cache writes, so a retired slot's garbage
                # can never reach an active slot. Writes land at the
                # DENSE-EQUIVALENT location (prompt bucket + step), so
                # the logical layout — pad gaps included — reproduces
                # the one-shot oracle's cache byte-for-byte.
                feed = jnp.where(active, tok, 0)
                eff_pos = jnp.where(active, pos, -1).astype(jnp.int32)
                eff_loc = jnp.where(active, loc, -1).astype(jnp.int32)
                logits2, vars_ = model.apply(
                    {"params": params, "cache": cache}, feed[:, None],
                    positions=eff_pos[:, None], **tables_kw(tables),
                    write_locations=eff_loc[:, None], lora=lora,
                    adapter_ids=aids, mutable=mutable)
                # The logits CARRY is active-gated like the cache
                # writes: an inactive row's dummy step produced
                # garbage logits, and in weight-pool mode "inactive"
                # includes every slot of the OTHER groups — letting
                # the dummy logits through would overwrite a masked
                # slot's pending next-token logits with values from a
                # foreign model's dispatch.
                logits3 = jnp.where(active[:, None],
                                    logits2[:, 0], logits)
                pos2 = jnp.where(active, pos + 1, pos)
                loc2 = jnp.where(active, loc + 1, loc)
                out = (tok, emit) + tuple(
                    vars_["counts"][what][0] for what in counted) + (
                        jnp.stack([True, draws, sorts]).astype(jnp.int32),)
                return ((vars_["cache"], logits3, pos2, loc2,
                         active2, produced2, next_rngs), out)

            carry = (cache, logbuf, pos, loc, active, produced, rngs)
            carry, (toks, emits, *counts) = jax.lax.scan(
                step, carry, None, length=k)
            cache, logbuf, pos, loc, active, produced, rngs = carry
            return (cache, logbuf, pos, loc, active, produced, rngs,
                    toks, emits) + tuple(c.sum(0) for c in counts)

        donate = (1, 2) if self._donate else ()
        B, V = self.n_slots, self.cfg.vocab_size
        sds = jax.ShapeDtypeStruct
        specs = (
            jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                   self.params),
            self._cache_specs(),
            sds((B, V), np.float32),
            self._table_specs(B),     # block tables
            sds((B,), np.int32),      # pos
            sds((B,), np.int32),      # loc
            sds((B,), np.bool_),      # active
            sds((B,), np.int32),      # produced
            sds((B, 2), np.uint32),   # rngs
            sds((B,), np.float32),    # temp
            sds((B,), np.int32),      # topk
            sds((B,), np.int32),      # stop
            sds((B,), np.int32),      # max_new
            self._lora_specs(),
            sds((B,), np.int32),      # adapter ids
        )
        self._report_attend("decode_chunk", B, 1)
        return self._report_temp("decode_chunk", jax.jit(
            self._named(run, "decode_chunk"),
            donate_argnums=donate).lower(*specs).compile())

    def _reset_fn(self, draft: bool = False, window: bool = False):
        """Compiled page invalidation: sets cached position ids to -1
        for every page selected by a [n_pages] mask (ONE compile per
        pool; the mask is data). Recycled pages pass through here
        before reuse, so a new tenant can never attend a previous
        request's KV — in either pool. ``window``: the window class's
        pool (its runs' leaves, its page count); the other call leaves
        those alone."""
        attr = "_draft_reset_exec" if draft else \
            "_window_reset_exec" if window else "_reset_exec"
        window_runs = self._window_runs
        with self._exec_lock:
            fn = getattr(self, attr)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        def run(cache, mask):
            flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
            leaves = []
            for path, leaf in flat:
                name = getattr(path[-1], "key", str(path[-1]))
                if name == "cached_pos" and window == (
                        getattr(path[0], "key", "") in window_runs):
                    leaf = jnp.where(mask[None, :, None], -1, leaf)
                leaves.append(leaf)   # ^ [layers, N, P]
            return jax.tree_util.tree_unflatten(treedef, leaves)

        n = self.draft_n_pages if draft else \
            self.window_pages if window else self.n_pages
        donate = (0,) if self._donate else ()
        specs = (self._cache_specs(draft),
                 jax.ShapeDtypeStruct((n,), np.bool_))
        fn = self._build(
            jax.jit(self._named(run, "kv_reset_window" if window
                                else "kv_reset"),
                    donate_argnums=donate).lower(*specs).compile)
        with self._exec_lock:
            if getattr(self, attr) is None:
                setattr(self, attr, fn)
            return getattr(self, attr)

    def _quant_chaos_fn(self, draft: bool = False):
        """Compiled worst-case-scale injection for the
        ``engine.kv_quant`` chaos point (int8 KV only): zeroes the
        pool's K/V scale planes, so every already-cached entry
        dequantizes to 0 — the maximum possible quantization error, as
        if the write-time scales had collapsed. Structured state
        (position ids, block tables, page refcounts) is untouched:
        quality and accept rate degrade observably, but nothing can
        crash or leak, and entries written AFTER the injection carry
        fresh correct scales, so the engine self-heals as decode
        advances (the caller also drops the prefix cache: cached
        prompt pages are never rewritten while cached, so they would
        otherwise stay corrupted past the injection budget)."""
        attr = "_draft_quant_chaos_exec" if draft else "_quant_chaos_exec"
        with self._exec_lock:
            fn = getattr(self, attr)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        def run(cache):
            flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
            leaves = []
            for path, leaf in flat:
                name = getattr(path[-1], "key", str(path[-1]))
                if name in ("key_scale", "value_scale"):
                    leaf = jnp.zeros_like(leaf)
                leaves.append(leaf)
            return jax.tree_util.tree_unflatten(treedef, leaves)

        donate = (0,) if self._donate else ()
        fn = self._build(jax.jit(
            self._named(run, "kv_quant_chaos"),
            donate_argnums=donate).lower(
                self._cache_specs(draft)).compile)
        with self._exec_lock:
            if getattr(self, attr) is None:
                setattr(self, attr, fn)
            return getattr(self, attr)

    def _maybe_kv_quant_chaos(self) -> None:
        """Draw the ``engine.kv_quant`` point once per hot iteration
        while the pool is int8 — a hit crushes BOTH pools' scale
        planes (docs/chaos.md)."""
        if self.cfg.kv_quant != "int8":
            return
        inj = chaos.draw("engine.kv_quant", target=self.name)
        if inj is None:
            return
        if inj.delay > 0:
            time.sleep(inj.delay)
        if inj.mode == "delay":
            return
        self._cache = self._quant_chaos_fn()(self._cache)
        if self._prefix is not None:
            # The crush corrupts CACHED prompt pages too, and a cached
            # page is never rewritten while cached — drop the whole
            # prefix cache so the corruption cannot outlive the
            # injection through future admissions (freed pages land on
            # the dirty set and are position-invalidated before reuse;
            # live slots keep their own refs and stay degraded only
            # for their own lifetime, which IS the injected fault).
            self._prefix.drop_all()
        if self.spec:
            self._draft_cache = self._quant_chaos_fn(draft=True)(
                self._draft_cache)

    def _copy_fn(self):
        """Compiled copy-on-write: clones page ``src`` into ``dst``
        keeping only the first ``keep`` token slots valid (positions
        past the matched prefix are stamped -1, so the source's later
        tokens can never leak into the borrowing request)."""
        with self._exec_lock:
            fn = self._copy_exec
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        ps = self.page_size

        def run(cache, dst, src, keep):
            flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
            leaves = []
            for path, leaf in flat:
                name = getattr(path[-1], "key", str(path[-1]))
                row = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)
                if name == "cached_pos":  # [layers, 1, P]
                    valid = jnp.arange(ps, dtype=jnp.int32)[None, None, :]
                    row = jnp.where(valid < keep, row, -1)
                leaves.append(jax.lax.dynamic_update_slice_in_dim(
                    leaf, row, dst, axis=1))
            return jax.tree_util.tree_unflatten(treedef, leaves)

        donate = (0,) if self._donate else ()
        sds = jax.ShapeDtypeStruct
        specs = (self._cache_specs(), sds((), np.int32),
                 sds((), np.int32), sds((), np.int32))
        fn = self._build(
            jax.jit(self._named(run, "kv_copy"),
                    donate_argnums=donate).lower(*specs).compile)
        with self._exec_lock:
            if self._copy_exec is None:
                self._copy_exec = fn
            return self._copy_exec

    def _draft_prefill_for(self, P: int):
        """The draft-pool prefill executable for FULL-prompt bucket P.
        The draft shares no prefix cache (its pages die with the slot),
        so it always prefills the whole prompt — cheap at draft depth,
        and it keeps the two pools' logical layouts identical."""
        with self._exec_lock:
            fn = self._draft_prefill_exec.get(P)
        if fn is not None:
            return fn
        fn = self._build(self._build_draft_prefill, P)
        with self._exec_lock:
            return self._draft_prefill_exec.setdefault(P, fn)

    def _build_draft_prefill(self, P: int):
        import jax
        import jax.numpy as jnp

        model = self.draft_model

        def run(dparams, dcache, tokens, table, true_len, dlora, aid):
            """tokens [1, P] right-padded FULL prompt. Writes the
            prompt's draft KV through the slot's draft block table; no
            logits are kept — the propose scan always starts by
            feeding the pending token, so the draft never samples from
            its prefill logits. The draft wears the SAME adapter as
            the target (truncated stacks) so draft KV and proposals
            stay in-distribution — a wrong draft costs only accept
            rate, but a free one is free."""
            pos = jnp.arange(P, dtype=jnp.int32)[None, :]
            pos = jnp.where(pos < true_len, pos, -1)
            _, vars_ = model.apply(
                {"params": dparams, "cache": dcache}, tokens,
                positions=pos, block_tables=table, lora=dlora,
                adapter_ids=aid, mutable=["cache"])
            return vars_["cache"]

        donate = (1,) if self._donate else ()
        specs = (
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.draft_params),
            self._cache_specs(draft=True),
            jax.ShapeDtypeStruct((1, P), np.int32),
            jax.ShapeDtypeStruct((1, self.n_blocks), np.int32),
            jax.ShapeDtypeStruct((), np.int32),
            self._lora_specs(draft=True),
            jax.ShapeDtypeStruct((1,), np.int32),
        )
        self._report_attend(f"draft_prefill_{P}", 1, P, draft=True)
        return self._report_temp(f"draft_prefill_{P}", jax.jit(
            self._named(run, f"draft_prefill_{P}"),
            donate_argnums=donate).lower(*specs).compile())

    def _spec_step(self):
        with self._exec_lock:
            fn = self._spec_exec
        if fn is not None:
            return fn
        fn = self._build(self._build_spec_step)
        with self._exec_lock:
            if self._spec_exec is None:
                self._spec_exec = fn
            return self._spec_exec

    def _build_spec_step(self):
        """ONE fused compiled iteration of speculative decode (one
        device dispatch per k+1 candidate tokens):

          1. draft-propose: k single-token draft steps from the
             pending token, sampling with each slot's own knobs/RNG
             stream and writing draft KV at the dense-equivalent
             locations;
          2. verify: the target scores [pending, d_1..d_k] as ONE
             multi-token window against the paged cache (writes land
             before the gather; the position-causal mask makes window
             self-attention exact — models/transformer.py);
          3. accept: Leviathan residual sampling per slot — accept d_i
             while U_i < min(1, p_i(d_i)/q_i(d_i)); the first
             rejection (or the k+1 bonus) samples the normalized
             residual max(p - q, 0), with q == 0 for the bonus, for
             non-speculating slots and for capacity-forced
             boundaries, making plain target sampling the same code
             path. temperature<=0 turns p into one-hot argmax, so
             greedy acceptance IS exact-match and the emitted tokens
             are the target's greedy chain, byte-identical to the
             oracle;
          4. rollback: rejected-tail entries (window index > accepted)
             have their cached position ids stamped -1 in BOTH pools —
             the same location math as the writes, so every
             speculative write is either kept or dead, never stale;
          5. draft catch-up: a masked draft step writes whatever the
             new cursor's last token is missing from the draft pool so
             the two pools stay validity-identical.

        Returns (cache, draft_cache, rngs, proposals [B,k],
        accepted [B], bonus [B])."""
        import jax
        import jax.numpy as jnp

        from ..models.generate import sample_rows

        model, draft_model = self.model, self.draft_model
        B, k = self.n_slots, self.propose_tokens
        V = self.cfg.vocab_size

        def warp(logits, temp, topk):
            """Per-slot warped next-token probs [B, S, V]: temperature
            + top-k masking, softmax; temperature<=0 -> one-hot argmax
            (the greedy limit — what makes greedy acceptance an exact
            argmax match). Mirrors models/generate._sample exactly."""
            greedy = jax.nn.one_hot(jnp.argmax(logits, -1), V,
                                    dtype=jnp.float32)
            scaled = logits / jnp.maximum(temp, 1e-6)[:, None, None]
            srt = jnp.sort(scaled, axis=-1)
            idx = jnp.maximum(V - topk, 0).astype(jnp.int32)
            kth = jnp.take_along_axis(
                srt, jnp.broadcast_to(idx[:, None, None],
                                      scaled.shape[:-1] + (1,)), axis=-1)
            masked = jnp.where((topk > 0)[:, None, None]
                               & (scaled < kth), -jnp.inf, scaled)
            probs = jax.nn.softmax(masked.astype(jnp.float32), -1)
            return jnp.where((temp <= 0.0)[:, None, None], greedy, probs)

        def invalidate(cache, tables, locs):
            """Stamp cached position ids -1 at per-slot locations
            ``locs`` [B, k+1] (-1 = skip) — identical location math to
            the writes (same table lookup, same clamping), so exactly
            the entries the window wrote are killed."""
            P = self.page_size
            ok = locs >= 0
            blk = jnp.where(ok, locs // P, 0)
            page = jnp.take_along_axis(tables, blk, axis=1)
            pg = jnp.where(ok & (page >= 0), page, -1)
            sl = jnp.where(ok, locs % P, 0)
            flat_pg = pg.reshape(-1)
            flat_sl = sl.reshape(-1)
            flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
            leaves = []
            for path, leaf in flat:
                name = getattr(path[-1], "key", str(path[-1]))
                if name == "cached_pos":  # [layers, N, P]
                    n = leaf.shape[1]
                    tgt = jnp.where(flat_pg >= 0, flat_pg, n)
                    leaf = leaf.at[:, tgt, flat_sl].set(-1, mode="drop")
                leaves.append(leaf)
            return jax.tree_util.tree_unflatten(treedef, leaves)

        def run(params, dparams, cache, dcache, tables, dtables,
                pending, pos, loc, max_loc, spec_on, draft_live,
                active, rngs, temp, topk, lora, dlora, aids):
            # spec_on: this iteration proposes/accepts for the slot;
            # draft_live: the slot HOLDS draft pages (spec_on implies
            # draft_live; a chaos full-rejection wave clears spec_on
            # only, and the catch-up step below keeps the draft pool's
            # validity aligned with the target's so the wave costs
            # throughput, never accept-rate after it ends).
            steps = jnp.arange(k + 1, dtype=jnp.int32)

            # -- 1. draft propose (k steps; masked for non-spec slots)
            def dstep(carry, _):
                dcache, tok, dpos, dloc, rngs = carry
                split = jax.vmap(jax.random.split)(rngs)
                next_rngs, sub = split[:, 0], split[:, 1]
                # Writes are capped at max_loc. Past it the block
                # index runs off the table — today's jax fills OOB
                # gathers with INT_MIN so the write already drops, but
                # under "clip" gather semantics (other jax versions)
                # it would land on the request's OWN last page and
                # destroy valid KV. The cap makes correctness
                # independent of gather OOB behavior; acceptance is
                # capacity-clamped there anyway.
                on = active & spec_on & (dloc <= max_loc)
                feed = jnp.where(active, tok, 0)
                eff_pos = jnp.where(on, dpos, -1).astype(jnp.int32)
                eff_loc = jnp.where(on, dloc, -1).astype(jnp.int32)
                logits, vars_ = draft_model.apply(
                    {"params": dparams, "cache": dcache}, feed[:, None],
                    positions=eff_pos[:, None], block_tables=dtables,
                    write_locations=eff_loc[:, None], lora=dlora,
                    adapter_ids=aids, mutable=["cache"])
                lg = logits[:, 0]
                nxt = sample_rows(lg, sub, temp, topk, active)
                return ((vars_["cache"], nxt, dpos + 1, dloc + 1,
                         next_rngs), (nxt, lg))

            carry = (dcache, pending, pos, loc, rngs)
            carry, (d_t, q_t) = jax.lax.scan(dstep, carry, None, length=k)
            dcache, _, _, _, rngs = carry
            D = d_t.T                      # [B, k]
            Q = jnp.swapaxes(q_t, 0, 1)    # [B, k, V]

            # -- 2. verify: one k+1-token window through the target
            win = jnp.concatenate([pending[:, None], D], axis=1)
            wpos = pos[:, None] + steps[None, :]
            wloc = loc[:, None] + steps[None, :]
            # Same max_loc write cap as the draft scan (and the
            # rollback below reuses the mask, so write and invalidate
            # always target the same entries). Logits at capped
            # indices are garbage, but acceptance can't reach them
            # (`within` below).
            writable = active[:, None] & (wloc <= max_loc[:, None])
            feed = jnp.where(active[:, None], win, 0)
            eff_pos = jnp.where(writable, wpos, -1)
            eff_loc = jnp.where(writable, wloc, -1)
            logits, vars_ = model.apply(
                {"params": params, "cache": cache}, feed,
                positions=eff_pos, block_tables=tables,
                write_locations=eff_loc, lora=lora,
                adapter_ids=aids, mutable=["cache"])
            cache = vars_["cache"]

            # -- 3. accept (rngs: one split for uniforms, one for the
            # residual/bonus categorical — fixed consumption per
            # iteration, so the per-slot stream is deterministic)
            Pw = warp(logits, temp, topk)          # [B, k+1, V]
            Qw = warp(Q, temp, topk)               # [B, k, V]
            within = wloc[:, 1:] <= max_loc[:, None]
            Qpad = jnp.concatenate(
                [Qw, jnp.zeros_like(Qw[:, :1])], axis=1)
            # q is zeroed wherever the accept test below is NOT a real
            # U-vs-p/q draw — non-speculating slots AND capacity-forced
            # boundaries (`within`): a forced rejection must sample the
            # plain target at that position (the q==0 path), not the
            # residual, or the last token of budget-capped sampled
            # requests would over-represent tokens with p > q.
            Qpad = jnp.where(
                spec_on[:, None, None]
                & jnp.concatenate(
                    [within, jnp.zeros_like(within[:, :1])],
                    axis=1)[..., None],
                Qpad, 0.0)
            split = jax.vmap(jax.random.split)(rngs)
            rngs, sub_u = split[:, 0], split[:, 1]
            U = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(sub_u)
            pd = jnp.take_along_axis(
                Pw[:, :k], D[..., None], axis=-1)[..., 0]
            qd = jnp.take_along_axis(
                Qpad[:, :k], D[..., None], axis=-1)[..., 0]
            ratio = pd / jnp.maximum(qd, 1e-30)
            acc = (U < jnp.minimum(ratio, 1.0)) & spec_on[:, None] \
                & within & active[:, None]
            cum = jnp.cumprod(acc.astype(jnp.int32), axis=1)
            a = jnp.sum(cum, axis=1)               # [B] accepted count
            p_sel = jnp.take_along_axis(
                Pw, a[:, None, None], axis=1)[:, 0]
            q_sel = jnp.take_along_axis(
                Qpad, a[:, None, None], axis=1)[:, 0]
            resid = jnp.maximum(p_sel - q_sel, 0.0)
            rsum = jnp.sum(resid, -1, keepdims=True)
            resid = jnp.where(rsum > 0, resid / jnp.maximum(rsum, 1e-30),
                              p_sel)
            split = jax.vmap(jax.random.split)(rngs)
            rngs, sub_b = split[:, 0], split[:, 1]
            bonus = jax.vmap(
                lambda kk, rr: jax.random.categorical(kk, jnp.log(rr))
            )(sub_b, resid).astype(jnp.int32)

            # -- 4. rollback: kill every window entry past the accept
            # point in both pools (the draft wrote indices 0..k-1)
            past = steps[None, :] > a[:, None]
            t_locs = jnp.where(writable & past, wloc, -1)
            d_locs = jnp.where(writable & past
                               & (steps[None, :] < k)
                               & spec_on[:, None], wloc, -1)
            cache = invalidate(cache, tables, t_locs)
            dcache = invalidate(dcache, dtables, d_locs)

            # -- 5. draft catch-up: the draft pool must stay valid
            # through the new cursor's last token (window index a) —
            # the propose scan wrote indices 0..k-1 when it ran, so
            # the gap is index k after a k-for-k sweep, or index a==0
            # (the pending token) when the scan was masked off (chaos
            # wave). One masked step writes it; its logits are unused.
            on = active & draft_live & ((a == k) | ~spec_on)
            last = jnp.take_along_axis(win, a[:, None], axis=1)[:, 0]
            eff_pos = jnp.where(on, pos + a, -1).astype(jnp.int32)
            eff_loc = jnp.where(on, loc + a, -1).astype(jnp.int32)
            _, vars_ = draft_model.apply(
                {"params": dparams, "cache": dcache},
                jnp.where(active, last, 0)[:, None],
                positions=eff_pos[:, None], block_tables=dtables,
                write_locations=eff_loc[:, None], lora=dlora,
                adapter_ids=aids, mutable=["cache"])
            dcache = vars_["cache"]
            return cache, dcache, rngs, D, a, bonus

        donate = (2, 3) if self._donate else ()
        sds = jax.ShapeDtypeStruct
        specs = (
            jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                   self.params),
            jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                   self.draft_params),
            self._cache_specs(),
            self._cache_specs(draft=True),
            sds((B, self.n_blocks), np.int32),  # target block tables
            sds((B, self.n_blocks), np.int32),  # draft block tables
            sds((B,), np.int32),      # pending token
            sds((B,), np.int32),      # pos
            sds((B,), np.int32),      # loc
            sds((B,), np.int32),      # max_loc
            sds((B,), np.bool_),      # spec_on
            sds((B,), np.bool_),      # draft_live
            sds((B,), np.bool_),      # active
            sds((B, 2), np.uint32),   # rngs
            sds((B,), np.float32),    # temp
            sds((B,), np.int32),      # topk
            self._lora_specs(),
            self._lora_specs(draft=True),
            sds((B,), np.int32),      # adapter ids
        )
        self._report_attend("spec_step", B, k + 1)    # the verify window
        self._report_attend("spec_step_draft", B, 1, draft=True)
        return self._report_temp("spec_step", jax.jit(
            self._named(run, "spec_step"),
            donate_argnums=donate).lower(*specs).compile())

    def warm(self, buckets: Optional[Sequence[int]] = None) -> int:
        """Compile the hot step (the decode chunk, or the fused
        speculative step when the draft is on) and the prefill(s) for
        ``buckets`` (default: every configured prompt bucket). Returns
        the number of compiled executables now available. Safe to call
        from a background thread: it only populates the AOT tables,
        never the live slot state."""
        if self.spec:
            # Spec engines never dispatch decode_chunk — every slot
            # (speculating or degraded) advances through the fused
            # verify step — so its compile is skipped entirely.
            self._spec_step()
            self._reset_fn(draft=True)
        else:
            self._decode()
        # The cold helpers too: the page-invalidate runs on the first
        # page reuse and the COW copy on the first partial prefix hit —
        # both would otherwise pay their one-time compile inside a
        # serving request.
        self._reset_fn()
        if self._wmgr is not None:
            self._reset_fn(window=True)
        if self._prefix is not None:
            self._copy_fn()
        from ..models.generate import pow2_bucket

        chunk_bucket = 0
        if self.prefill_chunk_tokens:
            # Chunked admission dispatches the chunk-size bucket for
            # every full chunk — compile it once here, not inside the
            # first long-prompt request.
            chunk_bucket = pow2_bucket(self.prefill_chunk_tokens,
                                       self.cfg.max_seq_len)
            self._prefill_for(chunk_bucket)
        for b in buckets if buckets is not None else self.prompt_buckets:
            if not 0 < chunk_bucket < b:
                # No admission dispatches a bucket over the chunk's: a
                # tail longer than the chunk goes in chunks
                # (_admit_resolved), so such a program would only be
                # compiled and held (at 32 k tokens it does not fit
                # beside the pool).
                self._prefill_for(int(b))
            if self.spec:
                # (The draft prefills a prompt whole: _admit_draft.)
                self._draft_prefill_for(int(b))
        with self._exec_lock:
            return (len(self._prefill_exec)
                    + len(self._draft_prefill_exec) + 1)

    # -- submission ----------------------------------------------------------
    def _make_request(self, prompt: Sequence[int], max_new_tokens: int,
                      temperature: float, top_k: int, seed: int,
                      stop_token: Optional[int],
                      adapter: Optional[str] = None,
                      qos: Optional[str] = None,
                      deadline_s: Optional[float] = None,
                      tenant: Optional[str] = None,
                      model: Optional[str] = None) -> Request:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        L = self.cfg.max_seq_len
        if len(prompt) + max_new_tokens > L:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the cache capacity {L}")
        # Adapter selection: explicit name, else the engine default;
        # "" always means base. Unknown names are a client mistake
        # (ValueError -> 400 at the server), never a 503.
        name = adapter if adapter is not None else self.adapter_default
        name = str(name or "")
        if name and (self._apool is None
                     or not self._apool.known(name)):
            raise ValueError(
                f"unknown adapter {name!r} (configured: "
                f"{sorted(self._apool.sources) if self._apool else []})")
        # Model selection (weight pool): explicit name, else the
        # engine's resident default; "" always means the default.
        # Unknown names are a client mistake (ValueError -> 400),
        # never a 503 — the pool only pages artifacts it was told
        # about at spec time.
        mdl = str(model or "")
        if mdl:
            if self._wpool is None:
                raise ValueError(
                    "per-request model selection requires a weight "
                    "pool (models= in the engine spec)")
            if not self._wpool.known(mdl):
                raise ValueError(
                    f"unknown model {mdl!r} (pooled: "
                    f"{sorted(self._wpool.sources)})")
        # QoS class: per-request override, else the engine default.
        # Unknown classes are a client mistake (-> 400), never a 503.
        cls = qos if qos is not None else self.qos_default
        if cls not in QOS_CLASSES:
            raise ValueError(
                f"unknown qos {cls!r} (expected one of "
                f"{sorted(QOS_CLASSES)})")
        # Deadline: per-request value, else the spec default (0 = no
        # deadline). Stored absolute so queue time counts against it.
        if deadline_s is None:
            deadline_s = self.deadline_default_s or None
        deadline = None
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                raise ValueError("deadline_s must be > 0")
            deadline = time.monotonic() + deadline_s
        req = Request(prompt, int(max_new_tokens), float(temperature),
                      int(top_k), int(seed),
                      -1 if stop_token is None else int(stop_token),
                      adapter=name, qos=cls, deadline=deadline,
                      model=mdl)
        req._flight = self.flight
        # Billable tenant: the client's explicit key, else the adapter
        # tenant ("" = the base tenant) — the same resolution the rate
        # limiter and the fairness queue use.
        if tenant is not None and str(tenant):
            req.tenant = str(tenant)
        req._usage = self.usage
        return req

    def _check_rate_locked(self, reqs: List[Request],
                           now: float) -> Optional["RateLimited"]:
        """Token-bucket admission for limited tenants (under _cond).
        Cost = prompt + max_new tokens (the weight a request can put
        on the engine). A tenant is admitted while its budget is
        positive and debits the full cost — overdraw is allowed, so
        the budget going negative is what paces the NEXT burst; the
        deficit converts directly into Retry-After seconds. The batch
        debits all-or-nothing, like every other admission check."""
        if not self.rate_limits:
            return None
        costs: Dict[str, float] = {}
        for r in reqs:
            tenant = r.adapter or ""
            if tenant in self.rate_limits:
                costs[tenant] = costs.get(tenant, 0.0) \
                    + len(r.prompt) + r.max_new
        for tenant, cost in costs.items():
            rate = self.rate_limits[tenant]
            burst = rate * self.rate_burst_s
            bucket = self._rate_buckets.get(tenant)
            if bucket is None:
                bucket = self._rate_buckets[tenant] = [burst, now]
            bucket[0] = min(burst, bucket[0]
                            + rate * (now - bucket[1]))
            bucket[1] = now
            if bucket[0] <= 0.0:
                retry = min(30.0, (cost - bucket[0]) / rate)
                return RateLimited(
                    f"tenant {tenant or 'base'!r} is over its "
                    f"{rate:g} tokens/s budget "
                    f"(deficit {-bucket[0]:.0f} tokens)",
                    retry_after_s=max(retry, 0.1))
        for tenant, cost in costs.items():
            self._rate_buckets[tenant][0] -= cost
        return None

    _SHED_HELP = {
        "kfx_lm_deadline_shed_total":
            "Requests shed before prefill as deadline-infeasible "
            "(503 + Retry-After).",
        "kfx_lm_rate_limited_total":
            "Requests shed by a tenant's token-weighted rate budget "
            "(503 + Retry-After).",
    }

    def _count_shed(self, family: str, n: int = 1) -> None:
        self._reg().counter(family, self._SHED_HELP[family]).inc(
            n, model=self.name)

    def _enqueue(self, reqs: List[Request]) -> None:
        """All-or-nothing enqueue: a batch that does not fit the
        bounded queue is rejected WHOLE — partial admission would
        orphan the admitted fraction (decoding with no waiter) exactly
        when the engine is most loaded. Admission-time policy runs
        here, before any prefill is burned: per-tenant token-rate
        budgets, deadline feasibility against the trailing queue-wait
        estimate, and batch-first load shedding (queued batch requests
        are evicted to make room for arriving interactive ones)."""
        shed_err: Optional[EngineOverloaded] = None
        shed_family = ""
        shed_victims: List[Request] = []
        with self._cond:
            if self._stopped:
                raise RuntimeError("engine is closed")
            if self._draining:
                raise EngineDraining(
                    f"engine {self.name} is draining; retry another "
                    "replica")
            now = time.monotonic()
            shed_err = self._check_rate_locked(reqs, now)
            if shed_err is not None:
                shed_family = "kfx_lm_rate_limited_total"
            if shed_err is None:
                # Deadline feasibility, judged with queue state in
                # hand: remaining headroom under the trailing queue
                # wait cannot make its deadline — shed NOW, before the
                # engine spends a prefill on it. An empty queue skips
                # the estimate (stale EWMA must not shed an idle
                # engine's traffic).
                est = self._qwait_ewma if len(self._queue) else 0.0
                for r in reqs:
                    if r.deadline is not None \
                            and r.deadline - now <= est:
                        shed_err = DeadlineInfeasible(
                            f"deadline {max(r.deadline - now, 0):.2f}s "
                            f"away but trailing queue wait is "
                            f"{est:.2f}s", retry_after_s=1.0)
                        shed_family = "kfx_lm_deadline_shed_total"
                        break
            if shed_err is None \
                    and len(self._queue) + len(reqs) > self.max_queue:
                overflow = len(self._queue) + len(reqs) - self.max_queue
                if all(r.qos == "interactive" for r in reqs):
                    # Batch is the first class shed under pressure:
                    # evict queued batch work (newest first) to make
                    # room for interactive arrivals.
                    shed_victims = self._queue.shed_batch(overflow)
                if len(self._queue) + len(reqs) > self.max_queue:
                    shed_err = EngineOverloaded(
                        f"admission queue full ({len(self._queue)} "
                        f"waiting, {len(reqs)} arriving, cap "
                        f"{self.max_queue})")
                    shed_family = ""
            if shed_err is not None:
                # Fall through: counters and futures resolve outside
                # the lock.
                pass
            elif self._active_count() == 0 and not self._queue \
                    and self._admitting is None:
                # Waking an idle loop: the parked interval is not a
                # stall — re-stamp progress so the liveness clock
                # starts at this admission, not at the last request.
                # (_admitting checked too: an arrival while a request
                # is stuck mid-admission must not reset the stall
                # clock of a genuinely wedged loop.)
                self._last_progress = time.monotonic()
            if shed_err is None:
                for r in reqs:
                    self._queue.push(r)
            depth = len(self._queue)
            self._cond.notify()
        if shed_victims:
            evict = EngineOverloaded(
                f"batch request shed for interactive admission "
                f"(engine {self.name} under queue pressure)")
            for v in shed_victims:
                v._finish(evict)
        self._reg().gauge("kfx_lm_queue_depth",
                          "Requests waiting for a decode-engine slot."
                          ).set(depth, model=self.name)
        if shed_err is not None:
            if shed_family:
                self._count_shed(shed_family)
            raise shed_err

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               stop_token: Optional[int] = None,
               adapter: Optional[str] = None,
               qos: Optional[str] = None,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None, meter_skip: int = 0,
               on_token: Optional[Callable[[Optional[int]], None]]
               = None, model: Optional[str] = None) -> Request:
        """Enqueue one prompt; returns the request handle (wait with
        ``.result(timeout)``). ``adapter`` selects a configured LoRA
        adapter by name (None = engine default, "" = base); ``model``
        selects a pooled model by name on a multi-model engine (None/""
        = the resident default); ``qos`` overrides the engine's class
        default; ``deadline_s`` is the per-request deadline (None =
        spec default, which may be none); ``on_token`` is the streaming
        sink — called on the loop thread with each token id as it
        lands, then None at retirement. Raises EngineOverloaded when
        the bounded admission queue is full,
        DeadlineInfeasible/RateLimited when admission policy sheds the
        request."""
        req = self._make_request(prompt, max_new_tokens, temperature,
                                 top_k, seed, stop_token, adapter,
                                 qos=qos, deadline_s=deadline_s,
                                 tenant=tenant, model=model)
        # Recovery re-dispatch (router stream_skip): the first N
        # regenerated tokens were already billed and streamed by the
        # replica that died — set BEFORE enqueue so even an instant
        # retirement bills them exactly once fleet-wide.
        req.meter_skip = max(int(meter_skip), 0)
        req.on_token = on_token
        self._enqueue([req])
        return req

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 stop_token: Optional[int] = None,
                 adapter: Optional[str] = None,
                 qos: Optional[str] = None,
                 deadline_s: Optional[float] = None,
                 tenant: Optional[str] = None,
                 model: Optional[str] = None
                 ) -> List[List[int]]:
        """Blocking convenience mirroring LMGenerator.generate: one
        request per prompt (seeded seed+i), results in prompt order.
        The batch enqueues atomically, and one deadline covers the
        whole batch: the request's own ``deadline_s`` when given
        (deadline-derived timeout), else request_timeout_s — both sit
        under the router's 60s backend timeout, so per-request fresh
        clocks can't stack past it."""
        reqs = self.submit_batch(prompts, max_new_tokens, temperature,
                                 top_k, seed, stop_token, adapter,
                                 qos=qos, deadline_s=deadline_s,
                                 tenant=tenant, model=model)
        wait_s = deadline_s if deadline_s else self.request_timeout_s
        deadline = time.monotonic() + wait_s
        return [r.result(max(0.001, deadline - time.monotonic()))
                for r in reqs]

    def submit_batch(self, prompts: Sequence[Sequence[int]],
                     max_new_tokens: int = 32, temperature: float = 0.0,
                     top_k: int = 0, seed: int = 0,
                     stop_token: Optional[int] = None,
                     adapter: Optional[str] = None,
                     qos: Optional[str] = None,
                     deadline_s: Optional[float] = None,
                     tenant: Optional[str] = None,
                     model: Optional[str] = None
                     ) -> List[Request]:
        """`generate` minus the blocking wait: one request per prompt
        (seeded seed+i), enqueued atomically, handles returned — so a
        caller (the model server's timing block) can read per-request
        flight state after collecting results."""
        reqs = [self._make_request(p, max_new_tokens, temperature,
                                   top_k, seed + i, stop_token, adapter,
                                   qos=qos, deadline_s=deadline_s,
                                   tenant=tenant, model=model)
                for i, p in enumerate(prompts)]
        self._enqueue(reqs)
        return reqs

    # -- page allocation -----------------------------------------------------
    def _alloc_pages(self, n: int) -> List[int]:
        """Take ``n`` pages, reclaiming LRU prefix-cache pages when the
        free list is short, and invalidating any recycled page's
        position ids on device BEFORE handing it out (one batched
        scatter per reuse wave). The ``engine.kv_alloc`` chaos point
        forces the failure path."""
        inj = chaos.draw("engine.kv_alloc", target=self.name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                raise PageAllocError(
                    f"chaos[engine.kv_alloc]: {self.name}")
        while self._mgr.n_free < n:
            if self._prefix is None or not self._prefix.evict_one(
                    spill=(self._spill_page
                           if self._offload is not None else None)):
                break  # alloc() raises with the honest numbers
        pages = self._mgr.alloc(n)
        if self._mgr.dirty:
            mask = np.zeros((self.n_pages,), np.bool_)
            mask[list(self._mgr.dirty)] = True
            self._cache = self._reset_fn()(self._cache, mask)
            self._mgr.dirty.clear()
        return pages

    def _alloc_draft_pages(self, n: int) -> List[int]:
        """Take ``n`` pages from the DRAFT pool, invalidating recycled
        pages' position ids first. No prefix cache to reclaim from and
        no chaos point: a draft shortfall is not a failure — the
        caller degrades the slot to non-speculative decode."""
        pages = self._draft_mgr.alloc(n)
        if self._draft_mgr.dirty:
            mask = np.zeros((self.draft_n_pages,), np.bool_)
            mask[list(self._draft_mgr.dirty)] = True
            self._draft_cache = self._reset_fn(draft=True)(
                self._draft_cache, mask)
            self._draft_mgr.dirty.clear()
        return pages

    def _place_window_blocks(self, slot: int, lo: int, hi: int) -> None:
        """Give ``slot`` a window-class page for every block of
        positions ``lo..hi`` it does not hold yet, recycled pages'
        position ids invalidated first. All or nothing: a shortfall
        raises PageAllocError and places none."""
        if self._wmgr is None or hi < lo:
            return
        ps = self.page_size
        row = self._wtables[slot]
        want = [b for b in range(lo // ps, hi // ps + 1) if row[b] < 0]
        if not want:
            return
        row[want] = self._wmgr.alloc(len(want))
        if self._wmgr.dirty:
            mask = np.zeros((self.window_pages,), np.bool_)
            mask[list(self._wmgr.dirty)] = True
            self._cache = self._reset_fn(window=True)(self._cache, mask)
            self._wmgr.dirty.clear()

    def _free_behind_window(self, slot: int, next_pos: int) -> None:
        """Give back the window-class pages of ``slot`` whose positions
        all lie behind the window of its next query, the one at
        ``next_pos``: that query sees ``next_pos - window + 1`` on."""
        if self._wmgr is None:
            return
        keep = max(0, next_pos - self.cfg.window + 1) // self.page_size
        first = int(self._wfirst[slot])
        if keep <= first:
            return
        row = self._wtables[slot, first:keep]
        gone = row[row >= 0]
        row[:] = -1
        self._wfirst[slot] = keep
        if gone.size:
            self._wmgr.decref(gone.tolist())
            self._reg().counter(
                "kfx_lm_window_pages_freed_total",
                "Window-class pages given back because every position "
                "in them lay behind the row's window.").inc(
                    int(gone.size), model=self.name)

    def _release_window(self, slot: int) -> None:
        if self._wmgr is not None:
            row = self._wtables[slot]
            self._wmgr.decref(row[row >= 0].tolist())
            row[:] = -1
            self._wfirst[slot] = 0

    def _release_slot(self, slot: int) -> None:
        """Return a slot's page references to the pool (pages still
        pinned by the prefix cache or other slots survive; the rest go
        back to the free list and will be invalidated before reuse).
        Draft pages are slot-private, so they always free whole, as
        are the window class's."""
        self._mgr.decref(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._tables[slot, :] = -1
        self._release_window(slot)
        self._active[slot] = False
        self._release_draft(slot)
        self._pending[slot] = -1
        aid = int(self._aids[slot])
        if aid >= 0 and self._apool is not None:
            # Unpin the slot's adapter; the FACTORS stay resident (LRU
            # keeps hot adapters in HBM across requests — paging out
            # happens only under slot pressure).
            self._apool.release(aid)
        self._aids[slot] = -1
        wid = int(self._wids[slot])
        if wid >= 0 and self._wpool is not None:
            # Unpin the slot's model; the WEIGHTS stay resident (LRU
            # keeps hot models in HBM across requests — eviction
            # happens only under slot pressure or the idle sweep).
            self._wpool.release(wid)
        self._wids[slot] = -1

    def _release_draft(self, slot: int) -> None:
        if self._draft_mgr is not None and self._draft_slot_pages[slot]:
            self._draft_mgr.decref(self._draft_slot_pages[slot])
        self._draft_slot_pages[slot] = []
        self._draft_tables[slot, :] = -1
        self._spec_ok[slot] = False

    # -- KV transfer plane (serving/kvtransfer.py) ---------------------------
    # Slot state is loop-thread-only, so every transfer operation that
    # touches it (export snapshot, import install, detach) runs as a
    # control job at an iteration boundary: other threads post a thunk
    # and wait. The network leg never holds the loop: migrate_out
    # snapshots on the loop, ships from the caller's thread, and only
    # detaches after the peer ACKs — so a severed transfer leaves the
    # donor's copy authoritative and running (zero lost requests).

    def _run_on_loop(self, fn: Callable[[], Any],
                     timeout: float = 30.0) -> Any:
        """Run ``fn`` on the decode-loop thread at the next iteration
        boundary and return its result (exceptions propagate to the
        caller). Called FROM the loop thread it just runs inline —
        handoff and offload paths compose without deadlock."""
        if threading.current_thread() is self._thread:
            return fn()
        box: Dict[str, Any] = {}
        done = threading.Event()

        def job() -> None:
            try:
                box["r"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed below
                box["e"] = e
            finally:
                done.set()

        with self._cond:
            if self._stopped:
                raise RuntimeError(f"engine {self.name} is closed")
            self._control.append(job)
            self._cond.notify()
        deadline = time.monotonic() + timeout
        while not done.wait(0.05):
            if self._stopped and not done.is_set():
                raise RuntimeError(
                    f"engine {self.name} closed before the control "
                    "job ran")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"engine {self.name} loop did not service the "
                    f"control job within {timeout}s")
        if "e" in box:
            raise box["e"]
        return box.get("r")

    def _service_control(self) -> None:
        """Drain pending control jobs (loop thread, iteration start).
        Job exceptions are captured into the waiter's box by the job
        wrapper itself — a refused import must fail the TRANSFER, not
        the engine. A job sees a quiesced boundary: what the last
        chunk owes is paid first (an export finishes its request, and
        the tokens come before the end marker)."""
        while True:
            with self._cond:
                if not self._control:
                    return
                job = self._control.popleft()
            self._pay_owed(overlapped=False)
            job()

    def _gather_fn(self):
        """Compiled single-page gather: one [layers, 1, ...] row per
        cache-tree leaf at page ``src`` — the export read and the
        offload demotion read (ONE compile serves both). Never
        donates: the pool must survive the read."""
        with self._exec_lock:
            fn = self._gather_exec
        if fn is not None:
            return fn
        import jax

        def run(cache, src):
            return jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_slice_in_dim(
                    leaf, src, 1, axis=1), cache)

        sds = jax.ShapeDtypeStruct
        specs = (self._cache_specs(), sds((), np.int32))
        fn = self._build(jax.jit(
            self._named(run, "kv_gather")).lower(*specs).compile)
        with self._exec_lock:
            if self._gather_exec is None:
                self._gather_exec = fn
            return self._gather_exec

    def _scatter_fn(self):
        """Compiled single-page scatter: writes one gathered row tree
        into page ``dst`` — the import write and the offload
        promote-on-hit (ONE compile serves both)."""
        with self._exec_lock:
            fn = self._scatter_exec
        if fn is not None:
            return fn
        import jax

        def run(cache, row, dst):
            return jax.tree_util.tree_map(
                lambda leaf, r: jax.lax.dynamic_update_slice_in_dim(
                    leaf, r, dst, axis=1), cache, row)

        donate = (0,) if self._donate else ()
        sds = jax.ShapeDtypeStruct
        specs = (self._cache_specs(), self._row_specs(),
                 sds((), np.int32))
        fn = self._build(
            jax.jit(self._named(run, "kv_scatter"),
                    donate_argnums=donate).lower(*specs).compile)
        with self._exec_lock:
            if self._scatter_exec is None:
                self._scatter_exec = fn
            return self._scatter_exec

    def _row_specs(self):
        """ShapeDtypeStructs of ONE page's row tree (page axis is 1
        on every cache leaf — the _copy_fn convention)."""
        import jax

        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape[:1] + (1,) + s.shape[2:], s.dtype),
            self._cache_specs())

    def _leaf_descriptors(self) -> List[Dict[str, Any]]:
        """Wire geometry: one (path, per-page shape, dtype) descriptor
        per cache-tree leaf in flatten order. The receiver requires
        leaf-for-leaf identity before scattering a single page — int8
        entries, scale planes and cached position ids all described,
        so an f32 donor can never feed an int8 receiver."""
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(
            self._row_specs())
        return [{"path": "".join(str(k) for k in path),
                 "shape": [int(d) for d in s.shape],
                 "dtype": np.dtype(s.dtype).name}
                for path, s in flat]

    def _page_payload(self, page: int) -> bytes:
        """One page's wire payload: every cache-tree leaf's row bytes
        concatenated in flatten order (loop thread only)."""
        import jax

        rows = self._gather_fn()(self._cache, np.int32(page))
        flat, _ = jax.tree_util.tree_flatten(rows)
        return b"".join(np.asarray(x).tobytes() for x in flat)

    def _unpack_page(self, payload: bytes):
        """Parse one wire payload back into a page row tree (numpy
        host arrays, fed straight to the compiled scatter). Size
        mismatches raise TransferError — geometry drift must never
        scatter garbage into the pool."""
        import jax

        specs, treedef = jax.tree_util.tree_flatten(self._row_specs())
        arrays: List[np.ndarray] = []
        off = 0
        for s in specs:
            dt = np.dtype(s.dtype)
            count = int(np.prod(s.shape))
            nbytes = count * dt.itemsize
            if off + nbytes > len(payload):
                raise kvtransfer.TransferError(
                    f"short page payload ({len(payload)} bytes, leaf "
                    f"at offset {off} needs {nbytes})")
            arrays.append(np.frombuffer(
                payload, dtype=dt, count=count,
                offset=off).reshape(s.shape))
            off += nbytes
        if off != len(payload):
            raise kvtransfer.TransferError(
                f"page payload size mismatch ({len(payload)} bytes, "
                f"geometry says {off})")
        return jax.tree_util.tree_unflatten(treedef, arrays)

    def _export_slot(self, slot: int) -> Tuple[Request, bytes, int]:
        """Snapshot one slot's in-flight request as a kvtransfer
        payload (loop thread only): pin the slot's pages, gather each
        to host bytes, and pack them with the full resume state —
        prompt + generated tokens, sampling knobs, RNG stash, the
        pending-logits row (mid-decode) or the prefill cursor
        (mid-chunking). The slot keeps running; the caller decides
        when (and whether) to detach it (_finish_migrated)."""
        req = self._slots[slot]
        assert req is not None, f"export of empty slot {slot}"
        cur = self._prefilling.get(slot)
        blocks = [b for b in range(self.n_blocks)
                  if self._tables[slot, b] >= 0]
        phys = [int(self._tables[slot, b]) for b in blocks]
        with obs_trace.span("engine.kv_export", trace_id=req.trace_id,
                            parent_id=req.span_id, model=self.name,
                            slot=str(slot), pages=str(len(blocks))):
            for pg in phys:
                self._mgr.incref(pg)  # pinned for the gather window
            try:
                frames = [self._page_payload(pg) for pg in phys]
            finally:
                self._mgr.decref(phys)
            rd = req.deadline
            header: Dict[str, Any] = {
                "format": 1,
                "model": self.name,
                "page_size": self.page_size,
                "max_seq_len": int(self.cfg.max_seq_len),
                "vocab": int(self.cfg.vocab_size),
                "leaves": self._leaf_descriptors(),
                "blocks": blocks,
                "resume": kvtransfer.resume_key(
                    req.prompt, req.max_new, req.temperature,
                    req.top_k, req.seed, req.stop, req.adapter),
                "req": {
                    "prompt": req.prompt,
                    "tokens": list(req.tokens),
                    "max_new": req.max_new,
                    "temperature": req.temperature,
                    "top_k": req.top_k,
                    "seed": req.seed,
                    "stop": req.stop,
                    "adapter": req.adapter or "",
                    "qos": req.qos,
                    "tenant": req.tenant,
                    "deadline_s": (max(rd - time.monotonic(), 0.001)
                                   if rd is not None else 0.0),
                },
            }
            if cur is not None:
                # Mid-prefill: the chunked cursor is the shipping unit
                # — the receiver resumes chunking at ``next``.
                header["phase"] = "prefill"
                header["cursor"] = {"next": int(cur["next"]),
                                    "bucket": int(cur["bucket"]),
                                    "remaining": int(cur["remaining"]),
                                    "fresh": bool(cur["fresh"])}
                rng = req.rng
            else:
                header["phase"] = "decode"
                header["slot_state"] = {
                    "pos": int(self._pos[slot]),
                    "loc": int(self._loc[slot]),
                    "max_loc": int(self._max_loc[slot]),
                    "pending": int(self._pending[slot]),
                }
                # The decode dispatch samples from the slot's LAST
                # logits row — it is state, exactly like the RNG.
                rng = np.asarray(self._rngs[slot], np.uint32)
                logrow = np.asarray(self._logbuf[slot])
                header["aux"] = {"dtype": logrow.dtype.name,
                                 "shape": [int(d)
                                           for d in logrow.shape]}
                frames = frames + [logrow.tobytes()]
            header["rng"] = ([int(x) for x in rng]
                             if rng is not None else None)
            payload = kvtransfer.encode(header, frames)
        return req, payload, len(blocks)

    def migrate_out(self, reason: str = "manual",
                    send: Optional[Callable[[bytes], str]] = None,
                    rids: Optional[Sequence[int]] = None
                    ) -> Dict[str, int]:
        """Live migration: export every in-flight request (optionally
        filtered by rid), ship each to a peer, and finish the local
        copy with RequestMigrated so the router's bounded re-dispatch
        attaches to the peer's adopted generation. Ordering is
        fail-safe: the local copy keeps decoding until the peer ACKs
        the import, so a severed transfer (the ``kv.transfer`` chaos
        point) costs nothing — the donor serves (or drains) the
        request exactly as if no migration was attempted, and the
        router's seeded re-dispatch remains the recovery of last
        resort. Returns {"moved", "failed", "pages"}."""
        if self._wpool is not None:
            raise ValueError(
                f"engine {self.name} hosts a weight pool: migrated "
                "pages would decode under the peer's weights")
        if self.cfg.has_slot_state:
            raise ValueError(
                f"engine {self.name} holds slot state ('mamba' layers): "
                "migration moves pages, and a slot's state would have "
                "to move with them")
        if self._wmgr is not None:
            raise ValueError(
                f"engine {self.name} has a second page class ('window' "
                "layers): migration moves the pages of one block table")
        send = send if send is not None else self._peer_send
        if send is None:
            raise ValueError(
                f"engine {self.name} has no KV transfer peer "
                "configured")
        wanted = set(rids) if rids is not None else None

        def snap() -> List[Tuple[Request, bytes, int]]:
            out = []
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                if wanted is not None and req.rid not in wanted:
                    continue
                out.append(self._export_slot(slot))
            return out

        moved = failed = pages = 0
        for req, payload, npages in self._run_on_loop(snap):
            t0 = time.monotonic()
            try:
                inj = chaos.draw("kv.transfer", target=self.name)
                if inj is not None:
                    if inj.delay > 0:
                        time.sleep(inj.delay)
                    if inj.mode != "delay":
                        raise kvtransfer.TransferError(
                            f"chaos[kv.transfer]: {self.name}")
                peer = send(payload)
            except Exception:
                failed += 1  # local copy keeps running: zero lost
                continue
            self._observe_transfer(time.monotonic() - t0)
            if self._run_on_loop(
                    lambda r=req, p=peer: self._finish_migrated(
                        r, p, reason)):
                moved += 1
                pages += npages
                self._count_migration(reason, npages)
            # else: it retired normally while the bytes traveled; the
            # peer's adopted copy finishes unclaimed and idles out.
        return {"moved": moved, "failed": failed, "pages": pages}

    def _observe_transfer(self, seconds: float, n: int = 1) -> None:
        self._reg().histogram(
            "kfx_lm_kv_transfer_seconds",
            "End-to-end KV transfer time (export snapshot to peer "
            "acknowledgement).",
            buckets=QUEUE_WAIT_BUCKETS).observe(
                seconds, n=n, model=self.name)

    def _count_migration(self, reason: str, npages: int) -> None:
        reg = self._reg()
        reg.counter("kfx_lm_kv_migrations_total",
                    "In-flight requests migrated to a peer replica, "
                    "by reason.").inc(1, model=self.name,
                                      reason=reason)
        reg.counter("kfx_lm_kv_pages_transferred_total",
                    "KV pages shipped to or adopted from peer "
                    "replicas.").inc(npages, model=self.name)

    def _finish_migrated(self, req: Request, peer: str,
                         reason: str) -> bool:
        """Detach a migrated request from its slot (loop thread):
        pages release, and the waiter gets RequestMigrated — the
        retriable "gone to ``peer``" the server turns into 503 +
        ``X-Kfx-Migrated``. Returns False when the request already
        retired (a migration racing normal completion costs nothing;
        the peer's adopted copy idles out unclaimed)."""
        slot = next((s for s, r in enumerate(self._slots)
                     if r is req), None)
        if slot is None:
            return False
        self._prefilling.pop(slot, None)
        self._slots[slot] = None
        self._release_slot(slot)
        if self.flight is not None:
            self.flight.event(req, "migrated", peer=peer,
                              reason=reason)
        req._finish(RequestMigrated(
            f"request migrated to {peer} ({reason})", peer=peer))
        self._touch_gauges()
        return True

    def kv_import(self, raw: bytes,
                  on_token: Optional[Callable[[Optional[int]], None]]
                  = None) -> Request:
        """Adopt a migrated request: verify the page stream (chain
        digest per page — TransferCorrupt discards the partial import
        WHOLE), check leaf-for-leaf geometry, then install it in a
        free slot at the next iteration boundary: allocate pages,
        scatter each frame, and restore exactly the slot state the
        donor exported (mid-decode) or the prefill cursor
        (mid-chunking). Returns the live Request — already decoding;
        wait on ``.result()`` or stream via ``on_token``. Raises
        TransferError/TransferCorrupt (nothing imported) or
        EngineOverloaded (no slot / no pages — the donor keeps the
        request)."""
        if self._wpool is not None:
            raise kvtransfer.TransferError(
                f"engine {self.name} hosts a weight pool: imported "
                "pages would decode under a different model's weights")
        if self.cfg.has_slot_state:
            raise kvtransfer.TransferError(
                f"engine {self.name} holds slot state ('mamba' layers): "
                "an import brings pages, and no state for the slot")
        if self._wmgr is not None:
            raise kvtransfer.TransferError(
                f"engine {self.name} has a second page class ('window' "
                "layers): an import brings the pages of one block table")
        inj = chaos.draw("kv.transfer", target=self.name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                raise kvtransfer.TransferCorrupt(
                    f"chaos[kv.transfer]: {self.name}")
        header, frames = kvtransfer.decode(raw)
        if int(header.get("format", -1)) != 1:
            raise kvtransfer.TransferError(
                f"unknown transfer format {header.get('format')!r}")
        if header.get("page_size") != self.page_size \
                or header.get("max_seq_len") != int(
                    self.cfg.max_seq_len) \
                or header.get("vocab") != int(self.cfg.vocab_size) \
                or header.get("leaves") != self._leaf_descriptors():
            raise kvtransfer.TransferError(
                "kv geometry mismatch: donor and receiver caches are "
                "not leaf-for-leaf identical")
        r = header["req"]
        stop = int(r["stop"])
        req = self._make_request(
            r["prompt"], int(r["max_new"]), float(r["temperature"]),
            int(r["top_k"]), int(r["seed"]),
            None if stop < 0 else stop, r["adapter"] or None,
            qos=r.get("qos"),
            deadline_s=float(r.get("deadline_s") or 0) or None,
            tenant=r.get("tenant") or None)
        req.tokens = [int(t) for t in r["tokens"]]
        # The donor billed (and possibly streamed) these tokens:
        # bill only receiver-generated output, once fleet-wide — the
        # same contract as the router's stream_skip re-dispatch.
        req.meter_skip = len(req.tokens)
        req.counted = True
        req.t_admitted = time.monotonic()
        req.on_token = on_token
        if header.get("rng") is not None:
            req.rng = np.asarray(header["rng"], np.uint32)
        self._run_on_loop(
            lambda: self._install_import(header, frames, req))
        return req

    def _install_import(self, header: Dict[str, Any],
                        frames: List[bytes], req: Request) -> None:
        """Install an adopted request (loop thread): the all-or-
        nothing half of kv_import. Any failure past allocation
        releases every page it took — a discarded partial import
        leaks nothing."""
        blocks = [int(b) for b in header["blocks"]]
        phase = header.get("phase", "decode")
        with obs_trace.span("engine.kv_import", trace_id=req.trace_id,
                            parent_id=req.span_id, model=self.name,
                            pages=str(len(blocks)), phase=phase):
            if self._draining:
                raise EngineDraining(
                    f"engine {self.name} is draining; the donor "
                    "keeps the request")
            slot = next((s for s, rq in enumerate(self._slots)
                         if rq is None), None)
            if slot is None:
                raise EngineOverloaded(
                    f"engine {self.name} has no free slot for a KV "
                    "import")
            if any(b < 0 or b >= self.n_blocks for b in blocks):
                raise kvtransfer.TransferError(
                    "block index out of range")
            if phase == "decode":
                st = header["slot_state"]
                if int(st["pending"]) >= 0 and not self.spec:
                    raise kvtransfer.TransferError(
                        "pending speculative token requires a "
                        "speculative receiver")
                if len(frames) != len(blocks) + 1:
                    raise kvtransfer.TransferError(
                        f"expected {len(blocks)} pages + 1 aux "
                        f"frame, got {len(frames)}")
            elif len(frames) != len(blocks):
                raise kvtransfer.TransferError(
                    f"expected {len(blocks)} pages, got "
                    f"{len(frames)}")
            # Parse every frame BEFORE touching the pool: a geometry
            # lie discovered at frame k must not strand k pages.
            rows = [self._unpack_page(frames[i])
                    for i in range(len(blocks))]
            aid = -1
            if req.adapter:
                aid = self._resolve_adapter(req)  # raises = refusal
                if aid < 0:
                    raise kvtransfer.TransferError(
                        f"imported pages hold adapter KV but "
                        f"{req.adapter!r} degraded to base here")
            try:
                pages = self._alloc_pages(len(blocks))
            except PageAllocError:
                if aid >= 0:
                    self._apool.release(aid)
                raise
            try:
                for row, pg in zip(rows, pages):
                    self._cache = self._scatter_fn()(
                        self._cache, row, np.int32(pg))
            except Exception as e:
                if self._donate:
                    self._fail_inflight(e)
                else:
                    self._mgr.decref(pages)  # discard the partial
                    if aid >= 0:             # import whole
                        self._apool.release(aid)
                raise
            trow = np.full((self.n_blocks,), -1, np.int32)
            for b, pg in zip(blocks, pages):
                trow[b] = pg
            self._tables[slot] = trow
            self._slot_pages[slot] = list(pages)
            self._aids[slot] = aid
            full = req.prompt + req.tokens
            n = len(full)
            ps = self.page_size
            # Register the imported PROMPT pages in the local prefix
            # cache: a migration carries its share of the fleet cache
            # with it, and the router's affinity re-learn (it follows
            # the successful re-dispatch) points the prefix here next.
            root = req.adapter.encode() if (req.adapter and aid >= 0) \
                else b""
            key = root
            covered = len(req.prompt) // ps
            if phase == "prefill":
                covered = min(int(header["cursor"]["next"]),
                              len(req.prompt)) // ps
            reg_block = covered
            if self._prefix is not None:
                reg_block = 0
                for b in range(covered):
                    pg = int(trow[b])
                    if pg < 0:
                        break
                    key = self._prefix.insert_full(
                        key, full[b * ps:(b + 1) * ps], pg, root=root)
                    reg_block = b + 1
            if phase == "prefill":
                cur = header["cursor"]
                self._active[slot] = False
                self._pending[slot] = -1
                self._slots[slot] = req
                self._prefilling[slot] = {
                    "req": req, "full": full, "n": n,
                    "next": int(cur["next"]), "key": key,
                    "reg_block": reg_block, "root": root,
                    "bucket": int(cur["bucket"]),
                    "remaining": int(cur["remaining"]),
                    "fresh": bool(cur.get("fresh"))}
            else:
                import jax
                import jax.numpy as jnp

                st = header["slot_state"]
                aux = header.get("aux") or {}
                logrow = np.frombuffer(
                    frames[len(blocks)],
                    dtype=np.dtype(str(aux.get("dtype", "float32"))))
                logrow = logrow.reshape(
                    [int(d) for d in aux["shape"]])
                self._logbuf = self._logbuf.at[slot].set(
                    jnp.asarray(logrow, self._logbuf.dtype))
                self._pos[slot] = int(st["pos"])
                self._loc[slot] = int(st["loc"])
                self._max_loc[slot] = int(st["max_loc"])
                self._pending[slot] = int(st["pending"])
                self._produced[slot] = len(req.tokens)
                if req.rng is not None:
                    self._rngs[slot] = req.rng
                else:
                    import jax

                    self._rngs[slot] = np.asarray(
                        jax.random.PRNGKey(req.seed), np.uint32)
                self._temp[slot] = req.temperature
                self._topk[slot] = req.top_k
                self._stop[slot] = req.stop
                self._max_new[slot] = req.max_new
                if self.spec:
                    # Adopted slots never speculate: the draft pool
                    # holds none of their KV. The fused verify step
                    # serves degraded slots exactly (1 token/iter).
                    self._spec_ok[slot] = False
                self._active[slot] = True
                self._slots[slot] = req
            if self.flight is not None:
                self.flight.event(req, "kv_import",
                                  pages=len(blocks), phase=phase)
            self._reg().counter(
                "kfx_lm_kv_pages_transferred_total",
                "KV pages shipped to or adopted from peer replicas."
                ).inc(len(blocks), model=self.name)
            self._touch_gauges()

    def _handoff_ready(self) -> None:
        """Prefill-role handoff (loop thread): every active slot whose
        prefill just completed (and was not handed off yet) exports
        NOW — before this iteration's decode step — and ships to a
        decode peer from a side thread, so the loop keeps chunking
        other prompts while the bytes travel. Transfer failure
        demotes the slot to local decode (mixed behavior):
        disaggregation is an optimization, never a correctness
        surface."""
        for slot, req in enumerate(self._slots):
            if req is None or not self._active[slot] \
                    or slot in self._prefilling:
                continue
            if req.rid in self._handoff_skip:
                continue
            if len(self._handoff_skip) > 4096:
                self._handoff_skip.clear()
            self._handoff_skip.add(req.rid)
            try:
                _, payload, npages = self._export_slot(slot)
            except Exception:
                continue  # decode locally
            threading.Thread(
                target=self._handoff_send,
                args=(req, payload, npages),
                name=f"kfx-kv-handoff-{self.name}",
                daemon=True).start()

    def _handoff_send(self, req: Request, payload: bytes,
                      npages: int) -> None:
        t0 = time.monotonic()
        try:
            inj = chaos.draw("kv.transfer", target=self.name)
            if inj is not None:
                if inj.delay > 0:
                    time.sleep(inj.delay)
                if inj.mode != "delay":
                    raise kvtransfer.TransferError(
                        f"chaos[kv.transfer]: {self.name}")
            peer = self._peer_send(payload)
        except Exception:
            return  # the slot decodes locally: zero lost
        self._observe_transfer(time.monotonic() - t0)
        try:
            if self._run_on_loop(lambda: self._finish_migrated(
                    req, peer, "disagg")):
                self._count_migration("disagg", npages)
        except (RuntimeError, TimeoutError):
            pass  # engine closed mid-handoff; the peer copy idles out

    # -- host-RAM offload tier ------------------------------------------------
    def _offload_gauge(self) -> None:
        if self._offload is None:
            return
        self._reg().gauge(
            "kfx_lm_kv_offload_pages",
            "Prefix-cache pages held per KV offload tier.").set(
                len(self._offload), model=self.name, tier="host")

    def _spill_page(self, e: "_PrefixEntry") -> None:
        """Demote one evicted prefix page into the host offload tier
        (refcount-aware by construction: evict_one only selects
        childless entries at pool ref 1, so no live slot still reads
        the page). Partial boundary pages are skipped — they are COW
        sources keyed by token comparison, not chain hash. The
        ``kv.offload`` chaos point (or any gather failure) drops the
        demotion: the page's next miss recomputes, never crashes."""
        if e.partial:
            return
        inj = chaos.draw("kv.offload", target=self.name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                return
        try:
            self._offload.put(e.key, self._page_payload(e.page))
        except Exception:
            return
        self._offload_gauge()

    def _promote_offloaded(self, full: List[int], max_reuse: int,
                           shared: List[int], matched: int,
                           key: bytes,
                           root: bytes = b"") -> Tuple[int, bytes]:
        """Extend a prefix-cache match from the host offload tier:
        while the next full page's chain hash is resident in host
        RAM, allocate a device page, scatter the payload back (the
        compiled promote — the same executable as the import path)
        and register it as a live cache entry, so the admission skips
        that much more prefill. ``shared`` grows in place. Pool
        pressure, geometry drift or the ``kv.offload`` chaos point
        stop the walk — the remaining tail re-prefills, exactly the
        cost of never having offloaded."""
        ps = self.page_size
        base = list(shared)
        for pg in base:
            self._mgr.incref(pg)  # eviction guard: promote allocs may
        ours: List[int] = []      # reclaim LRU cache pages
        while matched + ps <= max_reuse:
            nxt = _chain_hash(key, full[matched:matched + ps])
            payload = self._offload.get(nxt)
            if payload is None:
                break
            inj = chaos.draw("kv.offload", target=self.name)
            if inj is not None:
                if inj.delay > 0:
                    time.sleep(inj.delay)
                if inj.mode != "delay":
                    break  # promote refused: the tail re-prefills
            try:
                row = self._unpack_page(payload)
            except kvtransfer.TransferError:
                self._offload.pop(nxt)  # stale geometry: unusable
                break
            try:
                page = self._alloc_pages(1)[0]
            except PageAllocError:
                break
            try:
                self._cache = self._scatter_fn()(
                    self._cache, row, np.int32(page))
            except Exception as e:
                if self._donate:
                    self._fail_inflight(e)  # pool rebuilt: refs gone
                    raise
                self._mgr.decref(base + ours + [page])
                raise
            self._offload.pop(nxt)
            self._prefix.insert_full(
                key, full[matched:matched + ps], page, root=root)
            ours.append(page)
            shared.append(page)
            key = nxt
            matched += ps
        # Promoted pages keep their cache ref (insert_full); ours and
        # the guards drop here — the caller pins ``shared`` right
        # after, same thread, nothing allocates in between.
        self._mgr.decref(base + ours)
        if ours:
            self._offload_gauge()
        return matched, key

    # -- the decode loop -----------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cond:
                while (not self._stopped and not self._queue
                       and self._active_count() == 0
                       and not self._control and not self._owed):
                    # A weight pool with an idle window must keep
                    # ticking while parked, or a fully-idle replica
                    # would never run the scale-to-zero sweep below.
                    if self._wpool is not None and self.model_idle_s > 0:
                        self._cond.wait(
                            timeout=min(1.0, self.model_idle_s))
                        break
                    self._cond.wait()
                if self._stopped:
                    return
            try:
                with self._iteration():
                    self._iterate()
            except Exception as e:     # a broken dispatch fails the
                self._fail_inflight(e)  # requests, never the engine;
                time.sleep(0.01)        # KeyboardInterrupt/SystemExit
                #                         propagate (they are shutdown,
                #                         not request failures)

    def _iterate(self) -> None:
        """One iteration of the loop, between two parks on the
        condition variable. The loop thread marks what it is doing
        phase by phase (``_phase``): control, admit, prefill.enqueue,
        decode.enqueue, then deliver and bookkeeping for the chunk
        BEFORE (``_pay_owed``: the hand-out runs while the device
        works on the chunk just enqueued), then device_wait."""
        with self._phase("engine.control"):
            # KV-transfer control jobs first (export snapshots,
            # import installs): they are slot-state surgery and
            # must see a quiesced iteration boundary, exactly like
            # admission.
            self._service_control()
            # Replica-side scale-to-zero: models idle past
            # model_idle_s leave their weight slots at the
            # iteration boundary (the timed park in _loop keeps the
            # sweep ticking on a fully-idle replica; the operator
            # can also push :evict explicitly).
            self._maybe_evict_idle()
        # Decode-stall accounting: prefill dispatch time (a
        # monolithic admission's, or this iteration's one
        # prompt chunk) is observed as stall only when active
        # decode slots existed to be stalled by it. On a backend
        # that dispatches asynchronously (the TPU) that time is the
        # ENQUEUE: the prefill's device time is paid inside the next
        # decode chunk's engine.device_wait.
        self._iter_stall = 0.0
        had_active = bool(self._active.any())
        dispatched = False
        with self._phase("engine.admit"):
            self._admit_ready()
        if self._active_count():
            self._maybe_wedge()
            # At most ONE prompt-chunk dispatch per iteration:
            # the chunked-prefill head-of-line bound.
            self._advance_prefill()
            if had_active and self._iter_stall > 0:
                with self._phase("engine.bookkeeping"):
                    self._reg().histogram(
                        "kfx_lm_decode_stall_seconds",
                        "Seconds active decode slots waited on a "
                        "prefill dispatch, per engine iteration (on "
                        "an asynchronous backend: on its enqueue).",
                        buckets=QUEUE_WAIT_BUCKETS).observe(
                            self._iter_stall, model=self.name)
                    # Attribute the stall to every active request
                    # that waited through it — the ``stalled_s``
                    # leg of the flight-recorder breakdown.
                    if self.flight is not None:
                        for slot, r in enumerate(self._slots):
                            if r is not None and self._active[slot]:
                                r.stall_s += self._iter_stall
            if self.role == "prefill" \
                    and self._peer_send is not None:
                # Disaggregation: ship every freshly-prefilled
                # slot's pages toward a decode peer BEFORE this
                # iteration's decode step — a successful
                # handoff never decodes a token here.
                self._handoff_ready()
            if bool(self._active.any()):
                dispatched = self._decode_once()
        if not dispatched:
            # No dispatch's hand-out: the iteration owes its flight
            # record and the gauges alone.
            self._owed.append(_Handout(self._iterations))
        if not self._active.any():
            # No row is decoding, so no chunk will be enqueued behind
            # what is owed (the loop may be about to park): paid now.
            self._pay_owed(overlapped=False)
        with self._phase("engine.bookkeeping"):
            # The progress heartbeat: one completed iteration. A
            # loop stuck inside a dispatch (or the wedge stall
            # above) never reaches this line, so /healthz sees the
            # timestamp go stale while slots are active.
            self._iterations += 1
            self._last_progress = time.monotonic()

    @contextlib.contextmanager
    def _iteration(self):
        """One ``engine.iteration`` annotation round an iteration of
        the loop (its number, the decoding and the prefilling slots as
        it starts), and at its end the flush of the iteration's host
        seconds by phase. Host time of the iteration under no named
        phase (page budgeting, chaos draws) is phase ``other``, so the
        family's sum is the loop thread's busy host time; the time it
        blocked on the device is counted apart, and time parked on the
        condition variable with nothing to do is in neither."""
        acc = self._phase_s
        acc.clear()
        del self._phase_stack[:]
        try:
            with self._phase("engine.iteration", "other",
                             iteration=self._iterations,
                             active=int(self._active.sum()),
                             prefilling=len(self._prefilling)):
                yield
        finally:
            reg = self._reg()
            wait = acc.pop("device_wait", 0.0)
            host = reg.counter(
                "kfx_lm_engine_host_seconds_total",
                "Host seconds of the engine's loop thread by phase of "
                "the iteration (device waits and idle parking "
                "excluded).")
            for phase, seconds in acc.items():
                host.inc(seconds, model=self.name, phase=phase)
            reg.counter(
                "kfx_lm_engine_device_wait_seconds_total",
                "Seconds the engine's loop thread blocked on the first "
                "host read of a dispatch's outputs.").inc(
                    wait, model=self.name)
            reg.counter(
                "kfx_lm_engine_iterations_total",
                "Iterations of the engine's loop.").inc(
                    1, model=self.name)

    @contextlib.contextmanager
    def _phase(self, name: str, key: str = "", **attrs):
        """Mark a phase of the iteration on the loop thread: a profiler
        annotation ``name`` (obs/trace.py's bridge) and, from the same
        two clock reads, its seconds summed under ``key`` (``name``
        less its ``engine.`` by default). Exclusive: while a nested
        phase is open the one around it does not accrue."""
        stack, acc = self._phase_stack, self._phase_s
        key = key or name[len("engine."):]
        with obs_trace.annotate(name, **attrs):
            now = time.perf_counter()
            if stack:
                acc[stack[-1][0]] += now - stack[-1][1]
            stack.append([key, now])
            try:
                yield
            finally:
                now = time.perf_counter()
                acc[key] += now - stack.pop()[1]
                if stack:
                    stack[-1][1] = now

    def _maybe_evict_idle(self) -> None:
        """The weight pool's idle sweep (loop thread, iteration
        boundary, rate-limited to ~1/s): every ref-0 model idle past
        ``model_idle_s`` drops its slot — scale-to-zero as an eviction
        the NEXT acquire undoes with a measured swap, never a process
        restart. The resident default stays warm (minReplicas=1
        semantics)."""
        if self._wpool is None or self.model_idle_s <= 0:
            return
        now = time.monotonic()
        if now - self._last_idle_sweep < min(1.0, self.model_idle_s):
            return
        self._last_idle_sweep = now
        self._wpool.evict_idle(self.model_idle_s,
                               keep=self.model_default)

    def _record_flight(self, iteration: Optional[int] = None) -> None:
        """Append an iteration's flight record (loop thread, with the
        rest of what the iteration owes: at its end, or behind the next
        chunk's enqueue — either way a wedge mid-iteration leaves the
        ring frozen at the last COMPLETED tick, which is what a
        postmortem reads). The slots and the queue are read as they
        are when it is written, the depth without the lock: a
        one-record-stale picture is fine for forensics and keeps the
        hot path lock-free."""
        active, prefilling = [], []
        for slot, r in enumerate(self._slots):
            if r is None:
                continue
            if slot in self._prefilling:
                prefilling.append((slot, r.rid))
            elif self._active[slot]:
                active.append((slot, r.rid))
        self.flight.record_iteration(
            iteration=(self._iterations if iteration is None
                       else iteration),
            active=active, prefilling=prefilling,
            pages_free=self._mgr.n_free,
            draft_pages_free=(self._draft_mgr.n_free
                              if self._draft_mgr is not None else 0),
            spec_proposed=self._spec_proposed,
            spec_accepted=self._spec_accepted,
            stall_s=self._iter_stall,
            queue_depth=len(self._queue),
            preemptions=self._preempts)

    def _admit_ready(self) -> None:
        """Admit queued requests into free slots (runs between chunks —
        iteration-level scheduling, never mid-dispatch). Admission is
        gated on free PAGES: a request the pool cannot hold right now
        stays queued (bounded — overflow already 503s at submit) while
        in-flight work retires and frees pages; if nothing is in
        flight to free them, it fails honestly instead of waiting
        forever."""
        while True:
            with self._cond:
                free = [i for i, r in enumerate(self._slots) if r is None]
                if not free or not self._queue:
                    break
                req = self._queue.pop()
                # Same locked step as the pop: drain()/heartbeat()
                # must never observe the gap where the request has
                # left the queue but is not yet tracked as admitting.
                self._admitting = req
            requeued = False
            try:
                # Deadline gate at the slot boundary, BEFORE prefill:
                # a request whose deadline expired while queued sheds
                # here — the engine never burns a prefill on work it
                # cannot finish in time ("zero post-prefill deadline
                # timeouts"). Requeued preempts carry sunk prefill
                # cost, but an expired deadline still ends them.
                if req.deadline is not None \
                        and time.monotonic() >= req.deadline:
                    self._count_shed("kfx_lm_deadline_shed_total")
                    req._finish(DeadlineInfeasible(
                        "deadline expired while queued "
                        f"(waited {time.monotonic() - req.t_enqueue:.2f}s)"))
                    continue
                self._admit(req, free[0])
            except PageAllocError as e:
                if self._active_count() == 0:
                    req._finish(e)
                else:
                    with self._cond:
                        self._queue.push_front(req)
                    requeued = True
            except Exception as e:
                # A failed prefill (compile/OOM) fails THIS request —
                # the req is not in a slot yet, so the loop-level
                # failure net would never resolve its future. (_admit
                # itself handles the donated-carry rebuild when the
                # failure was mid-dispatch.) One poisoned request fails
                # alone; the loop keeps serving everyone else.
                req._finish(e)
            finally:
                self._admitting = None
            if requeued:
                break

    def _resolve_adapter(self, req: Request) -> int:
        """The request's adapter id for this admission: acquire (and
        page in, if needed) its named adapter, pinning the slot for
        the request's residency. A LOAD failure — bad artifact or the
        ``engine.adapter_load`` chaos point — honors the
        ``adapter_fallback`` knob: "base" degrades the request to the
        base model (-1, counted kfx_lm_adapter_fallbacks_total);
        "error" re-raises AdapterLoadError (-> 503 + Retry-After).
        AdapterSlotError (every slot pinned) always propagates — it is
        pool pressure, handled exactly like KV-page exhaustion."""
        if self._apool is None or not req.adapter:
            return -1
        try:
            return self._apool.acquire(req.adapter)
        except AdapterSlotError:
            raise
        except AdapterLoadError:
            if self.adapter_fallback == "error":
                raise
            self._reg().counter(
                "kfx_lm_adapter_fallbacks_total",
                "Requests degraded to base-only after an adapter "
                "load failure (adapters.fallback=base).").inc(
                    1, model=self.name)
            return -1

    def _resolve_model(self, req: Request) -> int:
        """The request's weight-pool slot for this admission: acquire
        (and swap in, if needed) its named model — or the engine's
        resident default — pinning the slot for the request's
        residency. There is NO fallback knob: serving a request under
        the wrong weights is never a degrade option, so a load failure
        propagates as WeightLoadError (-> 503 + Retry-After; the
        router re-dispatches or the activator spawns a dedicated
        replica). WeightSlotError (every slot worn by in-flight work)
        is pool pressure, handled exactly like KV-page exhaustion —
        the request requeues while slots retire."""
        if self._wpool is None:
            return -1
        return self._wpool.acquire(req.model or self.model_default)

    def _on_model_evict(self, name: str, root: bytes) -> None:
        """Weight-pool eviction hook (loop thread, fired BEFORE the
        slot can be refilled): drop the evicted model's live prefix
        chains so a stale prefix hit can never pair with freshly
        swapped-in weights. Host-offloaded pages need no sweep — their
        chain keys embed the per-load generation, so a reloaded model
        roots a fresh chain that can never match them."""
        if self._prefix is not None:
            self._prefix.drop_root(root)

    def _params_for(self, slot: int):
        """The param tree a dispatch for ``slot`` must run under: the
        slot's pinned pool model, or the engine's resident params
        outside pool mode."""
        wid = int(self._wids[slot])
        if self._wpool is None or wid < 0:
            return self.params
        return self._wpool.tree(wid)

    def _root_for(self, req: Request, aid: int, wid: int) -> bytes:
        """Prefix-cache chain root for an admission. Pool mode roots
        at the weight slot's ``name@generation`` (fresh per load, so
        chains built against evicted weights never match again);
        otherwise the resolved ADAPTER name — cached pages hold
        adapter-specific KV, and a request degraded to base-only
        (adapters.fallback=base) must chain with base traffic."""
        if wid >= 0:
            return self._wpool.root(wid)
        return req.adapter.encode() if (req.adapter and aid >= 0) \
            else b""

    def _admit(self, req: Request, slot: int) -> None:
        # Fault point: admission failure/latency — the engine-era
        # analogue of serving.predict (docs/chaos.md).
        inj = chaos.draw("engine.admit", target=self.name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                req._finish(RuntimeError(
                    f"chaos[engine.admit]: {self.name}"))
                return
        # Adapter resolution BEFORE any page work: prompt KV is
        # adapter KV, so the id must be live for the prefill dispatch.
        # AdapterLoadError in fallback="error" mode fails this request
        # via _admit_ready's net; AdapterSlotError requeues like page
        # pressure. Any later failure that does not install the
        # request in the slot releases the pin (the finally below).
        aid = self._resolve_adapter(req)
        wid = -1
        try:
            # Weight-pool resolution rides the same contract: the slot
            # must be pinned (and the swap done) before any page work,
            # since prompt KV is decoded under these weights.
            # WeightSlotError requeues like page pressure;
            # WeightLoadError fails this request via _admit_ready's
            # net (503 + Retry-After — never the wrong weights).
            wid = self._resolve_model(req)
            self._admit_resolved(req, slot, aid, wid)
        finally:
            # _fail_inflight (donated-dispatch death) may already have
            # dropped every pin via release_all(); ref 0 means this
            # pin is gone — releasing again would corrupt the count.
            if aid >= 0 and self._slots[slot] is not req \
                    and self._apool.ref[aid] > 0:
                self._apool.release(aid)
            if wid >= 0 and self._slots[slot] is not req \
                    and self._wpool.ref[wid] > 0:
                self._wpool.release(wid)

    def _admit_resolved(self, req: Request, slot: int,
                        aid: int, wid: int = -1) -> None:
        import jax

        from ..models.generate import pow2_bucket

        L, ps = self.cfg.max_seq_len, self.page_size
        # Recompute continuation: a preempted request re-prefills
        # prompt + already-generated (teacher forcing — same values
        # the incremental decode wrote, so the completion stays exact)
        # and keeps appending to the same token list.
        full = req.prompt + req.tokens
        n = len(full)
        remaining = req.max_new - len(req.tokens)
        bucket = pow2_bucket(n, L - remaining)
        # Shared-prefix reuse, capped at n-1: the last prompt token
        # must run through the model to produce the next-token logits.
        # The chain roots at the weight slot's name@generation in pool
        # mode, else the resolved ADAPTER name: cached pages hold
        # model/adapter-specific KV, so identical tokens under
        # different weights never collide (_root_for).
        root = self._root_for(req, aid, wid)
        shared: List[int] = []
        cow = None
        matched = 0
        key = root
        if self._prefix is not None:
            shared, cow, matched, key = self._prefix.match(
                full, n - 1, root=root)
            if self._offload is not None and cow is None \
                    and len(self._offload):
                # Page-aligned matches may extend from the host
                # offload tier (compiled promote-on-hit); a COW match
                # already consumed mid-page tokens, past which the
                # chain cannot fold.
                matched, key = self._promote_offloaded(
                    full, n - 1, shared, matched, key, root=root)
        tail = full[matched:]
        if self.prefill_chunk_tokens and \
                len(tail) > self.prefill_chunk_tokens:
            # Chunked admission: the tail is longer than one chunk, so
            # a monolithic prefill here would stall every active slot
            # past the chunk bound. Place the request and leave a
            # cursor; the loop advances it one chunk per iteration.
            return self._admit_chunked(req, slot, full, n, remaining,
                                       bucket, shared, cow, matched,
                                       key, aid, wid)
        P = pow2_bucket(len(tail), L)
        fn = self._prefill_for(P)       # compile OUTSIDE the mutation
        cfn = self._copy_fn() if cow else None  # window: failing here
        # leaves the pool untouched and fails only this request.
        first_own = len(shared)  # COW lands in the first owned block
        # Blocks this admission must place: the COW copy target plus
        # every block the prompt tail writes ([matched, n-1]); decode
        # blocks are allocated lazily at chunk boundaries. The matched
        # pages (and the COW source) are pinned FIRST: _alloc_pages
        # reclaims LRU cache pages, and an unpinned just-matched page
        # (ref 1, cache-only) could be evicted and handed back as a
        # tail page — one physical page at two logical blocks.
        pinned = shared + ([cow[0]] if cow is not None else [])
        for pg in pinned:
            self._mgr.incref(pg)
        want_blocks = list(range(first_own, (n - 1) // ps + 1))
        if bucket // ps > (n - 1) // ps:
            # Reserve the FIRST decode block too when the pad gap puts
            # it past the prompt blocks: an admission that cannot place
            # one decodable token would be preempted (youngest) at the
            # very next chunk boundary, wasting the whole prefill in an
            # admit/preempt ping-pong under pool pressure.
            want_blocks.append(bucket // ps)
        try:
            pages = self._alloc_pages(len(want_blocks))
        except PageAllocError:
            self._mgr.decref(pinned)  # back to their cache/slot refs
            raise
        try:
            # A row is admitted only if both classes can serve it: the
            # window class holds the whole tail while it is written.
            self._place_window_blocks(slot, matched, n - 1)
        except PageAllocError:
            self._mgr.decref(pinned + pages)
            raise
        row = np.full((self.n_blocks,), -1, np.int32)
        for j, pg in enumerate(shared):
            row[j] = pg
        for b, pg in zip(want_blocks, pages):
            row[b] = pg
        self._count_admission(req, matched, n)
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :len(tail)] = tail
        t_dispatch = time.monotonic()
        # The span times the ENQUEUE of the prefill: on a backend that
        # dispatches asynchronously (the TPU) the call returns at once
        # and the program's device time is in the trace under its own
        # name (kfx_prefill_<P>), paid by the host at the next
        # engine.device_wait.
        with obs_trace.span("engine.admit", trace_id=req.trace_id,
                            parent_id=req.span_id, model=self.name,
                            slot=str(slot), bucket=str(bucket),
                            prefix_tokens=str(matched)), \
                self._phase("engine.prefill.enqueue"):
            try:
                if cow is not None:
                    self._cache = cfn(self._cache,
                                      np.int32(row[first_own]),
                                      np.int32(cow[0]),
                                      np.int32(cow[1]))
                self._cache, self._logbuf = self._keep_counts(fn(
                    self.params if wid < 0 else self._wpool.tree(wid),
                    self._cache, self._logbuf, tokens,
                    row[None, :] if self._wmgr is None else (
                        row[None, :], np.array(self._wtables[slot])[None]),
                    np.int32(slot), np.int32(len(tail)),
                    np.int32(matched), self._lora_tree(),
                    np.full((1,), aid, np.int32)), 2)
            except Exception as e:
                if self._donate:
                    # A failed DISPATCH may have died after the
                    # donation, deleting the carried buffers — and with
                    # them every active slot's KV. Fail those requests
                    # honestly and rebuild, or the next decode_chunk
                    # crashes on deleted arrays.
                    self._fail_inflight(e)
                else:
                    self._mgr.decref(pinned + pages)
                    self._release_window(slot)
                raise
        # A monolithic prefill is decode stall for every active slot —
        # the head-of-line blocking the chunked path exists to bound.
        self._iter_stall += time.monotonic() - t_dispatch
        self._stamp_prefill(req)
        if cow is not None:
            # The COW source's pin was only for the copy window; the
            # slot keeps the private clone, not the source.
            self._mgr.decref([cow[0]])
        self._tables[slot] = row
        self._slot_pages[slot] = shared + pages
        self._free_behind_window(slot, n)
        # Register this prompt's pages for future admissions: every
        # full prompt page not already cached, chained after the
        # matched prefix, plus the partially-filled boundary page.
        if self._prefix is not None:
            # ``key`` covers the matched FULL pages; block len(shared)
            # (COW'd or fresh) chains from it like any other page.
            # (Admission stats were counted by _count_admission above
            # — once per client request, never for preempt-requeues.)
            h = key
            for b in range(len(shared), n // ps):
                h = self._prefix.insert_full(
                    h, full[b * ps:(b + 1) * ps], int(row[b]),
                    root=root)
            if n % ps and row[n // ps] >= 0:
                self._prefix.insert_partial(
                    h, full[(n // ps) * ps:n], int(row[n // ps]),
                    root=root)
        self._pos[slot] = n
        self._loc[slot] = bucket
        self._max_loc[slot] = bucket + remaining - 1
        self._active[slot] = True
        self._produced[slot] = len(req.tokens)
        if req.rng is not None:
            # Preemption stashed the live per-request stream (one split
            # per emitted token, so this equals a replay); restoring it
            # skips O(tokens) sequential split dispatches that would
            # stall every active slot on re-admission.
            self._rngs[slot] = req.rng
        else:
            self._rngs[slot] = np.asarray(
                jax.random.PRNGKey(req.seed), np.uint32)
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._stop[slot] = req.stop
        self._max_new[slot] = req.max_new
        self._pending[slot] = -1  # next iteration samples from logbuf
        self._aids[slot] = aid
        self._wids[slot] = wid    # slot owns the weight-pool pin now
        self._slots[slot] = req
        req.slot = slot
        if self.spec:
            self._admit_draft(req, slot, full, n)

    def _admit_draft(self, req: Request, slot: int, full: List[int],
                     n: int) -> None:
        """Prefill the FULL prompt into the slot's draft pages. Any
        failure — draft-pool exhaustion or a broken dispatch — degrades
        this slot to non-speculative decode (it still completes through
        the verify window at one token per iteration) instead of
        failing an admission the TARGET pool already accepted."""
        from ..models.generate import pow2_bucket

        ps, L = self.page_size, self.cfg.max_seq_len
        try:
            Pf = pow2_bucket(n, L)
            fn = self._draft_prefill_for(Pf)  # compile outside mutation
            pages = self._alloc_draft_pages((n - 1) // ps + 1)
        except PageAllocError:
            self._spec_degraded += 1
            self._spec_ok[slot] = False
            return
        row = np.full((self.n_blocks,), -1, np.int32)
        for b, pg in enumerate(pages):
            row[b] = pg
        tokens = np.zeros((1, Pf), np.int32)
        tokens[0, :n] = full
        try:
            self._draft_cache = fn(
                self.draft_params, self._draft_cache, tokens,
                row[None, :], np.int32(n),
                self._lora_tree(draft=True),
                np.full((1,), int(self._aids[slot]), np.int32))
        except Exception:
            if self._donate:
                # The donated draft cache may be dead — every slot's
                # draft KV with it. Rebuild and degrade them all; the
                # TARGET pool is untouched, so decode stays correct.
                for s in range(self.n_slots):
                    self._release_draft(s)
                self._draft_mgr = BlockManager(self.draft_n_pages,
                                               self.page_size)
                self._draft_cache = self._init_cache(draft=True)
            else:
                self._draft_mgr.decref(pages)
            self._spec_degraded += self.n_slots if self._donate else 1
            self._spec_ok[slot] = False
            return
        self._draft_tables[slot] = row
        self._draft_slot_pages[slot] = pages
        self._spec_ok[slot] = True

    def _count_admission(self, req: Request, matched: int,
                         n: int) -> bool:
        """First-admission stats, counted exactly once per CLIENT
        request (``req.counted``): the queue-wait histogram, the
        prefix-hit counters for ``matched`` reused tokens, and the
        admitted-prompt-token total (the prefill_skipped_frac
        denominator). A requeued preempt — mid-decode or mid-prefill —
        is recompute, not client traffic: it counts nothing. ONE
        implementation for the monolithic and chunked admission paths;
        returns whether this admission was counted (the chunked path's
        late re-match follows the same verdict)."""
        if req.counted:
            return False
        req.counted = True
        req.t_admitted = time.monotonic()
        req.it_admitted = self._iterations
        if self.flight is not None:
            self.flight.event(req, "admit", matched=matched, prompt=n)
        wait = req.t_admitted - req.t_enqueue
        # Trailing queue-wait EWMA: the deadline feasibility check's
        # estimate of what a newly-enqueued request will wait. Biased
        # toward recency (0.3) so a drained backlog stops shedding
        # within a few admissions.
        self._qwait_ewma = wait if self._qwait_ewma <= 0.0 \
            else 0.7 * self._qwait_ewma + 0.3 * wait
        self._reg().histogram(
            "kfx_lm_queue_wait_seconds",
            "Decode-engine admission wait (enqueue to slot prefill).",
            buckets=QUEUE_WAIT_BUCKETS).observe(wait, model=self.name)
        if self._apool is not None:
            # Per-tenant traffic accounting — the fairness story's
            # observable ("" requests count as the base tenant).
            self._reg().counter(
                "kfx_lm_adapter_requests_total",
                "Admitted client requests by adapter tenant.").inc(
                    1, model=self.name,
                    adapter=req.adapter or "base")
        if req._usage is not None:
            # Admission-side billing: request + prompt tokens, once —
            # gated by the same ``req.counted`` latch as everything
            # above, so preemption-by-recompute never double-bills.
            req._usage.admit(req.tenant, req.qos,
                             req.adapter or "base", len(req.prompt))
        if self._prefix is not None:
            if matched:
                self._count_prefix_hit(matched)
            self._prompt_tokens += n
        return True

    def _count_prefix_hit(self, matched: int) -> None:
        self._prefix.hits += 1
        self._prefix.tokens_reused += matched
        self._reg().counter(
            "kfx_lm_prefix_cache_hits_total",
            "Admissions that reused cached prefix pages.").inc(
                1, model=self.name)

    def _clone_cow_page(self, pinned: List[int], cow) -> int:
        """One COW boundary-page clone for the chunked paths: allocate
        a private page, run the compiled copy of ``cow`` (source page
        already pinned via ``pinned``), release the SOURCE's pin (the
        slot keeps the clone). On failure every pin this call was
        trusted with is released first: PageAllocError re-raises with
        ``pinned`` decref'd; a failed DISPATCH re-raises after either
        the donated-carry rebuild (_fail_inflight — the monolithic
        path's contract) or, non-donated, decref of ``pinned`` + the
        clone. Callers decide whether the raise dooms the admission
        (_admit_chunked) or just the optimization
        (_late_prefix_match)."""
        cfn = self._copy_fn()   # compile OUTSIDE the mutation window
        try:
            page = self._alloc_pages(1)[0]
        except PageAllocError:
            self._mgr.decref(pinned)
            raise
        try:
            self._cache = cfn(self._cache, np.int32(page),
                              np.int32(cow[0]), np.int32(cow[1]))
        except Exception as e:
            if self._donate:
                self._fail_inflight(e)
            else:
                self._mgr.decref(pinned + [page])
            raise
        self._mgr.decref([cow[0]])
        return page

    def _admit_chunked(self, req: Request, slot: int, full: List[int],
                       n: int, remaining: int, bucket: int,
                       shared: List[int], cow, matched: int,
                       key: bytes, aid: int = -1,
                       wid: int = -1) -> None:
        """Chunked admission: place the request in the slot WITHOUT a
        prompt prefill dispatch — pin the matched prefix pages (and
        clone the COW boundary page, a one-page compiled copy), record
        the queue wait and prefix stats exactly as the monolithic path
        does, and leave a prefill cursor for the loop to advance one
        page-multiple chunk per iteration. The slot is NOT active
        until the cursor completes, so the decode dispatch masks it;
        it IS in ``_slots``, so drain/heartbeat/occupancy count it as
        in-flight work."""
        first_own = len(shared)
        # Matched pages (and the COW source) pinned BEFORE any
        # allocation, same eviction hazard as the monolithic path.
        pinned = shared + ([cow[0]] if cow is not None else [])
        for pg in pinned:
            self._mgr.incref(pg)
        # Chunked admission stamps the SAME engine.admit span the
        # monolithic path does (the documented per-admission trace
        # node); the prefill dispatches follow as engine.prefill_chunk
        # children of the request's trace.
        with obs_trace.span("engine.admit", trace_id=req.trace_id,
                            parent_id=req.span_id, model=self.name,
                            slot=str(slot), bucket=str(bucket),
                            prefix_tokens=str(matched), chunked="1"):
            cow_page = None
            if cow is not None:
                cow_page = self._clone_cow_page(pinned, cow)
        row = np.full((self.n_blocks,), -1, np.int32)
        for j, pg in enumerate(shared):
            row[j] = pg
        own: List[int] = []
        if cow_page is not None:
            row[first_own] = cow_page
            own.append(cow_page)
        fresh = self._count_admission(req, matched, n)
        self._tables[slot] = row
        self._slot_pages[slot] = shared + own
        self._active[slot] = False
        self._pending[slot] = -1
        self._aids[slot] = aid
        self._wids[slot] = wid    # slot owns the weight-pool pin now
        self._slots[slot] = req
        req.slot = slot
        self._prefilling[slot] = {
            "req": req, "full": full, "n": n, "next": matched,
            "key": key, "reg_block": len(shared),
            "root": self._root_for(req, aid, wid),
            "bucket": bucket, "remaining": remaining,
            # Whether THIS admission was counted as a client
            # admission — the late re-match's hit accounting must
            # follow the same verdict (a requeued preempt re-matching
            # its own registered pages is recompute, not reuse).
            "fresh": fresh}

    def _advance_prefill(self) -> None:
        """Advance chunked prefill by at most ONE chunk dispatch per
        engine iteration (oldest cursor first — FIFO service, so a
        long prompt behind a longer one still makes progress). Pages
        allocate at the chunk boundary; pool exhaustion preempts the
        youngest in-flight slot, which may be this cursor itself (its
        request re-queues whole as a recompute continuation)."""
        if not self._prefilling:
            return
        from ..models.generate import pow2_bucket

        slot = min(self._prefilling,
                   key=lambda s: self._prefilling[s]["req"].t_enqueue)
        cur = self._prefilling[slot]
        req = cur["req"]
        if self._prefix is not None and cur["next"] == 0 \
                and not self._slot_pages[slot]:
            # Late prefix match, once per cursor before its first
            # chunk: admission matched nothing (the page owner may
            # have been mid-prefill in the SAME wave), but by now the
            # owner's completed chunks have registered — re-match so
            # same-wave identical prompts still share (the PR-7
            # one-wave sharing contract, preserved under chunking).
            if not self._late_prefix_match(slot, cur):
                return  # donated COW death: engine state was rebuilt
        L, ps = self.cfg.max_seq_len, self.page_size
        start, n = cur["next"], cur["n"]
        length = min(self.prefill_chunk_tokens, n - start)
        last = start + length >= n
        P = pow2_bucket(length, L)
        try:
            fn = self._prefill_for(P)
        except Exception as e:
            # A compile failure poisons THIS request only.
            self._abort_prefill(slot, e)
            return
        # Page budget: this chunk's blocks, plus (on the final chunk)
        # the first decode block when the pad gap puts it past the
        # prompt blocks — the monolithic path's ping-pong guard.
        blocks = list(range(start // ps, (start + length - 1) // ps + 1))
        if last and cur["bucket"] // ps > (n - 1) // ps:
            blocks.append(cur["bucket"] // ps)
        while True:
            try:
                for b in blocks:
                    if self._tables[slot, b] < 0:
                        pg = self._alloc_pages(1)[0]
                        self._tables[slot, b] = pg
                        self._slot_pages[slot].append(pg)
                self._place_window_blocks(slot, start, start + length - 1)
                break
            except PageAllocError as e:
                victims = [s for s, r in enumerate(self._slots)
                           if r is not None]
                if len(victims) <= 1:
                    # Nothing in flight can free pages: fail honestly
                    # (the 503 + Retry-After shed contract).
                    self._abort_prefill(slot, e)
                    return
                victim = self._preempt_victim(victims)
                self._preempt(victim)
                if victim == slot:
                    return  # this cursor was the victim: re-queued
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :length] = cur["full"][start:start + length]
        t_dispatch = time.monotonic()
        # Like engine.admit, the span times the chunk's ENQUEUE.
        with obs_trace.span("engine.prefill_chunk",
                            trace_id=req.trace_id,
                            parent_id=req.span_id, model=self.name,
                            slot=str(slot), start=str(start),
                            tokens=str(length)), \
                self._phase("engine.prefill.enqueue"):
            try:
                self._cache, self._logbuf = self._keep_counts(fn(
                    self._params_for(slot), self._cache, self._logbuf,
                    tokens, self._tables_arg(slot),
                    np.int32(slot), np.int32(length), np.int32(start),
                    self._lora_tree(),
                    np.full((1,), int(self._aids[slot]), np.int32)), 2)
            except Exception as e:
                if self._donate:
                    self._fail_inflight(e)
                else:
                    self._abort_prefill(slot, e)
                return
        self._iter_stall += time.monotonic() - t_dispatch
        self._stamp_prefill(req)
        self._reg().counter(
            "kfx_lm_prefill_chunks_total",
            "Prompt-chunk prefill dispatches (chunked admission).").inc(
                1, model=self.name)
        if self.flight is not None:
            self.flight.event(req, "prefill_chunk", start=start,
                              tokens=length)
        cur["next"] = start + length
        self._free_behind_window(slot, start + length)
        self._register_prefix_pages(slot, cur, final=last)
        if last:
            self._finish_prefill(slot)

    def _stamp_prefill(self, req: Request) -> None:
        """A prompt dispatch of ``req`` was just enqueued: until its
        first token lands, that is the end of its prefill span (the
        TTFT split of the flight recorder's ``timing``)."""
        if req.t_first == 0.0:
            req.t_prefill_end = time.monotonic()
            req.prefill_iters = self._iterations - req.it_admitted + 1

    def _late_prefix_match(self, slot: int, cur: Dict[str, Any]
                           ) -> bool:
        """Adopt a prefix-cache match for a cursor that admitted
        against an empty match: pin the matched full pages, clone the
        COW boundary page, and fast-forward the cursor — exactly the
        admission-time hit, just discovered at first-chunk time. A
        failed COW page allocation (or a non-donated dispatch failure)
        abandons the match and plain chunked prefill continues —
        sharing is an optimization, never a requirement. Returns False
        only when a DONATED COW dispatch died (the carried cache is
        gone, every request already failed via _fail_inflight — the
        caller must stop touching this cursor)."""
        req = cur["req"]
        # Same resolved-id rule as admission: a degraded slot (aid -1)
        # holds base KV and must match the base chain; a pool slot
        # matches only its weight generation's chain.
        shared, cow, matched, key = self._prefix.match(
            cur["full"], cur["n"] - 1,
            root=self._root_for(req, int(self._aids[slot]),
                                int(self._wids[slot])))
        if not matched:
            return True
        pinned = shared + ([cow[0]] if cow is not None else [])
        for pg in pinned:
            self._mgr.incref(pg)
        cow_page = None
        if cow is not None:
            try:
                cow_page = self._clone_cow_page(pinned, cow)
            except PageAllocError:
                return True   # match abandoned; plain prefill continues
            except Exception:
                # Donated-carry death: the helper already failed every
                # request and rebuilt — stop touching this cursor.
                # Non-donated: pins released, the plain chunked
                # prefill continues unharmed.
                return not self._donate
        own = list(shared)
        for j, pg in enumerate(shared):
            self._tables[slot, j] = pg
        if cow_page is not None:
            self._tables[slot, len(shared)] = cow_page
            own.append(cow_page)
        self._slot_pages[slot] = own
        cur["next"] = matched
        cur["key"] = key
        cur["reg_block"] = len(shared)
        if cur["fresh"]:
            self._count_prefix_hit(matched)
        return True

    def _register_prefix_pages(self, slot: int, cur: Dict[str, Any],
                               final: bool) -> None:
        """Incremental prefix-cache registration: every full prompt
        page the cursor has fully covered chains after the matched
        prefix (so same-prefix admissions later in the wave already
        share), and the partially-filled boundary page registers once
        at completion — the monolithic path's coverage, chunk by
        chunk."""
        if self._prefix is None:
            return
        ps = self.page_size
        n, full = cur["n"], cur["full"]
        h = cur["key"]
        root = cur.get("root", b"")
        covered = min(cur["next"], n) // ps
        b = cur["reg_block"]
        while b < covered:
            h = self._prefix.insert_full(
                h, full[b * ps:(b + 1) * ps],
                int(self._tables[slot, b]), root=root)
            b += 1
        cur["key"], cur["reg_block"] = h, b
        if final and n % ps and self._tables[slot, n // ps] >= 0:
            self._prefix.insert_partial(
                h, full[(n // ps) * ps:n],
                int(self._tables[slot, n // ps]), root=root)

    def _finish_prefill(self, slot: int) -> None:
        """Cursor complete: the slot's pages hold the whole prompt at
        its dense-equivalent locations and ``logbuf[slot]`` the last
        real token's logits — flip the slot active with exactly the
        state the monolithic path would have left, then prefill the
        draft (one full-prompt dispatch at draft depth)."""
        import jax

        cur = self._prefilling.pop(slot)
        req = cur["req"]
        n, bucket = cur["n"], cur["bucket"]
        self._pos[slot] = n
        self._loc[slot] = bucket
        self._max_loc[slot] = bucket + cur["remaining"] - 1
        self._active[slot] = True
        self._produced[slot] = len(req.tokens)
        if req.rng is not None:
            # A preempt stash from an earlier DECODING life of this
            # request; restoring it keeps the sampled stream exact.
            self._rngs[slot] = req.rng
        else:
            self._rngs[slot] = np.asarray(
                jax.random.PRNGKey(req.seed), np.uint32)
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._stop[slot] = req.stop
        self._max_new[slot] = req.max_new
        self._pending[slot] = -1
        if self.spec:
            self._admit_draft(req, slot, cur["full"], n)

    def _abort_prefill(self, slot: int, error: BaseException) -> None:
        """Tear a prefill cursor down, releasing the slot's pages
        whole, and fail its request ALONE with ``error`` (the
        poisoned-request contract — the loop keeps serving everyone
        else). Pool-pressure recompute requeues go through _preempt,
        never here."""
        cur = self._prefilling.pop(slot)
        self._slots[slot] = None
        self._release_slot(slot)
        cur["req"]._finish(error)

    def _ensure_chunk_pages(self) -> None:
        """Allocate, at the chunk boundary, every page the next chunk
        may write (decode locations loc..loc+k-1, capped at the slot's
        budget). On pool exhaustion the YOUNGEST active slot is
        preempted — pages freed, request re-queued at the front as a
        recompute continuation — so the oldest requests always make
        progress; a lone slot that still cannot be placed fails with
        PageAllocError."""
        while True:
            try:
                for slot, req in enumerate(self._slots):
                    if req is None or not self._active[slot]:
                        continue
                    lo = int(self._loc[slot])
                    hi = min(lo + self.chunk_tokens - 1,
                             int(self._max_loc[slot]))
                    for b in range(lo // self.page_size,
                                   hi // self.page_size + 1):
                        if self._tables[slot, b] < 0:
                            pg = self._alloc_pages(1)[0]
                            self._tables[slot, b] = pg
                            self._slot_pages[slot].append(pg)
                    # The window class's pages lie by position.
                    at = int(self._pos[slot])
                    self._place_window_blocks(slot, at, at + hi - lo)
                return
            except PageAllocError:
                # Victims include mid-prefill slots: their pages are
                # as reclaimable as a decoder's, and preempting the
                # youngest keeps the oldest requests progressing.
                victims = [s for s, r in enumerate(self._slots)
                           if r is not None]
                if len(victims) <= 1:
                    raise
                self._preempt(self._preempt_victim(victims))

    def _preempt_victim(self, victims: List[int]) -> int:
        """QoS-aware preemption ordering: a batch slot is always
        sacrificed before any interactive slot (True > False in the
        key), and within a class the YOUNGEST goes first — the oldest
        requests of the better class always make progress."""
        return max(victims,
                   key=lambda s: (self._slots[s].qos == "batch",
                                  self._slots[s].t_enqueue))

    def _preempt(self, slot: int) -> None:
        # Paid before the row goes back to the queue: any thread may
        # finish a queued request (drain, close, a batch request shed
        # at submit), and the tokens it is owed come before that.
        self._pay_owed(overlapped=False)
        req = self._slots[slot]
        if self._active[slot]:
            # Stash the live RNG stream so re-admission resumes it
            # (greedy ignores it; sampled must not fork from the
            # replayed run). A mid-PREFILL victim has consumed no
            # stream yet — any earlier stash stays authoritative.
            req.rng = np.array(self._rngs[slot], np.uint32)
        self._prefilling.pop(slot, None)
        self._slots[slot] = None
        self._release_slot(slot)
        self._reg().counter(
            "kfx_lm_kv_preemptions_total",
            "Slots preempted (recompute-requeued) on pool exhaustion."
            ).inc(1, model=self.name)
        self._preempts += 1
        req.preempts += 1
        if self.flight is not None:
            self.flight.event(req, "preempt", slot=slot)
        with self._cond:
            self._queue.push_front(req)

    def _ensure_spec_pages(self) -> None:
        """Spec-mode page budget for the next verify window, at the
        iteration boundary: a speculating slot writes target locations
        loc..loc+k (pending + k proposals) and the same span in the
        draft pool (k proposals + the catch-up token); a degraded slot
        only ever writes the pending token at loc. Target-pool
        exhaustion preempts the youngest slot (both pools freed, PR-7
        semantics); DRAFT-pool exhaustion just degrades the slot —
        speculation is an optimization, never a capacity constraint."""
        while True:
            try:
                for slot, req in enumerate(self._slots):
                    if req is None or not self._active[slot]:
                        continue
                    lo = int(self._loc[slot])
                    hi = lo
                    if self._spec_ok[slot]:
                        hi = min(lo + self.propose_tokens,
                                 int(self._max_loc[slot]))
                    for b in range(lo // self.page_size,
                                   hi // self.page_size + 1):
                        if self._tables[slot, b] < 0:
                            pg = self._alloc_pages(1)[0]
                            self._tables[slot, b] = pg
                            self._slot_pages[slot].append(pg)
                break
            except PageAllocError:
                victims = [s for s, r in enumerate(self._slots)
                           if r is not None]
                if len(victims) <= 1:
                    raise
                self._preempt(self._preempt_victim(victims))
        for slot, req in enumerate(self._slots):
            if req is None or not self._active[slot] \
                    or not self._spec_ok[slot]:
                continue
            lo = int(self._loc[slot])
            hi = min(lo + self.propose_tokens, int(self._max_loc[slot]))
            try:
                for b in range(lo // self.page_size,
                               hi // self.page_size + 1):
                    if self._draft_tables[slot, b] < 0:
                        pg = self._alloc_draft_pages(1)[0]
                        self._draft_tables[slot, b] = pg
                        self._draft_slot_pages[slot].append(pg)
            except PageAllocError:
                self._release_draft(slot)
                self._spec_degraded += 1

    def _sample_host(self, logits: np.ndarray, req: Request,
                     rng: np.ndarray) -> Tuple[int, np.ndarray]:
        """One host-side sample from a [V] logits row with the
        request's knobs, mirroring models/generate._sample semantics:
        greedy is argmax (same first-max tie-break as jnp.argmax, so
        parity holds bitwise); sampled draws inverse-CDF from the
        warped distribution with a uniform from the slot's jax PRNG
        stream (deterministic per seed). Returns (token, next_rng)."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits)), rng
        import jax

        nxt, sub = jax.random.split(jax.numpy.asarray(rng))
        u = float(jax.random.uniform(sub))
        scaled = logits.astype(np.float64) / max(req.temperature, 1e-6)
        if req.top_k > 0:
            kth = np.sort(scaled)[max(logits.shape[-1] - req.top_k, 0)]
            scaled = np.where(scaled < kth, -np.inf, scaled)
        probs = np.exp(scaled - np.max(scaled))
        probs /= probs.sum()
        tok = int(np.searchsorted(np.cumsum(probs), u))
        return min(tok, logits.shape[-1] - 1), np.asarray(nxt, np.uint32)

    def _land(self, h: _Handout, slot: int, fresh: List[int],
              done: bool) -> None:
        """What the next enqueue needs of a row's new tokens, at once:
        they join the request (a preempted row re-prefills from them),
        the first one stamps ``t_first`` where it lands, and a row that
        is done gives up its slot and pages for the next admission.
        What the world outside is owed for them, the sink's tokens and
        the request's finish, goes on the hand-out ``h``."""
        req = self._slots[slot]
        if fresh:
            req.tokens.extend(fresh)
            h.emitted += len(fresh)
            if req.t_first == 0.0:
                req.t_first = time.monotonic()
                if self.flight is not None:
                    self.flight.event(req, "first_token")
        if done:
            self._slots[slot] = None
            self._release_slot(slot)
        if done or (fresh and req.on_token is not None):
            h.rows.append((req, fresh, done))

    def _pay_owed(self, overlapped: bool) -> None:
        """THE hand-out routine (loop thread): pay, oldest first, what
        the iterations owe (_Handout). Called right after a decode
        chunk's enqueue (``overlapped``: the device works on that chunk
        meanwhile), and with nothing enqueued wherever waiting would
        let something overtake the tokens or leave them on a parked
        loop: at the end of an iteration that leaves no row decoding,
        ahead of a control job, a preemption, a wedge and
        ``_fail_inflight``. A request's tokens reach its sink in order
        and before its end marker on every path."""
        reg = self._reg()
        while self._owed:
            h = self._owed.popleft()
            try:
                if h.chunks:
                    with self._phase("engine.bookkeeping"):
                        # Before the tokens go out: a client that holds
                        # its last token finds the layers' counts of it
                        # in /metrics, and they grow with the chunk
                        # counter, not a delivery later.
                        self._flush_counts(h.counts, h.chunks)
            finally:
                # Whatever the registry does, a request that left its
                # slot is finished here or nowhere.
                with self._phase("engine.deliver"):
                    for req, fresh, done in h.rows:
                        for t in fresh:
                            req._notify(t)
                        if done:
                            req._finish()
            with self._phase("engine.bookkeeping"):
                if h.emitted:
                    reg.counter("kfx_lm_generated_tokens_total",
                                "Tokens generated since startup.").inc(
                                    h.emitted, model=self.name)
                if h.chunks:
                    reg.counter(
                        "kfx_lm_engine_handouts_total",
                        "Decode-chunk / verify hand-outs (tokens to the "
                        "sinks, finishes, counts, gauges), by whether "
                        "the next chunk was enqueued first (overlapped="
                        "\"1\": the hand-out ran while the device "
                        "worked) or nothing was (\"0\").").inc(
                            h.chunks, model=self.name,
                            overlapped="1" if overlapped else "0")
                self._touch_gauges()
                if self.flight is not None and h.iteration is not None:
                    self._record_flight(h.iteration)

    def _emit_host(self, h: _Handout, slot: int, toks: List[int]) -> None:
        """Land emitted tokens in the slot's request, honoring the
        stop-token and max_new contracts exactly as the chunked path
        does (the stop token itself is never emitted; the slot retires
        at the first hit or when the budget fills); retires the slot
        itself when done."""
        req = self._slots[slot]
        fresh: List[int] = []
        done = False
        for t in toks:
            if req.stop >= 0 and t == req.stop:
                done = True
                break
            fresh.append(int(t))
            if len(req.tokens) + len(fresh) >= req.max_new:
                done = True
                break
        self._land(h, slot, fresh, done)

    def _spec_once(self) -> bool:
        """One speculative iteration: host-sample pending tokens for
        fresh admissions, budget the window's pages, dispatch the
        fused propose+verify+accept step, then apply the accept
        verdicts to the per-slot bookkeeping. False where no window
        was dispatched."""
        import jax

        # Fresh admissions (and requeued preempts) have no pending
        # token: sample it from the prefill logits — the same token
        # the chunked path's first decode step would produce. Active
        # only: a mid-prefill slot's logbuf row is not final yet.
        fresh = [s for s, r in enumerate(self._slots)
                 if r is not None and self._active[s]
                 and self._pending[s] < 0]
        if fresh:
            with self._phase("engine.device_wait"):
                logbuf = np.asarray(self._logbuf)  # waits on the prefill
            # Speculation pays at once, here and below: the verify
            # window's verdicts are read on the host before the next
            # window can be enqueued, so there is nothing to run behind.
            first = _Handout(None)
            self._owed.append(first)
            for s in fresh:
                req = self._slots[s]
                tok, self._rngs[s] = self._sample_host(
                    logbuf[s], req, self._rngs[s])
                self._emit_host(first, s, [tok])
                if self._slots[s] is not None:
                    self._pending[s] = tok
            self._pay_owed(overlapped=False)
        if not self._active_count():
            return False
        # Chaos: a full-rejection wave — every slot verifies as if its
        # draft proposed garbage. Throughput falls to the
        # non-speculative floor; outputs stay exact (the bonus token
        # is the target's own sample either way).
        wave_off = False
        inj = chaos.draw("engine.spec_verify", target=self.name)
        if inj is not None:
            if inj.delay > 0:
                time.sleep(inj.delay)
            if inj.mode != "delay":
                wave_off = True
        self._ensure_spec_pages()
        if not self._active_count():
            return False
        self._maybe_kv_quant_chaos()
        k = self.propose_tokens
        draft_live = self._spec_ok & self._active
        spec_on = np.zeros_like(draft_live) if wave_off else draft_live
        oldest = min((r for r in self._slots if r is not None),
                     key=lambda r: r.t_enqueue)
        n_active = self._active_count()
        with obs_trace.span("engine.verify", trace_id=oldest.trace_id,
                            parent_id=oldest.span_id, model=self.name,
                            slots=str(n_active), k=str(k)) as sp:
            with self._phase("engine.decode.enqueue"):
                out = self._spec_step()(
                    self.params, self.draft_params, self._cache,
                    self._draft_cache,
                    np.ascontiguousarray(self._tables),
                    np.ascontiguousarray(self._draft_tables),
                    self._pending, self._pos, self._loc, self._max_loc,
                    spec_on, draft_live, self._active, self._rngs,
                    self._temp, self._topk, self._lora_tree(),
                    self._lora_tree(draft=True),
                    np.ascontiguousarray(self._aids))
            (self._cache, self._draft_cache, rngs, D, A, bonus) = out
            with self._phase("engine.device_wait"):
                D = np.asarray(D)          # [B, k]
                A = np.asarray(A)          # [B]
                bonus = np.asarray(bonus)  # [B]
                self._rngs = np.array(rngs)
            sp.attrs["accepted"] = str(int(
                sum(int(A[s]) for s in range(self.n_slots)
                    if spec_on[s])))
        reg = self._reg()
        # The verify window IS spec mode's decode-chunk dispatch: one
        # family for "hot decode dispatches" in both engine modes.
        h = self._take_handout()
        proposed = int(np.sum(spec_on))
        accepted = 0
        for slot in range(self.n_slots):
            req = self._slots[slot]
            if req is None or not self._active[slot]:
                continue
            a = int(A[slot])
            if spec_on[slot]:
                accepted += a
                # Per-request speculation attribution (spec_accept
                # in the flight-recorder breakdown).
                req.spec_prop += k
                req.spec_acc += a
            toks = [int(t) for t in D[slot, :a]] \
                + [int(bonus[slot])]
            self._emit_host(h, slot, toks)
            if self._slots[slot] is not None:
                # Cursor advance = pending + accepted proposals now
                # in both pools; the bonus becomes the new pending
                # token.
                self._pos[slot] += a + 1
                self._loc[slot] += a + 1
                self._pending[slot] = int(bonus[slot])
        with self._phase("engine.bookkeeping"):
            if proposed:
                self._spec_proposed += proposed * k
                self._spec_accepted += accepted
                with self._spec_lock:
                    self._spec_window.append(
                        (time.monotonic(), proposed * k, accepted))
                reg.counter(
                    "kfx_lm_spec_proposed_total",
                    "Draft tokens proposed to the verify dispatch."
                    ).inc(proposed * k, model=self.name)
                reg.counter(
                    "kfx_lm_spec_accepted_total",
                    "Draft proposals the target model accepted."
                    ).inc(accepted, model=self.name)
        self._pay_owed(overlapped=False)
        return True

    def _take_handout(self) -> _Handout:
        """The hand-out of the dispatch whose outputs the host has just
        read, owed from here on: it takes what the programs counted up
        to that dispatch, which has all run (the counts of a dispatch
        enqueued later would make the flush wait for it)."""
        h = _Handout(self._iterations, 1, self._counts_pending)
        self._counts_pending = []
        self._owed.append(h)
        return h

    def _decode_once(self) -> bool:
        """One decode dispatch (a chunk, or with a draft a verify
        window) for every active row; False where none was made. What
        the chunk BEFORE owes is paid right behind this one's enqueue."""
        if self.spec:
            return self._spec_once()
        self._ensure_chunk_pages()
        if not self._active_count():
            return False  # every slot preempted away
        self._maybe_kv_quant_chaos()
        oldest = min((r for r in self._slots if r is not None),
                     key=lambda r: r.t_enqueue)
        n_active = self._active_count()
        with obs_trace.span("engine.chunk", trace_id=oldest.trace_id,
                            parent_id=oldest.span_id, model=self.name,
                            slots=str(n_active),
                            k=str(self.chunk_tokens)):
            if self._wpool is None:
                with self._phase("engine.decode.enqueue"):
                    out = self._decode()(
                        self.params, self._cache, self._logbuf,
                        self._tables_arg(), self._pos,
                        self._loc, self._active, self._produced,
                        self._rngs, self._temp, self._topk, self._stop,
                        self._max_new, self._lora_tree(),
                        np.ascontiguousarray(self._aids))
                (self._cache, self._logbuf, pos, loc, active,
                 produced, rngs, toks, emits) = self._keep_counts(out, 9)
                self._pay_owed(overlapped=True)
                # The first host read blocks until the chunk (and any
                # prefill enqueued before it) has run on the device.
                with self._phase("engine.device_wait"):
                    # np.array (copy): admission mutates these rows in
                    # place, and a bare asarray of a jax output is a
                    # read-only view.
                    self._pos = np.array(pos)
                    self._loc = np.array(loc)
                    self._active = np.array(active)
                    self._produced = np.array(produced)
                    self._rngs = np.array(rngs)
                    toks = np.asarray(toks)    # [k, B]
                    emits = np.asarray(emits)  # [k, B] bool
                if self._wmgr is not None:
                    for slot in np.flatnonzero(self._active):
                        self._free_behind_window(
                            int(slot), int(self._pos[slot]))
            else:
                toks, emits = self._decode_grouped()
        h = self._take_handout()
        for slot, req in enumerate(self._slots):
            if req is None or slot in self._prefilling:
                # A mid-prefill slot rides the dispatch fully
                # masked: inactive by design, not retired —
                # finishing it here would return an empty
                # completion.
                continue
            hits = np.flatnonzero(emits[:, slot])
            self._land(h, slot, toks[hits, slot].tolist(),
                       not self._active[slot])
        return True

    @property
    def _counted(self) -> Tuple[str, ...]:
        """What this configuration's layers count beside their result,
        in _COUNT_FAMILIES' order: a selection's layers the positions
        cached for a query and the locations the main attention read
        (models/latent.py COUNTS, a layer), routed-expert layers what
        they dispatched (models/experts.py COUNTS). The programs hand
        the counts back after their own outputs."""
        c = self.cfg
        return tuple(what for what, on in (
            ("sparse", c.kv_lora_rank > 0 and c.index_topk > 0),
            ("moe", c.expert_layers > 0),
            ("ssm", c.has_slot_state),
            ("window", c.has_window_pages))
            if on)

    def _keep_counts(self, out, n: int):
        """The first ``n`` outputs of a model program; what its layers
        (and, after them, the decode chunk's sampler) counted beside
        them waits, on the device, for ``_flush_counts``."""
        if len(out) > n:
            self._counts_pending.append(out[n:])
        return out[:n]

    def _flush_counts(self, pending: Sequence[Any], chunks: int) -> None:
        """Into the registry, what the programs counted up to a decode
        dispatch (``pending``, _take_handout's) and the dispatch itself
        (``chunks``): counted together, so that a reader who holds the
        layers' counts against the chunk counter between two scrapes
        never sees a chunk on one side only. Called once that
        dispatch's outputs are on the host, so every program in
        ``pending`` has run and no read here waits."""
        reg = self._reg()
        reg.counter("kfx_lm_engine_chunks_total",
                    "Decode-chunk / verify dispatches.").inc(
                        chunks, model=self.name)
        sums = {"sparse": np.zeros(2, np.int64),
                "moe": np.zeros(5, np.int64),
                "ssm": np.zeros(3, np.int64),
                "window": np.zeros(3, np.int64),
                "sample": np.zeros(3, np.int64)}
        decode = {what: np.zeros_like(c) for what, c in sums.items()}
        # A prefill hands back its layers' counts, a decode chunk the
        # sampler's after them.
        for counts in pending:
            a_chunk = len(counts) > len(self._counted)
            for what, c in zip(self._counted + ("sample",), counts):
                c = np.asarray(c, np.int64)
                c = c.reshape(-1, c.shape[-1]).sum(0)
                sums[what] += c
                if a_chunk:
                    decode[what] += c
        flat = lambda d: [v for c in d.values() for v in c]
        for (family, text), v, of_chunks in zip(
                _COUNT_FAMILIES.items(), flat(sums), flat(decode)):
            reg.counter(family, text).inc(int(v), model=self.name)
            if family in _DECODE_TWINS:
                reg.counter(_DECODE_TWINS[family]).inc(
                    int(of_chunks), model=self.name)

    def _decode_grouped(self):
        """One decode chunk across every active slot, in WEIGHT-POOL
        mode: active slots group by their pinned weight slot and the
        SAME compiled chunk executable runs once per group — params
        are a traced argument, so N models share one AOT compilation —
        with the group's slots active and everyone else masked.
        Per-group outputs merge under the group mask: the dispatch ran
        with other slots masked, so its verdicts for them (active
        forced False, rng streams advanced by the scan) are artifacts
        of the mask, not state — each slot's pos/loc/active/produced/
        rng advance exactly once, in its own group's dispatch, keeping
        every per-slot stream byte-identical to a dedicated engine's.
        toks/emits accumulate (emit is active-gated, so groups never
        overlap); cache/logbuf chain through the donation — safe
        because the compiled step gates BOTH per row (cache writes at
        location -1, logits carry under the active mask), so a
        foreign group's dispatch cannot touch a masked slot's KV or
        its pending next-token logits."""
        fn = self._decode()
        wids = sorted({int(self._wids[s])
                       for s in range(self.n_slots)
                       if self._active[s]})
        toks_all = np.zeros((self.chunk_tokens, self.n_slots),
                            np.int32)
        emits_all = np.zeros((self.chunk_tokens, self.n_slots),
                             np.bool_)
        for wid in wids:
            gmask = np.asarray(self._active & (self._wids == wid))
            with self._phase("engine.decode.enqueue"):
                out = fn(
                    self._wpool.tree(wid), self._cache, self._logbuf,
                    self._tables_arg(), self._pos,
                    self._loc, gmask, self._produced, self._rngs,
                    self._temp, self._topk, self._stop, self._max_new,
                    self._lora_tree(),
                    np.ascontiguousarray(self._aids))
            (self._cache, self._logbuf, pos, loc, active, produced,
             rngs, toks, emits) = self._keep_counts(out, 9)
            self._pay_owed(overlapped=True)  # behind the first group's
            with self._phase("engine.device_wait"):
                toks = np.asarray(toks)
                emits = np.asarray(emits)
            # np.where allocates fresh writable arrays, preserving
            # the copy-before-mutation contract of the single-model
            # path.
            self._pos = np.where(gmask, np.asarray(pos), self._pos)
            self._loc = np.where(gmask, np.asarray(loc), self._loc)
            self._produced = np.where(gmask, np.asarray(produced),
                                      self._produced)
            self._active = np.where(gmask, np.asarray(active),
                                    self._active)
            self._rngs = np.where(gmask[:, None], np.asarray(rngs),
                                  self._rngs)
            toks_all = np.where(emits, toks, toks_all)
            emits_all = emits_all | emits
        return toks_all, emits_all

    def _fail_inflight(self, e: BaseException) -> None:
        # Tokens that landed before the failure go out before it does;
        # what a dispatch that died had counted is lost with it.
        self._counts_pending = []
        self._pay_owed(overlapped=False)
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._slots[slot] = None
                req._finish(e)
        self._prefilling.clear()
        if self._apool is not None:
            # Every wearer just failed; loaded adapters stay resident
            # (the stacks are never donated, so a dead dispatch cannot
            # have corrupted them).
            self._apool.release_all()
        self._aids[:] = -1
        if self._wpool is not None:
            # Same contract for pooled model weights: slot trees are
            # never donated, so they survive a dead dispatch intact —
            # only the request pins drop (the pinned default is not
            # refcounted, so it stays unevictable).
            self._wpool.release_all()
        self._wids[:] = -1
        self._active[:] = False
        self._tables[:, :] = -1
        self._slot_pages = [[] for _ in range(self.n_slots)]
        self._mgr = BlockManager(self.n_pages, self.page_size)
        if self._wmgr is not None:
            self._wmgr = BlockManager(self.window_pages, self.page_size)
            self._wtables[:, :] = -1
            self._wfirst[:] = 0
        if self._prefix is not None:
            self._prefix = PrefixCache(self._mgr)
        self._draft_tables[:, :] = -1
        self._draft_slot_pages = [[] for _ in range(self.n_slots)]
        self._spec_ok[:] = False
        self._pending[:] = -1
        if self.spec:
            self._draft_mgr = BlockManager(self.draft_n_pages,
                                           self.page_size)
        if not self._stopped:
            # A dispatch that died mid-donation leaves the carried
            # device buffers invalidated — rebuild so the engine keeps
            # serving the next requests (the fresh pool is all-empty,
            # so no dirty-page invalidation is owed either).
            self._cache = self._init_cache()
            self._logbuf = self._init_logbuf()
            if self.spec:
                self._draft_cache = self._init_cache(draft=True)
        self._touch_gauges()

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Stop the loop and fail every in-flight/queued request (a
        racing submit gets an immediate error, never a timeout)."""
        with self._cond:
            if self._stopped:
                return
            self._stopped = True
            queued = self._queue.drain_all()
            self._cond.notify_all()
        self._thread.join(timeout=10.0)
        err = RuntimeError("engine closed")
        for req in queued:
            req._finish(err)
        self._fail_inflight(err)
