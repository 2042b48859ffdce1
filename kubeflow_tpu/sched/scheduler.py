"""The cluster gang scheduler.

One ``Scheduler`` instance per control plane is the single admission
point between a workload controller deciding "this job needs a gang"
and ``gang.spawn`` actually forking processes. It owns:

  * the **capacity model** — the slice's total chip count (see
    ``slice_capacity``: the host's TPU device nodes, or an emulated
    slice when the plane runs on the CPU) minus the chips reserved by
    admitted gangs;
  * **gang all-or-nothing admission** — a job's full replica set is
    reserved atomically or not at all; a gang can never half-start on
    capacity grounds (the spawn layer already guarantees the same for
    process-level failures);
  * per-namespace **priority-ordered FIFO queues** — higher
    ``runPolicy.schedulingPolicy.priority`` first, then fair share
    (the namespace holding fewer admitted chips wins the tie), then
    submission order. Small-job **backfill** keeps the slice busy while
    a wide job waits at the head, with a starvation guard: a head
    passed over ``BACKFILL_STARVATION_LIMIT`` times stops further
    backfill until it admits;
  * **preemption** — when the head outranks running work and cannot
    fit, the lowest-priority victims (youngest first: least work lost)
    are suspended via ``runPolicy.suspend``, which makes the training
    operator tear the gang down; the runner's checkpoint contract means
    the victim resumes from its latest saved step when the scheduler
    re-admits it. A storm guard bounds the blast radius:
    ``PREEMPTION_COOLDOWN_S`` between cycles and
    ``MAX_VICTIMS_PER_CYCLE`` victims each.

Wakeups are event-driven: controllers register a waker per kind, and
every release/suspend/admit re-runs the schedule pass and enqueues the
jobs whose turn arrived — there is no quota busy-poll.

Observability: ``kfx_sched_queue_seconds{namespace,priority}``,
``kfx_sched_admitted_total`` / ``kfx_sched_preempted_total``, and
pull-time capacity/queue-depth gauges via ``collect``; every
preemption evaluates the ``sched.preempt`` chaos point (an injection
aborts that cycle — the storm guard's failure path under test).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from .. import chaos
from ..core.store import Conflict, NotFound, ResourceStore

# Spec/annotation surface.
PRIORITY_ANNOTATION = "kubeflow.org/priority"
PREEMPTED_ANNOTATION = "kubeflow.org/preempted-by"

# Queue-condition reasons (the training operator copies them onto the
# job's Queued condition and events).
REASON_CAPACITY = "WaitingForCapacity"
REASON_QUOTA = "QuotaExceeded"
REASON_UNSCHEDULABLE = "Unschedulable"

_QUEUED = "Queued"
_ADMITTED = "Admitted"

DEFAULT_SLICE_CHIPS = 32


def accelerator_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes
    (``/dev/accel*``, or one numbered vfio group per chip as on v5e):
    what a worker's ``jax.devices()`` will find, learned without
    starting a backend here — a chip has one owner, and the plane must
    never be it."""
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def slice_capacity() -> int:
    """Total schedulable chips. ``KFX_SLICE_CHIPS`` wins. A plane that
    is not on the CPU (``JAX_PLATFORMS`` other than "cpu") has the
    chips its host exposes: a job that asks for more stays queued as
    Unschedulable instead of being handed virtual devices. On the CPU
    (the tests, a laptop) the slice is emulated: the virtual-mesh
    ``--xla_force_host_platform_device_count`` XLA flag (vmeshenv.py
    sets it), else the host core count with a generous floor — the
    emulation runs one process per chip, so a small core count
    oversubscribes gracefully rather than starving wide jobs."""
    env = os.environ.get("KFX_SLICE_CHIPS", "")
    if env:
        try:
            n = int(env)
            if n > 0:
                return n
        except ValueError:
            pass
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        chips = accelerator_chips()
        if chips:
            return chips
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    if m:
        return int(m.group(1))
    return max(os.cpu_count() or 1, DEFAULT_SLICE_CHIPS)


def job_chips(job) -> int:
    """A training job's chip footprint in the capacity model. Kinds
    with a declarative parallelism spec report it via ``chip_count()``
    (a 2x4 tensor-by-pipeline JAXJob reserves 8 chips as ONE gang even
    when a single worker process drives all 8 virtual devices);
    everything else reserves one chip per replica process."""
    fn = getattr(job, "chip_count", None)
    if callable(fn):
        try:
            return max(int(fn()), 1)
        except Exception:
            pass  # fall through to the replica count
    try:
        return max(int(job.total_replicas()), 1)
    except Exception:
        return 1


def job_priority(job) -> int:
    """A training job's scheduling priority (higher preempts lower):
    ``runPolicy.schedulingPolicy.priority``, else the
    ``kubeflow.org/priority`` annotation, else 0."""
    try:
        p = job.run_policy().priority
    except Exception:
        p = 0
    if p:
        return p
    try:
        return int(job.metadata.annotations.get(PRIORITY_ANNOTATION, 0))
    except (TypeError, ValueError):
        return 0


@dataclasses.dataclass
class _Entry:
    """One job known to the scheduler — queued or holding a reservation."""

    ukey: str            # "<kind-lower>/<namespace>/<name>" (gang-key shape)
    kind: str
    name: str
    namespace: str
    chips: int
    priority: int
    seq: int             # admission order (FIFO within priority class)
    enqueued_at: float   # wall clock, for the queue-seconds histogram
    state: str = _QUEUED
    preempted: bool = False    # suspended by the scheduler, auto-resumes
    preempting: bool = False   # head with an in-flight preemption cycle
    passed_over: int = 0       # backfill jumps over this head so far
    reason: str = REASON_CAPACITY
    message: str = ""
    # Serving reservations (InferenceService replica sets) are ELASTIC:
    # always _ADMITTED, holding `chips` granted chips while `wanted`
    # records the autoscaler's target — the schedule pass grows chips
    # toward wanted as capacity frees. They are never preemption
    # victims (a serving replica has no checkpoint to resume from).
    serving: bool = False
    wanted: int = 0


class Scheduler:
    """Capacity-aware gang admission for every training-job kind."""

    PREEMPTION_COOLDOWN_S = 1.0
    MAX_VICTIMS_PER_CYCLE = 2
    BACKFILL_STARVATION_LIMIT = 16

    def __init__(self, store: ResourceStore, capacity: Optional[int] = None,
                 metrics=None):
        self.store = store
        self.capacity = capacity if capacity else slice_capacity()
        self.metrics = metrics
        self._lock = threading.RLock()
        self._entries: Dict[str, _Entry] = {}
        self._seq = 0
        self._last_preempt = float("-inf")
        self._wakers: Dict[str, Callable[[str], None]] = {}

    # -- wiring --------------------------------------------------------------
    def register_waker(self, kind: str, fn: Callable[[str], None]) -> None:
        """``fn(namespace/name)`` is called when a queued job of ``kind``
        is admitted (or resumed) — the controller's workqueue add."""
        with self._lock:
            self._wakers[kind] = fn

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def _ukey(kind: str, name: str, namespace: str) -> str:
        return f"{kind.lower()}/{namespace}/{name}"

    def _reserved_locked(self, namespace: Optional[str] = None) -> int:
        return sum(e.chips for e in self._entries.values()
                   if e.state == _ADMITTED
                   and (namespace is None or e.namespace == namespace))

    def _wake(self, e: _Entry) -> None:
        fn = self._wakers.get(e.kind)
        if fn is not None:
            try:
                fn(f"{e.namespace}/{e.name}")
            except Exception:
                pass  # a broken waker must never wedge the schedule pass

    # -- the admission contract ---------------------------------------------
    def try_admit(self, job) -> Tuple[bool, str, str]:
        """Ask for the job's full replica set. Returns
        ``(admitted, reason, message)`` — ``admitted`` means the chips
        are reserved and the gang may spawn; otherwise the job is
        queued and its controller will be woken when its turn comes."""
        ukey = self._ukey(job.KIND, job.name, job.namespace)
        with self._lock:
            e = self._entries.get(ukey)
            if e is None:
                e = _Entry(ukey=ukey, kind=job.KIND, name=job.name,
                           namespace=job.namespace,
                           chips=job_chips(job),
                           priority=job_priority(job), seq=self._seq,
                           enqueued_at=time.time())
                self._seq += 1
                self._entries[ukey] = e
            else:
                # A re-apply may have resized or re-prioritised the job.
                if e.state == _QUEUED:
                    e.chips = job_chips(job)
                    e.priority = job_priority(job)
            if e.state == _ADMITTED:
                return True, "", ""
            self._schedule_locked()
            if e.state == _ADMITTED:
                return True, "", ""
            return False, e.reason, e.message

    def release(self, kind: str, name: str, namespace: str) -> None:
        """The job no longer needs chips (finished or deleted): drop its
        entry and hand the freed capacity to the queue."""
        with self._lock:
            if self._entries.pop(self._ukey(kind, name, namespace),
                                 None) is None:
                return
            self._schedule_locked()

    # -- serving reservations (elastic, autoscaler-driven) --------------------
    SERVING_KIND = "InferenceService"

    def resize_serving(self, name: str, namespace: str, wanted: int,
                       priority: int = 5) -> int:
        """Elastic chip reservation for one InferenceService's replica
        set (one replica process == one chip, like gang members).
        Returns the chips *granted* now — shrink is immediate (freed
        chips wake queued training), growth takes free capacity first
        and then preempts strictly-lower-priority training for the
        shortfall (bounded by the preemption storm guard; remaining
        shortfall is granted as victims drain, waking the serving
        controller). ``wanted <= 0`` drops the reservation."""
        ukey = self._ukey(self.SERVING_KIND, name, namespace)
        with self._lock:
            e = self._entries.get(ukey)
            if wanted <= 0:
                if e is not None:
                    self._entries.pop(ukey, None)
                    self._schedule_locked()
                return 0
            wanted = min(wanted, self.capacity)
            if e is None:
                e = _Entry(ukey=ukey, kind=self.SERVING_KIND, name=name,
                           namespace=namespace, chips=0, priority=priority,
                           seq=self._seq, enqueued_at=time.time(),
                           state=_ADMITTED, serving=True, reason="")
                self._seq += 1
                self._entries[ukey] = e
            e.priority = priority
            e.wanted = wanted
            if wanted < e.chips:
                e.chips = wanted
                self._schedule_locked()  # returned chips wake the queue
            else:
                self._grow_serving_locked(wake=False)
                if e.chips < e.wanted:
                    self._preempt_for_serving_locked(e)
            return e.chips

    def serving_granted(self, name: str, namespace: str) -> int:
        with self._lock:
            e = self._entries.get(
                self._ukey(self.SERVING_KIND, name, namespace))
            return e.chips if e is not None else 0

    def _grow_serving_locked(self, wake: bool = True) -> None:
        """Hand free chips to under-granted serving reservations,
        highest priority first. Runs at the top of every schedule pass:
        latency-critical serving growth takes freed capacity before
        queued training backfills it (the arbitration policy —
        docs/scheduling.md)."""
        pending = sorted((e for e in self._entries.values()
                          if e.serving and e.state == _ADMITTED
                          and e.wanted > e.chips),
                         key=lambda e: (-e.priority, e.seq))
        for e in pending:
            free = self.capacity - self._reserved_locked()
            if free <= 0:
                return
            grant = min(e.wanted - e.chips, free)
            if grant > 0:
                e.chips += grant
                if wake:
                    self._wake(e)

    def _preempt_for_serving_locked(self, e: _Entry) -> None:
        """Preempt lower-priority training for a serving shortfall.
        Unlike a gang head, a serving reservation is elastic — every
        chip freed is a replica that can serve — so partial relief is
        taken even when the full shortfall cannot be met."""
        head = _Entry(ukey=e.ukey, kind=e.kind, name=e.name,
                      namespace=e.namespace, chips=e.wanted - e.chips,
                      priority=e.priority, seq=e.seq,
                      enqueued_at=e.enqueued_at)
        self._maybe_preempt_locked(
            head, self.capacity - self._reserved_locked(), partial=True)

    def on_suspended(self, job) -> bool:
        """The training operator tore the gang down on
        ``runPolicy.suspend``. A scheduler-preempted job goes back to
        the queue (it resumes automatically, oldest-first among its
        priority class); a user-suspended job leaves the scheduler
        entirely. Returns True when the job stays queued for resume."""
        ukey = self._ukey(job.KIND, job.name, job.namespace)
        was_preempted = bool(
            job.metadata.annotations.get(PREEMPTED_ANNOTATION))
        with self._lock:
            e = self._entries.get(ukey)
            if e is None and was_preempted:
                # Plane restart recovery: the annotation is the durable
                # record that this suspend was ours to undo.
                e = _Entry(ukey=ukey, kind=job.KIND, name=job.name,
                           namespace=job.namespace,
                           chips=job_chips(job),
                           priority=job_priority(job), seq=self._seq,
                           enqueued_at=time.time(), preempted=True)
                self._seq += 1
                self._entries[ukey] = e
            kept = False
            if e is not None:
                if e.preempted or was_preempted:
                    if e.state == _ADMITTED:
                        e.state = _QUEUED
                        e.enqueued_at = time.time()
                    e.preempted = True
                    kept = True
                else:
                    self._entries.pop(ukey, None)
            self._schedule_locked()
        return kept

    # -- the schedule pass ---------------------------------------------------
    def _order_locked(self, queued: List[_Entry]) -> List[_Entry]:
        """Priority desc, then fair share across namespaces (fewer
        admitted chips first), then FIFO submission order."""
        used = {}
        for e in self._entries.values():
            if e.state == _ADMITTED:
                used[e.namespace] = used.get(e.namespace, 0) + e.chips
        return sorted(queued, key=lambda e: (-e.priority,
                                             used.get(e.namespace, 0),
                                             e.seq))

    def _quota_blocked_locked(self, e: _Entry) -> Optional[str]:
        """The per-namespace cap (profile ``count/jobs`` /
        ``count/replicas``), enforced here against the scheduler's own
        admitted set — operators/platform.py installs the numbers, the
        scheduler is the one gate (no check/spawn race between
        controllers)."""
        try:
            profile = self.store.try_get("Profile", e.namespace)
        except Exception:
            return None  # a store fault must not wedge scheduling
        if profile is None:
            return None
        hard = (profile.resource_quota().get("hard")) or {}
        max_jobs = hard.get("count/jobs")
        max_replicas = hard.get("count/replicas")
        if max_jobs is None and max_replicas is None:
            return None
        jobs = sum(1 for o in self._entries.values()
                   if o.state == _ADMITTED and o.namespace == e.namespace)
        replicas = self._reserved_locked(e.namespace)
        if max_jobs is not None and jobs + 1 > int(max_jobs):
            return (f"profile {profile.name}: count/jobs={max_jobs} "
                    f"exhausted ({jobs} active)")
        if max_replicas is not None and \
                replicas + e.chips > int(max_replicas):
            return (f"profile {profile.name}: count/replicas={max_replicas} "
                    f"exhausted ({replicas} active + {e.chips} requested)")
        return None

    def _schedule_locked(self) -> None:
        """Admit queued entries until nothing more fits: head first, then
        backfill in order; preempt for a blocked high-priority head.
        Under-granted serving reservations drink first (elastic growth
        beats queued batch work for freed capacity)."""
        self._grow_serving_locked()
        skip: set = set()  # failed a resume write this pass; retry later
        while True:
            queued = [e for e in self._entries.values()
                      if e.state == _QUEUED and e.ukey not in skip]
            if not queued:
                return
            order = self._order_locked(queued)
            free = self.capacity - self._reserved_locked()
            head = order[0]
            pick = None
            head_capacity_blocked = False
            for e in order:
                if e.chips > self.capacity:
                    e.reason = REASON_UNSCHEDULABLE
                    e.message = (f"needs {e.chips} chips but the slice "
                                 f"has {self.capacity}")
                    continue
                quota_msg = self._quota_blocked_locked(e)
                if quota_msg is None and e.chips <= free:
                    pick = e
                    break
                if quota_msg is not None:
                    e.reason, e.message = REASON_QUOTA, quota_msg
                else:
                    e.reason = REASON_CAPACITY
                    e.message = (f"queued for {e.chips} chip(s); "
                                 f"{free} free of {self.capacity}")
                if e is head:
                    head_capacity_blocked = quota_msg is None
                    if e.preempting or \
                            e.passed_over >= self.BACKFILL_STARVATION_LIMIT:
                        break  # no backfill past a preempting/starved head
            if pick is None:
                if head_capacity_blocked:
                    self._maybe_preempt_locked(head, free)
                return
            if not self._admit_locked(pick):
                skip.add(pick.ukey)
                continue
            if pick is not head and head_capacity_blocked:
                # Only capacity-blocked heads age toward the starvation
                # guard: a quota-blocked head waits on its own
                # namespace, and stopping backfill would not help it.
                head.passed_over += 1

    def _admit_locked(self, e: _Entry) -> bool:
        if e.preempted and not self._resume_locked(e):
            return False  # un-suspend failed; stays queued, retried later
        e.state = _ADMITTED
        e.passed_over = 0
        e.preempting = False
        e.reason = e.message = ""
        if self.metrics is not None:
            self.metrics.histogram(
                "kfx_sched_queue_seconds",
                "Time jobs wait in the scheduler queue before admission.",
            ).observe(max(time.time() - e.enqueued_at, 0.0),
                      namespace=e.namespace, priority=str(e.priority))
            self.metrics.counter(
                "kfx_sched_admitted_total",
                "Gangs admitted by the scheduler.",
            ).inc(1, namespace=e.namespace)
        self._wake(e)
        return True

    def _resume_locked(self, e: _Entry) -> bool:
        """Undo a preemption: clear ``runPolicy.suspend`` so the training
        operator recreates the gang (which restores from the latest
        checkpoint). Returns False when the store write failed."""
        try:
            job = self.store.try_get(e.kind, e.name, e.namespace)
        except Exception:
            return False
        if job is None:
            self._entries.pop(e.ukey, None)
            return False
        rp = job.spec.setdefault("runPolicy", {})
        rp["suspend"] = False
        if "suspend" in job.spec:
            job.spec["suspend"] = False
        job.metadata.annotations.pop(PREEMPTED_ANNOTATION, None)
        try:
            self.store.update(job)
            self.store.record_event(
                job, "Normal", "SchedulerResumed",
                f"capacity available again; resuming from the latest "
                f"checkpoint after preemption "
                f"({time.time() - e.enqueued_at:.1f}s queued)")
        except (Conflict, NotFound):
            return False
        except Exception:
            return False  # store chaos: retried on the next pass
        e.preempted = False
        return True

    def _maybe_preempt_locked(self, head: _Entry, free: int,
                              partial: bool = False) -> None:
        """Suspend the lowest-priority victims so ``head`` can fit —
        bounded by the cooldown and the per-cycle victim cap (the
        preemption-storm guard). ``partial`` (serving growth) takes
        victims even when the full need cannot be met: each freed chip
        is one more serving replica, unlike a gang that is all-or-
        nothing. Serving reservations are never victims."""
        now = time.monotonic()
        if now - self._last_preempt < self.PREEMPTION_COOLDOWN_S:
            return
        pool = sorted(
            (e for e in self._entries.values()
             if e.state == _ADMITTED and not e.preempted and not e.serving
             and e.priority < head.priority),
            key=lambda e: (e.priority, -e.seq))  # lowest prio, youngest 1st
        # Chips already being freed by in-flight preemptions (victims
        # suspended but their gangs not yet torn down) count toward the
        # head: without this a multi-cycle preemption would read as
        # "pointless" halfway through and strand the head.
        inflight = sum(e.chips for e in self._entries.values()
                       if e.state == _ADMITTED and e.preempted)
        need = head.chips - free - inflight
        take: List[_Entry] = []
        for v in pool:
            if need <= 0 or len(take) >= self.MAX_VICTIMS_PER_CYCLE:
                break
            take.append(v)
            need -= v.chips
        if not take:
            return
        if not partial and need > 0 and len(take) == len(pool):
            return  # even preempting everything eligible cannot fit head
        self._last_preempt = now
        suspended = 0
        for v in take:
            try:
                # Fault point: a preemption that fails to land (the
                # reference's eviction API call erroring). The cycle
                # aborts; the cooldown paces the retry.
                chaos.fail_or_delay("sched.preempt", RuntimeError,
                                    f"preempt {v.ukey}", target=v.ukey)
            except RuntimeError:
                break
            if self._preempt_one_locked(v, head):
                suspended += 1
        if suspended:
            head.preempting = True

    def _preempt_one_locked(self, v: _Entry, head: _Entry) -> bool:
        try:
            job = self.store.try_get(v.kind, v.name, v.namespace)
        except Exception:
            return False
        if job is None:
            self._entries.pop(v.ukey, None)
            return False
        rp = job.spec.setdefault("runPolicy", {})
        rp["suspend"] = True
        job.metadata.annotations[PREEMPTED_ANNOTATION] = head.ukey
        try:
            self.store.update(job)
        except Exception:
            return False
        v.preempted = True
        try:
            self.store.record_event(
                job, "Warning", "Preempted",
                f"preempted by {head.ukey} (priority {head.priority} > "
                f"{v.priority}); suspending — resumes from its latest "
                f"checkpoint when capacity frees")
        except Exception:
            pass
        if self.metrics is not None:
            self.metrics.counter(
                "kfx_sched_preempted_total",
                "Gangs preempted (suspended) by higher-priority jobs.",
            ).inc(1, namespace=v.namespace)
        return True

    # -- observability -------------------------------------------------------
    def collect(self, reg) -> None:
        """Pull-time collector for /metrics: capacity, reservations and
        queue depth (the counters/histogram are recorded live)."""
        with self._lock:
            reserved = self._reserved_locked()
            serving = sum(e.chips for e in self._entries.values()
                          if e.serving and e.state == _ADMITTED)
            serving_wanted = sum(e.wanted for e in self._entries.values()
                                 if e.serving and e.state == _ADMITTED)
            depth: Dict[str, int] = {}
            for e in self._entries.values():
                if e.state == _QUEUED:
                    depth[e.namespace] = depth.get(e.namespace, 0) + 1
        reg.gauge("kfx_sched_capacity_chips",
                  "Total schedulable chips of the slice."
                  ).set(self.capacity)
        reg.gauge("kfx_sched_reserved_chips",
                  "Chips reserved by admitted gangs.").set(reserved)
        reg.gauge("kfx_sched_serving_chips",
                  "Chips granted to elastic serving reservations "
                  "(subset of reserved).").set(serving)
        reg.gauge("kfx_sched_serving_wanted_chips",
                  "Chips serving reservations are asking for "
                  "(>= granted while a scale-up waits on capacity)."
                  ).set(serving_wanted)
        g = reg.gauge("kfx_sched_queue_depth",
                      "Jobs waiting in the scheduler queue by namespace.")
        g.clear()
        for ns, n in depth.items():
            g.set(n, namespace=ns)

    def snapshot(self) -> Dict:
        """Queue + capacity state for ``kfx queue``."""
        with self._lock:
            queued = self._order_locked(
                [e for e in self._entries.values() if e.state == _QUEUED])
            running = sorted(
                (e for e in self._entries.values() if e.state == _ADMITTED),
                key=lambda e: e.seq)
            return {
                "capacity": self.capacity,
                "reserved": self._reserved_locked(),
                "free": self.capacity - self._reserved_locked(),
                "running": [self._row(e) for e in running],
                "queue": [self._row(e, pos) for pos, e in
                          enumerate(queued, start=1)],
            }

    @staticmethod
    def _row(e: _Entry, position: Optional[int] = None) -> Dict:
        row = {
            "key": e.ukey, "kind": e.kind, "name": e.name,
            "namespace": e.namespace, "chips": e.chips,
            "priority": e.priority, "state": e.state,
            "preempted": e.preempted,
            "waitedSeconds": round(max(time.time() - e.enqueued_at, 0.0), 3),
            "reason": e.reason, "message": e.message,
        }
        if e.serving:
            row["serving"] = True
            row["wanted"] = e.wanted
        if position is not None:
            row["position"] = position
        return row
