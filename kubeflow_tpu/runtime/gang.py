"""Gang process launcher: all-or-nothing start, liveness, whole-gang restart.

This is the data-plane half of the training operators. Where the reference
creates pods and lets kubelet + a gang scheduler (volcano PodGroup) run
them (SURVEY.md §2.1 common lib), we launch local OS processes directly:

  * all-or-nothing start — if any member fails to spawn, the gang is torn
    down (a distributed job must never half-start);
  * liveness monitoring — a supervisor thread reaps exits;
  * whole-gang restart with exponential backoff — a dead worker invalidates
    the collective (jax.distributed world membership is fixed), so failure
    of one member kills and relaunches all, bounded by backoffLimit; the
    runner contract resumes from the latest orbax checkpoint (SURVEY.md §5.3/5.4);
  * chief-exit success semantics — the job succeeds when the chief replica
    (rank 0 of the elected type) exits 0, like tf-operator's Chief handling;
  * cleanPodPolicy — what happens to still-running members on completion.
"""

from __future__ import annotations

import dataclasses
import os
import re
import signal
import subprocess
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import chaos
from ..api import training as T
from ..obs import trace as obs_trace
from . import lifetime

PENDING = "Pending"
RUNNING = "Running"
SUCCEEDED = "Succeeded"
FAILED = "Failed"
RESTARTING = "Restarting"
KILLED = "Killed"

# k8s $(VAR) references in container command/args (expanded from env).
# "$$" is the k8s escape and collapses to a literal "$", so "$$(VAR)"
# yields the text "$(VAR)" without expansion (matched first, leftmost).
_ENV_VAR_RE = re.compile(r"\$\$|\$\(([A-Za-z_][A-Za-z0-9_]*)\)")


def expand_k8s_refs(text: str, env: Dict[str, str]) -> str:
    """Kubernetes container command/args expansion: $(VAR) from env,
    unresolved refs stay verbatim, $$ escapes to a literal $."""
    return _ENV_VAR_RE.sub(
        lambda m: "$" if m.group(0) == "$$"
        else env.get(m.group(1), m.group(0)), text)


# Exit codes considered retryable under restartPolicy=ExitCode (reference
# semantics: >128 = killed by signal = retryable infrastructure failure).
def _retryable_exit(code: int) -> bool:
    return code > 128 or code < 0


@dataclasses.dataclass
class ProcessSpec:
    replica_type: str
    index: int
    argv: List[str]
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    cwd: Optional[str] = None

    @property
    def id(self) -> str:
        return f"{self.replica_type.lower()}-{self.index}"


@dataclasses.dataclass
class ReplicaStatus:
    state: str = PENDING
    pid: Optional[int] = None
    exit_code: Optional[int] = None
    started_at: Optional[float] = None
    finished_at: Optional[float] = None


@dataclasses.dataclass
class GangStatus:
    phase: str = PENDING
    reason: str = ""
    message: str = ""
    restart_count: int = 0
    replicas: Dict[str, ReplicaStatus] = dataclasses.field(default_factory=dict)

    def counts(self) -> Dict[str, Dict[str, int]]:
        """Per-replica-type {active, succeeded, failed} — the shape of the
        reference's ReplicaStatuses."""
        out: Dict[str, Dict[str, int]] = {}
        for pid, st in self.replicas.items():
            rtype = pid.rsplit("-", 1)[0]
            c = out.setdefault(rtype, {"active": 0, "succeeded": 0, "failed": 0})
            if st.state == RUNNING:
                c["active"] += 1
            elif st.state == SUCCEEDED:
                c["succeeded"] += 1
            elif st.state in (FAILED, KILLED):
                c["failed"] += 1
        return out


class Gang:
    """One supervised process gang (= one training job instance)."""

    GRACE_SECONDS = 3.0
    RESTART_BASE_DELAY = 0.2
    RESTART_MAX_DELAY = 30.0

    def __init__(
        self,
        name: str,
        specs: List[ProcessSpec],
        workdir: str,
        *,
        restart_policy: str = T.RESTART_ON_FAILURE,
        backoff_limit: Optional[int] = 3,
        active_deadline: Optional[float] = None,
        clean_policy: str = T.CLEAN_POD_RUNNING,
        chief_replica_type: str = "",
        on_change: Optional[Callable[["Gang"], None]] = None,
        restart_env_hook: Optional[
            Callable[[int], Dict[str, Dict[str, str]]]] = None,
        trace_id: str = "",
        parent_span_id: str = "",
    ):
        self.name = name
        self.specs = specs
        self.workdir = workdir
        self.restart_policy = restart_policy
        self.backoff_limit = backoff_limit
        self.active_deadline = active_deadline
        self.clean_policy = clean_policy
        self.chief_replica_type = chief_replica_type or (
            specs[0].replica_type if specs else "")
        self.on_change = on_change
        # Submission correlation ID (obs.trace): exported to every
        # member as KFX_TRACE_ID and stamped on the log attempt header,
        # so runner output joins the control plane's events on one ID.
        # parent_span_id is the reconcile span that created this gang;
        # each attempt's gang.spawn span hangs under it, and members
        # inherit the spawn span via KFX_SPAN_ID so their own spans
        # join the same trace tree across the process boundary.
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        # Called with the attempt number before each (re)launch; returns
        # env overrides keyed by replica id — used to re-allocate
        # rendezvous ports so a restart (or a port-collision crash) always
        # gets fresh ones. The key "*" applies to every member; a replica
        # id key (e.g. "worker-1") applies to that member only, on top of
        # "*" (TF_CONFIG differs per task). Values are {VAR: value} dicts.
        self.restart_env_hook = restart_env_hook

        self._lock = threading.RLock()
        self._procs: Dict[str, subprocess.Popen] = {}
        self._status = GangStatus(
            replicas={s.id: ReplicaStatus() for s in specs})
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self._started_at: Optional[float] = None
        self.log_dir = os.path.join(workdir, "logs")
        # Keepalive pipe (created when supervision starts, so a Gang that
        # loses GangManager.ensure's create race and is never started
        # leaks no fds): members inherit the read end; the write end lives
        # only in this process. Supervisor death closes it -> EOF ->
        # runners' parent-watch kills their own process group
        # (runtime/lifetime.py).
        self._keepalive_r = self._keepalive_w = -1

    # -- observability -----------------------------------------------------
    def status(self) -> GangStatus:
        with self._lock:
            return GangStatus(
                phase=self._status.phase,
                reason=self._status.reason,
                message=self._status.message,
                restart_count=self._status.restart_count,
                replicas={k: dataclasses.replace(v)
                          for k, v in self._status.replicas.items()},
            )

    def log_path(self, replica_id: str) -> str:
        return os.path.join(self.log_dir, f"{replica_id}.log")

    def _notify(self) -> None:
        if self.on_change is not None:
            try:
                self.on_change(self)
            except Exception:
                pass

    def _set_phase(self, phase: str, reason: str = "", message: str = "") -> None:
        with self._lock:
            self._status.phase = phase
            self._status.reason = reason
            self._status.message = message
        self._notify()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._monitor is not None:
                raise RuntimeError(f"gang {self.name} already started")
            self._monitor = threading.Thread(
                target=self._supervise, name=f"gang-{self.name}", daemon=True)
        self._monitor.start()

    def _launch_all(self, attempt: int) -> bool:
        """All-or-nothing spawn. Returns False if any member failed to start."""
        os.makedirs(self.log_dir, exist_ok=True)
        overrides = {}
        if self.restart_env_hook is not None:
            overrides = self.restart_env_hook(attempt) or {}
        launched: Dict[str, subprocess.Popen] = {}
        preexec = lifetime.make_child_preexec(os.getpid())
        # One gang.spawn span per attempt: runs on the supervisor
        # thread, so trace/parent come from the gang's stored context,
        # not thread-locals. Members inherit its ID (KFX_SPAN_ID) so
        # every runner span lands under this node of the trace tree.
        spawn_sp = obs_trace.start_span(
            "gang.spawn", trace_id=self.trace_id,
            parent_id=self.parent_span_id, gang=self.name,
            attempt=str(attempt), members=str(len(self.specs)))
        try:
            for spec in self.specs:
                # Fault point: member spawn failure — must take the
                # all-or-nothing teardown path below, never half-start.
                chaos.fail_or_delay("gang.spawn", OSError,
                                    f"spawn {self.name}/{spec.id}",
                                    target=spec.id)
                env = dict(os.environ)
                env.update(spec.env)
                env.update(overrides.get("*", {}))
                env.update(overrides.get(spec.id, {}))
                env[lifetime.PARENT_FD_ENV] = str(self._keepalive_r)
                if self.trace_id:
                    env.setdefault("KFX_TRACE_ID", self.trace_id)
                env[obs_trace.SPAN_ENV] = spawn_sp.span_id
                if obs_trace.COMPONENT_ENV not in spec.env:
                    # The replica id labels the member's span log (a
                    # stale inherited value must not win over it).
                    env[obs_trace.COMPONENT_ENV] = spec.id
                argv = [expand_k8s_refs(a, env) for a in spec.argv]
                logf = open(self.log_path(spec.id), "ab")
                trace_tag = f" trace={self.trace_id}" if self.trace_id else ""
                logf.write(
                    f"==== attempt {attempt} {time.strftime('%Y-%m-%dT%H:%M:%S')}"
                    f"{trace_tag} ====\n".encode())
                logf.flush()
                p = subprocess.Popen(
                    argv, env=env, cwd=spec.cwd or self.workdir,
                    stdout=logf, stderr=subprocess.STDOUT,
                    start_new_session=True, preexec_fn=preexec,
                    pass_fds=(self._keepalive_r,))
                logf.close()  # child holds the fd
                launched[spec.id] = p
        except Exception as e:  # spawn failure -> tear down the partial gang
            obs_trace.finish_span(spawn_sp, status="error")
            for p in launched.values():
                _terminate(p, self.GRACE_SECONDS)
            with self._lock:
                for rid in self._status.replicas:
                    self._status.replicas[rid] = ReplicaStatus(state=FAILED)
                self._status.message = f"spawn failed: {e}"
            return False
        obs_trace.finish_span(spawn_sp)
        now = time.time()
        with self._lock:
            self._procs = launched
            for rid, p in launched.items():
                self._status.replicas[rid] = ReplicaStatus(
                    state=RUNNING, pid=p.pid, started_at=now)
            self._started_at = self._started_at or now
        return True

    def _supervise(self) -> None:
        try:
            self._keepalive_r, self._keepalive_w = os.pipe()
            os.set_inheritable(self._keepalive_r, True)
            attempt = 0
            while not self._stop.is_set():
                if not self._launch_all(attempt):
                    self._set_phase(FAILED, "SpawnFailed",
                                    self._status.message)
                    return
                self._set_phase(RUNNING, "GangRunning",
                                f"{len(self.specs)} processes running"
                                + (f" (restart {attempt})" if attempt else ""))
                outcome = self._watch_attempt()
                if outcome in (SUCCEEDED, FAILED, KILLED):
                    return
                # outcome == RESTARTING
                attempt += 1
                with self._lock:
                    self._status.restart_count = attempt
                delay = min(self.RESTART_BASE_DELAY * (2 ** (attempt - 1)),
                            self.RESTART_MAX_DELAY)
                self._set_phase(RESTARTING, "GangRestarting",
                                f"restart {attempt} after {delay:.1f}s backoff")
                if self._stop.wait(delay):
                    return
        finally:
            # PR_SET_PDEATHSIG fires when the forking THREAD dies, so this
            # thread must outlive every member it forked — otherwise
            # cleanPodPolicy=None survivors (chief succeeded, workers
            # intentionally left running) would be killed the moment we
            # return. Linger until they exit or the gang is deleted.
            self._linger()
            for fd in (self._keepalive_w, self._keepalive_r):
                try:
                    os.close(fd)
                except OSError:
                    pass

    def _linger(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                alive = any(p.poll() is None for p in self._procs.values())
            if not alive:
                return
            if self._stop.wait(0.2):
                return

    def _watch_attempt(self) -> str:
        """Poll member processes until a terminal decision for this attempt."""
        chief_id = f"{self.chief_replica_type.lower()}-0"
        # Fault point: the supervisor SIGKILLs one member mid-attempt
        # (the `kfx kill-replica` scenario, injected). The rule's delay
        # (default 0.25s) lets the member actually start before it
        # dies; the draw — and with it the injection count, budget and
        # event — happens only at kill time with a live victim in hand,
        # so kfx_chaos_injected_total never claims a kill that a fast
        # attempt outran. `match` scopes by gang name.
        plan = chaos.active_plan()
        peek = plan.rules.get("gang.kill") if plan is not None else None
        kill_at = (time.time() + (peek.delay or 0.25)
                   if peek is not None else None)
        while True:
            if kill_at is not None and time.time() >= kill_at:
                kill_at = None
                victim = self._chaos_victim(chief_id)
                if victim is not None and \
                        chaos.draw("gang.kill", target=self.name) is not None:
                    self.kill_replica(victim)
            if self._stop.is_set():
                self._kill_all()
                self._set_phase(KILLED, "GangDeleted", "gang deleted")
                return KILLED
            if (self.active_deadline is not None and self._started_at
                    and time.time() - self._started_at > self.active_deadline):
                self._kill_all()
                self._set_phase(FAILED, "DeadlineExceeded",
                                f"activeDeadlineSeconds={self.active_deadline} exceeded")
                return FAILED
            exited_fail: Optional[str] = None
            all_done = True
            chief_done_ok = False
            changed = False
            with self._lock:
                for rid, p in self._procs.items():
                    st = self._status.replicas[rid]
                    code = p.poll()
                    if code is None:
                        all_done = False
                        continue
                    if st.state == RUNNING:
                        st.exit_code = code
                        st.finished_at = time.time()
                        st.state = SUCCEEDED if code == 0 else FAILED
                        changed = True
                    if st.state == FAILED and exited_fail is None:
                        exited_fail = rid
                    if rid == chief_id and st.state == SUCCEEDED:
                        chief_done_ok = True
            if changed:
                self._notify()
            if exited_fail is not None:
                code = self._status.replicas[exited_fail].exit_code or 0
                retry = self._should_retry(code)
                self._kill_all()
                if retry:
                    return RESTARTING
                self._set_phase(
                    FAILED, "ReplicaFailed",
                    f"{exited_fail} exited with code {code}; "
                    f"restartPolicy={self.restart_policy}, "
                    f"restarts={self._status.restart_count}")
                return FAILED
            if chief_done_ok or all_done:
                if self.clean_policy in (T.CLEAN_POD_RUNNING, T.CLEAN_POD_ALL):
                    self._kill_all(mark=SUCCEEDED)
                self._set_phase(SUCCEEDED, "GangSucceeded",
                                "chief exited 0" if chief_done_ok else
                                "all replicas exited 0")
                return SUCCEEDED
            time.sleep(0.05)

    def _chaos_victim(self, chief_id: str) -> Optional[str]:
        """Deterministic kill target: the first running non-chief
        member (sorted), else the chief — a one-member gang still gets
        its kill."""
        with self._lock:
            running = sorted(
                rid for rid, p in self._procs.items() if p.poll() is None)
        non_chief = [rid for rid in running if rid != chief_id]
        return (non_chief or running or [None])[0]

    def _should_retry(self, exit_code: int) -> bool:
        if self.restart_policy == T.RESTART_NEVER:
            return False
        if self.restart_policy == T.RESTART_EXIT_CODE and not _retryable_exit(exit_code):
            return False
        if self.backoff_limit is not None and \
                self._status.restart_count >= self.backoff_limit:
            return False
        return True

    def _kill_all(self, mark: str = KILLED) -> None:
        """Terminate members still running; finished members keep their
        recorded state. `mark` is the state assigned to the killed ones
        (SUCCEEDED on cleanPodPolicy teardown after chief success)."""
        with self._lock:
            procs = dict(self._procs)
        for rid, p in procs.items():
            if p.poll() is None:
                _terminate(p, self.GRACE_SECONDS)
                with self._lock:
                    st = self._status.replicas[rid]
                    st.state = mark
                    st.exit_code = p.poll()
                    st.finished_at = time.time()
        self._notify()

    def delete(self) -> None:
        """Stop supervision and kill everything (resource deletion path)."""
        self._stop.set()
        self._kill_all()
        if self._monitor is not None:
            self._monitor.join(timeout=self.GRACE_SECONDS + 5)

    def kill_replica(self, replica_id: str) -> bool:
        """Fault-injection hook (SURVEY.md §5.3: `kfx kill-worker`)."""
        with self._lock:
            p = self._procs.get(replica_id)
        if p is not None and p.poll() is None:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                p.kill()
            return True
        return False


def _terminate(p: subprocess.Popen, grace: float) -> None:
    try:
        os.killpg(os.getpgid(p.pid), signal.SIGTERM)
    except (ProcessLookupError, PermissionError):
        try:
            p.terminate()
        except ProcessLookupError:
            return
    deadline = time.time() + grace
    while time.time() < deadline:
        if p.poll() is not None:
            return
        time.sleep(0.02)
    try:
        os.killpg(os.getpgid(p.pid), signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        try:
            p.kill()
        except ProcessLookupError:
            pass
    p.wait()


class GangManager:
    """Registry of live gangs keyed by job key — what the operators talk to."""

    def __init__(self, base_workdir: str):
        self.base_workdir = base_workdir
        self._lock = threading.Lock()
        self._gangs: Dict[str, Gang] = {}

    def slice_capacity(self) -> int:
        """Total chips of the slice this runtime launches gangs onto —
        the capacity model the cluster scheduler (sched/) admits
        against (sched.slice_capacity has the discovery order)."""
        from ..sched import slice_capacity

        return slice_capacity()

    def get(self, key: str) -> Optional[Gang]:
        with self._lock:
            return self._gangs.get(key)

    def count(self) -> int:
        with self._lock:
            return len(self._gangs)

    def workdir_for(self, key: str) -> str:
        """The (stable) workdir a gang for `key` uses — also valid for
        finished gangs that were forgotten (log retrieval)."""
        return os.path.join(self.base_workdir, key.replace("/", "_"))

    def ensure(self, key: str, factory: Callable[[str], Gang]) -> Gang:
        """Get the gang for `key`, creating+starting it via `factory` if
        absent. factory receives the gang workdir."""
        with self._lock:
            gang = self._gangs.get(key)
            if gang is not None:
                return gang
        workdir = self.workdir_for(key)
        os.makedirs(workdir, exist_ok=True)
        gang = factory(workdir)
        with self._lock:
            existing = self._gangs.get(key)
            if existing is not None:
                return existing
            self._gangs[key] = gang
        gang.start()
        return gang

    def delete(self, key: str) -> None:
        with self._lock:
            gang = self._gangs.pop(key, None)
        if gang is not None:
            gang.delete()

    def forget(self, key: str) -> None:
        """Drop a finished gang from the registry without killing it."""
        with self._lock:
            self._gangs.pop(key, None)

    def shutdown(self) -> None:
        with self._lock:
            gangs = list(self._gangs.values())
            self._gangs.clear()
        for g in gangs:
            g.delete()
