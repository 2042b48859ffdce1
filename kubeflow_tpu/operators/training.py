"""Training-job operators: JAXJob, TFJob, PyTorchJob, MPIJob.

The reconcile shape mirrors the reference training operators
(SURVEY.md §2.1 tf-operator `syncTFJob`/`reconcilePods` and the common
JobController): on every event for a job key,

  1. fetch the resource; deletion tears the gang down (`on_delete`);
  2. if suspended → ensure no gang, mark Suspended;
  3. if not finished → ensure the gang exists (all replicas spawned
     all-or-nothing with kind-specific rendezvous env — the pod-creation
     equivalent), then
  4. project live gang state into status: conditions
     (Created/Running/Restarting/Succeeded/Failed), replicaStatuses
     {active,succeeded,failed}, start/completion times;
  5. if finished → apply ttlSecondsAfterFinished garbage collection.

Where the reference writes pods and lets NCCL/TF-gRPC/MPI rendezvous inside
containers, these operators inject the environment that makes worker
processes rendezvous directly (SURVEY.md §5.8):

  * JAXJob      → jax.distributed coordinates; XLA collectives over ICI/DCN
  * TFJob       → TF_CONFIG cluster-spec JSON (genTFConfig parity)
  * PyTorchJob  → MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK (SetPodEnv parity)
  * MPIJob      → hostfile + OMPI_COMM_WORLD_* env; `mpirun` in the launcher
                  command is executed by the local mpirun shim
                  (kubeflow_tpu.runners.mpi_launcher)
"""

from __future__ import annotations

import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..api import training as T
from ..api.base import Resource, utcnow
from ..core.controller import Controller, Result
from ..core.store import ResourceStore
from ..obs import trace as obs_trace
from ..runtime import gang as G
from ..runtime import rendezvous as rdv
from ..utils.net import free_port
from ..utils.proc import inject_pythonpath

# Sleep-forever placeholder for replica templates with no command (the
# reference's MPI workers run sshd and just host processes).
_PLACEHOLDER_ARGV = [sys.executable, "-c",
                     "import time\nwhile True: time.sleep(3600)"]


def _phase_condition(phase: str) -> Optional[Tuple[str, str, str]]:
    """Map a gang phase to (condition type, reason, terminal Running status)."""
    return {
        G.RUNNING: (T.JOB_RUNNING, "GangRunning", "True"),
        G.RESTARTING: (T.JOB_RESTARTING, "GangRestarting", "False"),
        G.SUCCEEDED: (T.JOB_SUCCEEDED, "GangSucceeded", "False"),
        G.FAILED: (T.JOB_FAILED, "GangFailed", "False"),
    }.get(phase)


class TrainingControllerBase(Controller):
    """Shared reconcile for every training kind. Subclasses implement
    ``build_specs`` (the env-injection contract §2.3) and set KIND."""

    JOB_CLASS: type = T.TrainingJob
    RESYNC_PERIOD: Optional[float] = 2.0

    def __init__(self, store: ResourceStore, gangs: G.GangManager,
                 worker_platform: Optional[str] = None):
        super().__init__(store)
        self.gangs = gangs
        # Platform pinned into worker env (JAX_PLATFORMS). None = the
        # plane's own (see platform_for).
        self.worker_platform = worker_platform if worker_platform is not None \
            else os.environ.get("KFX_WORKER_PLATFORM")
        # Set by the control plane when the platform operators are present:
        # quota admission + PodDefault injection (operators/platform.py).
        self.admission = None
        # Set by the control plane: the cluster gang scheduler (sched/).
        # Every gang creation routes through it; queued jobs are woken
        # event-driven when capacity frees (no quota busy-poll).
        self.scheduler = None

    # -- gang bookkeeping ---------------------------------------------------
    def _gang_key(self, key: str) -> str:
        return f"{self.KIND.lower()}/{key}"

    def on_delete(self, obj: Resource) -> None:
        self.gangs.delete(self._gang_key(obj.key))
        if self.scheduler is not None:
            self.scheduler.release(self.KIND, obj.name, obj.namespace)

    # -- per-kind contract --------------------------------------------------
    def build_specs(self, job: T.TrainingJob, workdir: str) -> Tuple[
            List[G.ProcessSpec],
            Optional[Callable[[int], Dict[str, Dict[str, str]]]]]:
        """Return (process specs, per-attempt env hook). The hook's dict
        is keyed by replica id, with "*" applying to every member (see
        Gang.restart_env_hook)."""
        raise NotImplementedError

    def platform_for(self, job: T.TrainingJob) -> str:
        """The JAX platform the job's workers run on unless a replica's
        own env says otherwise: the operator's explicit
        ``worker_platform``, else what the plane itself has in
        ``JAX_PLATFORMS`` (workers inherit its environment — "cpu" under
        the tests, the accelerator on a TPU host). Nothing here trades
        the accelerator for the CPU: a job too wide for the host stays
        queued as Unschedulable (sched/scheduler.py)."""
        if self.worker_platform is not None:
            return self.worker_platform
        return os.environ.get("JAX_PLATFORMS", "")

    # -- reconcile ----------------------------------------------------------
    def reconcile(self, key: str) -> Optional[Result]:
        job = self.get_resource(key)
        if job is None:
            self.gangs.delete(self._gang_key(key))
            if self.scheduler is not None:
                ns, _, name = key.partition("/")
                self.scheduler.release(self.KIND, name, ns)
            return None
        assert isinstance(job, T.TrainingJob)
        policy = job.run_policy()
        gkey = self._gang_key(key)

        if policy.suspend:
            if self.gangs.get(gkey) is not None:
                self.gangs.delete(gkey)
                self.record_event(job, "Normal", "JobSuspended",
                                  "gang terminated (spec.runPolicy.suspend)")
            # A scheduler-preempted job stays queued for auto-resume;
            # a user-suspended one leaves the scheduler (its chips free
            # either way — this is what makes suspend the preemption
            # primitive).
            kept = self.scheduler.on_suspended(job) \
                if self.scheduler is not None else False
            if not job.has_condition(T.JOB_SUSPENDED):
                msg = ("preempted; resumes from its latest checkpoint "
                       "when capacity frees") if kept else "job is suspended"
                job.set_condition(T.JOB_SUSPENDED, "True", "JobSuspended",
                                  msg)
                job.set_condition(T.JOB_RUNNING, "False", "JobSuspended", "")
                self._update_status(job)
            return None
        if job.has_condition(T.JOB_SUSPENDED):
            # Resume: clear the condition; the gang is recreated below.
            job.set_condition(T.JOB_SUSPENDED, "False", "JobResumed",
                              "job resumed")
            self._update_status(job)

        if job.is_finished():
            self.gangs.forget(gkey)
            if self.scheduler is not None:
                self.scheduler.release(self.KIND, job.name, job.namespace)
            return self._gc_after_ttl(job, policy)

        gang = self.gangs.get(gkey)
        if gang is None:
            queued = self._admission_gate(job)
            if queued is not None:
                reason, message = queued
                if self._set_if_changed(job, T.JOB_QUEUED, "True",
                                        reason, message):
                    self._update_status(job)
                    self.record_event(job, "Warning", reason, message)
                if self.scheduler is not None:
                    # Event-driven: the scheduler wakes this key when
                    # its turn comes — no requeue busy-poll.
                    return None
                # Legacy quota fallback (no scheduler wired): retry.
                return Result(requeue=True, requeue_after=1.0)
            gang = self._create_gang(job, gkey, policy)
        if not job.has_condition(T.JOB_CREATED):
            # One status write for Queued-clear + Created + startTime:
            # split writes conflict on resourceVersion and the retry
            # used to skip this block once the gang existed, losing
            # startTime for any job that had waited in the queue.
            if job.has_condition(T.JOB_QUEUED):
                job.set_condition(T.JOB_QUEUED, "False", "Admitted",
                                  "capacity available")
            job.set_condition(T.JOB_CREATED, "True", "JobCreated",
                              f"gang of {job.total_replicas()} created")
            job.status.setdefault("startTime", utcnow())
            self._update_status(job)
            self.record_event(job, "Normal", "JobCreated",
                              f"created gang of {job.total_replicas()} "
                              f"process(es)")
        elif job.has_condition(T.JOB_QUEUED):
            job.set_condition(T.JOB_QUEUED, "False", "Admitted",
                              "capacity available")
            self._update_status(job)
        self._sync_status(job, gang)
        return None

    def _admission_gate(self, job: T.TrainingJob
                        ) -> Optional[Tuple[str, str]]:
        """The single admission point before gang.spawn: ask the cluster
        scheduler for the job's full replica set (all-or-nothing).
        Returns None when admitted, else ``(reason, message)`` for the
        Queued condition. Without a scheduler (standalone controllers)
        the legacy profile-quota check applies."""
        if self.scheduler is not None:
            from ..sched import job_chips, job_priority

            # The sched.admit span sits between this job's reconcile
            # and its gang.spawn in the `kfx trace` waterfall.
            with obs_trace.span("sched.admit", kind=self.KIND,
                                job=job.key,
                                chips=str(job_chips(job)),
                                priority=str(job_priority(job))) as sp:
                admitted, reason, message = self.scheduler.try_admit(job)
                sp.attrs["admitted"] = "true" if admitted else "false"
            return None if admitted else (reason, message)
        if self.admission is not None:
            denial = self.admission.check_job(job)
            if denial:
                return "QuotaExceeded", denial
        return None

    def _create_gang(self, job: T.TrainingJob, gkey: str,
                     policy: T.RunPolicy) -> G.Gang:
        key = job.key
        ctrl = self

        def factory(workdir: str) -> G.Gang:
            specs, env_hook = ctrl.build_specs(job, workdir)
            for spec in specs:
                inject_pythonpath(spec.env)
            if ctrl.admission is not None:
                applied = ctrl.admission.mutate_specs(job, specs)
                if applied:
                    ctrl.record_event(job, "Normal", "PodDefaultsApplied",
                                      ", ".join(applied))
            # restartPolicy comes from the chief replica's spec (the
            # reference tracks it per replica; one gang = one policy here,
            # chief's wins as it decides success anyway).
            chief = job.chief_replica_type()
            rp = job.replica_specs()[chief].restart_policy
            from ..obs.trace import current_span_id, trace_of

            return G.Gang(
                name=job.name,
                specs=specs,
                workdir=workdir,
                restart_policy=rp,
                backoff_limit=policy.backoff_limit
                if policy.backoff_limit is not None else 3,
                active_deadline=policy.active_deadline_seconds,
                clean_policy=policy.clean_pod_policy,
                chief_replica_type=chief,
                on_change=lambda g: ctrl.queue.add(key),
                restart_env_hook=env_hook,
                trace_id=trace_of(job),
                # The factory runs on the reconcile worker thread, so
                # the open span here is the creating reconcile — the
                # node every gang.spawn attempt hangs under.
                parent_span_id=current_span_id(),
            )

        return self.gangs.ensure(gkey, factory)

    @staticmethod
    def _set_if_changed(job: T.TrainingJob, ctype: str, status: str,
                        reason: str, message: str) -> bool:
        """Upsert a condition only when (status, reason, message) differ —
        keeps resyncs from generating an endless status-write/event loop."""
        from ..api.base import get_condition

        cur = get_condition(job.conditions, ctype)
        if cur is not None and (cur.status, cur.reason, cur.message) == \
                (status, reason, message):
            return False
        job.set_condition(ctype, status, reason, message)
        return True

    def _sync_status(self, job: T.TrainingJob, gang: G.Gang) -> None:
        st = gang.status()
        fresh = self.get_resource(job.key)
        if fresh is None:
            return
        job = fresh  # re-read to avoid clobbering concurrent status writers
        changed = False
        mapped = _phase_condition(st.phase)
        if mapped is not None:
            ctype, reason, _ = mapped
            changed |= self._set_if_changed(job, ctype, "True", reason,
                                            st.message)
            if ctype in (T.JOB_SUCCEEDED, T.JOB_FAILED):
                changed |= self._set_if_changed(job, T.JOB_RUNNING, "False",
                                                reason, "")
                if "completionTime" not in job.status:
                    job.status["completionTime"] = utcnow()
                if changed:
                    self.record_event(
                        job,
                        "Normal" if ctype == T.JOB_SUCCEEDED else "Warning",
                        f"Job{ctype}", st.message)
            elif ctype == T.JOB_RESTARTING:
                changed |= self._set_if_changed(job, T.JOB_RUNNING, "False",
                                                reason, st.message)
            elif ctype == T.JOB_RUNNING and job.has_condition(T.JOB_RESTARTING):
                changed |= self._set_if_changed(job, T.JOB_RESTARTING, "False",
                                                reason, "gang running again")
        counts = st.counts()
        if counts != job.status.get("replicaStatuses"):
            job.status["replicaStatuses"] = counts
            changed = True
        if st.restart_count != job.status.get("restartCount", 0):
            job.status["restartCount"] = st.restart_count
            changed = True
        if changed:
            self._update_status(job)

    def _update_status(self, job: T.TrainingJob) -> None:
        from ..core.store import Conflict, NotFound

        try:
            self.store.update_status(job)
        except (Conflict, NotFound):
            self.queue.add(job.key)  # reconcile again off the fresh object

    def _gc_after_ttl(self, job: T.TrainingJob,
                      policy: T.RunPolicy) -> Optional[Result]:
        ttl = policy.ttl_seconds_after_finished
        if ttl is None:
            return None
        done = job.status.get("completionTime")
        if not done:
            return None
        from ..api.base import age_seconds

        age = age_seconds(done)
        if age >= ttl:
            from ..core.store import NotFound

            try:
                self.store.delete(self.KIND, job.name, job.namespace)
            except NotFound:
                pass
            return None
        return Result(requeue=True, requeue_after=ttl - age + 0.05)

    # -- shared env helpers -------------------------------------------------
    def _member_layout(self, job: T.TrainingJob) -> List[Tuple[str, int, int]]:
        """[(rtype, index, global_rank)] in a stable order with the chief
        replica type ranked first (rank 0 must be the chief process)."""
        specs = job.replica_specs()
        chief = job.chief_replica_type()
        order = [chief] + [t for t in specs if t != chief]
        return rdv.flatten_replicas([(t, specs[t].replicas) for t in order])


class JAXJobController(TrainingControllerBase):
    """The TPU-native flagship operator. Every worker gets
    ``jax.distributed.initialize`` coordinates; the coordinator port is
    re-allocated on each gang restart (a dead coordinator cannot be
    re-bound immediately)."""

    KIND = "JAXJob"
    JOB_CLASS = T.JAXJob

    def build_specs(self, job, workdir):
        import json

        members = self._member_layout(job)
        n = len(members)
        par = job.parallelism()
        chips_per_proc = job.chip_count() // max(n, 1)
        specs = []
        for rtype, idx, rank in members:
            rs = job.replica_specs()[rtype]
            platform = rs.env().get("JAX_PLATFORMS") or \
                self.platform_for(job)
            env = rdv.jax_env(
                job_name=job.name, namespace=job.namespace,
                coordinator="",  # injected per attempt by the hook
                num_processes=n, process_id=rank, rtype=rtype, index=idx,
                workdir=workdir, platform=platform)
            env.pop(rdv.ENV_COORDINATOR)
            if par:
                # The declarative mesh plan travels to the runner as
                # env (runners/jax_runner.parallelism_from_env); CLI
                # flags in the manifest's argv still win.
                env["KFX_PARALLELISM"] = json.dumps(par)
            if chips_per_proc > 1 and platform == "cpu":
                # On the CPU each worker process drives chip_count/
                # replicas virtual devices (vmeshenv recipe; must
                # precede the worker's first jax import, which env
                # guarantees). On an accelerator the worker drives the
                # host's real chips and gets nothing of the kind.
                from ..vmeshenv import virtual_mesh_env

                # (gloo collectives for n>1 already set by jax_env.)
                env.update(virtual_mesh_env(chips_per_proc))
            env.update(rs.env())
            specs.append(G.ProcessSpec(
                replica_type=rtype, index=idx,
                argv=rs.argv() or list(_PLACEHOLDER_ARGV), env=env,
                cwd=rs.working_dir()))

        def env_hook(attempt: int) -> Dict[str, Dict[str, str]]:
            return {"*": {rdv.ENV_COORDINATOR: f"127.0.0.1:{free_port()}"}}

        return specs, env_hook


class TFJobController(TrainingControllerBase):
    """tf-operator parity: injects per-task ``TF_CONFIG`` (genTFConfig).

    Cluster ports are allocated by the per-attempt env hook at the moment
    the gang launches — not at spec-build time — so the unbound-port
    window is milliseconds, and every restart (including one caused by a
    port collision crashing a TF server) rendezvouses on fresh ports.
    A user-supplied TF_CONFIG in the replica env always wins."""

    KIND = "TFJob"
    JOB_CLASS = T.TFJob

    def build_specs(self, job, workdir):
        members = self._member_layout(job)
        specs = []
        for rtype, idx, _ in members:
            rs = job.replica_specs()[rtype]
            specs.append(G.ProcessSpec(
                replica_type=rtype, index=idx,
                argv=rs.argv() or list(_PLACEHOLDER_ARGV), env=rs.env(),
                cwd=rs.working_dir()))

        def env_hook(attempt: int) -> Dict[str, Dict[str, str]]:
            cluster: Dict[str, List[str]] = {}
            for rtype, idx, _ in members:
                cluster.setdefault(rtype, []).append(
                    f"127.0.0.1:{free_port()}")
            over: Dict[str, Dict[str, str]] = {}
            for rtype, idx, _ in members:
                if "TF_CONFIG" in job.replica_specs()[rtype].env():
                    continue
                over[f"{rtype.lower()}-{idx}"] = rdv.tf_env(
                    cluster, rtype, idx)
            return over

        return specs, env_hook


class PyTorchJobController(TrainingControllerBase):
    """pytorch-operator parity: MASTER_ADDR/PORT + WORLD_SIZE/RANK; the
    master port is re-allocated per attempt like the JAX coordinator."""

    KIND = "PyTorchJob"
    JOB_CLASS = T.PyTorchJob

    def build_specs(self, job, workdir):
        members = self._member_layout(job)
        world = len(members)
        specs = []
        for rtype, idx, rank in members:
            rs = job.replica_specs()[rtype]
            env = rdv.pytorch_env("127.0.0.1", 0, world, rank)
            env.pop("MASTER_PORT")
            env.update(rs.env())
            specs.append(G.ProcessSpec(
                replica_type=rtype, index=idx,
                argv=rs.argv() or list(_PLACEHOLDER_ARGV), env=env,
                cwd=rs.working_dir()))

        def env_hook(attempt: int) -> Dict[str, Dict[str, str]]:
            return {"*": {"MASTER_PORT": str(free_port())}}

        return specs, env_hook


class MPIJobController(TrainingControllerBase):
    """mpi-operator parity: Launcher (chief) + Workers. A hostfile is
    written into the gang workdir and exported as KFX_HOSTFILE /
    OMPI_MCA_orte_default_hostfile; ``mpirun ...`` launcher commands are
    executed by the local shim (kubeflow_tpu.runners.mpi_launcher), which
    spawns the ranks as local processes — the single-host equivalent of
    the reference's kubexec-into-workers model."""

    KIND = "MPIJob"
    JOB_CLASS = T.MPIJob

    def build_specs(self, job, workdir):
        assert isinstance(job, T.MPIJob)
        specs_by_type = job.replica_specs()
        n_workers = specs_by_type.get("Worker", T.ReplicaSpec(replicas=0)).replicas
        slots = job.slots_per_worker()
        hostfile = os.path.join(workdir, "hostfile")
        with open(hostfile, "w") as f:
            f.write(rdv.mpi_hostfile(
                [f"worker-{i}" for i in range(n_workers)], slots))

        # Platform env must reach the ranks the launcher shim spawns (they
        # inherit the launcher env): multi-rank JAX on the CPU backend
        # needs gloo collectives, same as JAXJob.
        platform = self.platform_for(job)
        platform_env: Dict[str, str] = {}
        if platform:
            platform_env["JAX_PLATFORMS"] = platform
        if platform == "cpu" and n_workers * slots > 1:
            platform_env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

        members = self._member_layout(job)
        specs = []
        worker_rank = 0
        world = n_workers * slots
        for rtype, idx, _ in members:
            rs = specs_by_type[rtype]
            if rtype == "Launcher":
                env = {
                    "KFX_HOSTFILE": hostfile,
                    "OMPI_MCA_orte_default_hostfile": hostfile,
                    "KFX_MPI_WORLD_SIZE": str(world),
                    **platform_env,
                }
                argv = self._launcher_argv(rs.argv())
            else:
                env = rdv.mpi_worker_env(worker_rank, world)
                worker_rank += slots
                argv = rs.argv() or list(_PLACEHOLDER_ARGV)
            env.update(rs.env())
            specs.append(G.ProcessSpec(
                replica_type=rtype, index=idx, argv=argv, env=env,
                cwd=rs.working_dir()))
        return specs, None

    @staticmethod
    def _launcher_argv(argv: List[str]) -> List[str]:
        """Route `mpirun`/`mpiexec` through the local shim (no system MPI
        here); anything else runs as-is."""
        if argv and os.path.basename(argv[0]) in ("mpirun", "mpiexec"):
            return [sys.executable, "-m", "kubeflow_tpu.runners.mpi_launcher",
                    *argv[1:]]
        return argv or list(_PLACEHOLDER_ARGV)


def training_controllers(store: ResourceStore, gangs: G.GangManager,
                         worker_platform: Optional[str] = None,
                         ) -> List[TrainingControllerBase]:
    return [cls(store, gangs, worker_platform) for cls in
            (JAXJobController, TFJobController, PyTorchJobController,
             MPIJobController)]
