"""Operator tests: reconcile jobs end-to-end onto real local process gangs.

Mirrors the reference test strategy (SURVEY.md §4): the rendezvous
*contract* is asserted at the env level (what each worker receives), and
job lifecycle is integration-tested against the in-memory store with real
(tiny) subprocesses instead of a fake clientset.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from kubeflow_tpu.api import training as T
from kubeflow_tpu.api.base import from_manifest
from kubeflow_tpu.controlplane import ControlPlane
from kubeflow_tpu.operators.training import (
    JAXJobController,
    MPIJobController,
    PyTorchJobController,
    TFJobController,
)
from kubeflow_tpu.runtime import rendezvous as rdv

PY = sys.executable
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _job(kind, name, replicas_field, replica_map, run_policy=None, ns="default"):
    spec = {replicas_field: replica_map}
    if run_policy:
        spec["runPolicy"] = run_policy
    return from_manifest({
        "apiVersion": "kubeflow.org/v1", "kind": kind,
        "metadata": {"name": name, "namespace": ns}, "spec": spec})


def _tmpl(args_py, env=None):
    """Pod template running `python -c <args_py>`."""
    c = {"name": "main", "command": [PY, "-c", args_py]}
    if env:
        c["env"] = [{"name": k, "value": v} for k, v in env.items()]
    return {"spec": {"containers": [c]}}


def _wait(pred, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture()
def cp(tmp_path):
    plane = ControlPlane(home=str(tmp_path / "kfx"), worker_platform="cpu")
    with plane:
        yield plane


ENV_DUMP = ("import json,os;"
            "print(json.dumps({k:v for k,v in os.environ.items()}))")


def test_plane_process_never_imports_jax(tmp_path):
    """A chip has one owner: the worker or the replica, never the plane
    that starts them. A fresh interpreter builds a ControlPlane, runs a
    JAXJob through it (admission, scheduler, gang spawn, status) and
    touches the serving operator's status helpers — and must come out
    without jax in sys.modules, let alone a backend. (This process
    cannot be asked: its other tests use jax.)"""
    script = f"""
import sys
from kubeflow_tpu.controlplane import ControlPlane
from kubeflow_tpu.api.base import from_manifest
job = from_manifest({{
    "apiVersion": "kubeflow.org/v1", "kind": "JAXJob",
    "metadata": {{"name": "nojax", "namespace": "default"}},
    "spec": {{"parallelism": {{"tensor": 2}},
             "jaxReplicaSpecs": {{"Worker": {{
        "replicas": 1, "restartPolicy": "Never",
        "template": {{"spec": {{"containers": [{{
            "name": "main",
            "command": [sys.executable, "-c", "print(1)"]}}]}}}}}}}}}}}})
with ControlPlane(home={str(tmp_path / "kfx")!r}) as cp:
    cp.apply([job])
    done = cp.wait_for_job("JAXJob", "nojax", timeout=60)
    assert done.has_condition("Succeeded"), done.conditions
    from kubeflow_tpu.serving.engine import quant_mode_string
    assert quant_mode_string("int8", "f32") == "w8"
assert "jax" not in sys.modules, "the plane imported jax"
print("plane_is_jax_free")
"""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run([PY, "-c", script], env=env, capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode == 0 and "plane_is_jax_free" in out.stdout, \
        out.stdout + out.stderr


class TestEnvContracts:
    """Unit-level: what env does each kind inject? (SURVEY.md §4 key insight:
    the reference tests multi-worker logic at the env-injection level.)"""

    def _specs(self, ctrl_cls, job, tmp_path):
        cp_ = ControlPlane(home=str(tmp_path / "h"), worker_platform="cpu")
        ctrl = next(c for c in cp_.manager.controllers.values()
                    if isinstance(c, ctrl_cls))
        specs, hook = ctrl.build_specs(job, str(tmp_path / "wd"))
        cp_.stop()
        return specs, hook

    def test_jaxjob_env(self, tmp_path):
        job = _job("JAXJob", "j", "jaxReplicaSpecs",
                   {"Worker": {"replicas": 3, "template": _tmpl("pass")}})
        specs, hook = self._specs(JAXJobController, job, tmp_path)
        assert [s.id for s in specs] == ["worker-0", "worker-1", "worker-2"]
        for rank, s in enumerate(specs):
            assert s.env[rdv.ENV_NUM_PROCESSES] == "3"
            assert s.env[rdv.ENV_PROCESS_ID] == str(rank)
            assert s.env["JAX_PLATFORMS"] == "cpu"
            assert s.env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "gloo"
        # Coordinator is allocated per attempt, distinct across attempts.
        a0 = hook(0)["*"][rdv.ENV_COORDINATOR]
        a1 = hook(1)["*"][rdv.ENV_COORDINATOR]
        assert a0.startswith("127.0.0.1:") and a0 != a1

    def test_multichip_jaxjob_keeps_the_accelerator(self, tmp_path,
                                                    monkeypatch):
        """On a TPU host (the plane's JAX_PLATFORMS is not cpu) a job
        that spans chips inherits the accelerator: the operator neither
        pins the CPU platform nor hands it virtual devices. A job too
        wide for the host is the scheduler's to refuse (test_sched)."""
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        monkeypatch.delenv("KFX_WORKER_PLATFORM", raising=False)
        job = _job("JAXJob", "wide", "jaxReplicaSpecs",
                   {"Worker": {"replicas": 1, "template": _tmpl("pass")}})
        job.spec["parallelism"] = {"tensor": 2, "data": 2, "fsdp": True}
        cp_ = ControlPlane(home=str(tmp_path / "h"))
        ctrl = next(c for c in cp_.manager.controllers.values()
                    if isinstance(c, JAXJobController))
        (spec,), _ = ctrl.build_specs(job, str(tmp_path / "wd"))
        cp_.stop()
        assert spec.env["JAX_PLATFORMS"] == "tpu,cpu"
        assert "XLA_FLAGS" not in spec.env
        assert json.loads(spec.env["KFX_PARALLELISM"])["tensor"] == 2

    def test_tfjob_tf_config(self, tmp_path):
        job = _job("TFJob", "t", "tfReplicaSpecs", {
            "Chief": {"replicas": 1, "template": _tmpl("pass")},
            "Worker": {"replicas": 2, "template": _tmpl("pass")},
            "PS": {"replicas": 1, "template": _tmpl("pass")},
        })
        specs, hook = self._specs(TFJobController, job, tmp_path)
        # TF_CONFIG is injected per attempt (launch-time ports), keyed by
        # replica id — not baked into the spec env at build time.
        env0 = hook(0)
        cfg = json.loads(env0["worker-1"]["TF_CONFIG"])
        assert set(cfg["cluster"]) == {"chief", "worker", "ps"}
        assert len(cfg["cluster"]["worker"]) == 2
        assert cfg["task"] == {"type": "worker", "index": 1}
        # every member sees the identical cluster spec
        assert all(json.loads(e["TF_CONFIG"])["cluster"] == cfg["cluster"]
                   for e in env0.values())
        assert set(env0) == {s.id for s in specs}
        # chief is rank 0 (first member) for gang success semantics
        assert specs[0].id == "chief-0"
        # a restart rendezvouses on fresh ports
        cfg1 = json.loads(hook(1)["worker-1"]["TF_CONFIG"])
        assert cfg1["cluster"] != cfg["cluster"]

    def test_tfjob_parallel_jobs_bindable_ports(self, cp):
        """Port-race regression: several TFJobs launching at once must all
        hand their members ports they can actually bind (allocation
        happens at launch, collisions would crash the TF server and be
        retried with fresh ports)."""
        script = (
            "import json, os, socket\n"
            "cfg = json.loads(os.environ['TF_CONFIG'])\n"
            "t = cfg['task']\n"
            "addr = cfg['cluster'][t['type']][t['index']]\n"
            "host, port = addr.rsplit(':', 1)\n"
            "s = socket.socket()\n"
            "s.bind((host, int(port)))  # my advertised port must be free\n"
            "s.listen(1)\n"
            "import time; time.sleep(1.0)\n"
            "s.close()\n")
        names = [f"tfp-{i}" for i in range(4)]
        for n in names:
            cp.apply([_job("TFJob", n, "tfReplicaSpecs", {
                "Chief": {"replicas": 1, "template": _tmpl(script)},
                "Worker": {"replicas": 2, "template": _tmpl(script)},
            })])
        for n in names:
            final = cp.wait_for_job("TFJob", n, timeout=60)
            assert final.has_condition(T.JOB_SUCCEEDED), \
                cp.job_logs("TFJob", n)

    def test_pytorchjob_env(self, tmp_path):
        job = _job("PyTorchJob", "p", "pytorchReplicaSpecs", {
            "Master": {"replicas": 1, "template": _tmpl("pass")},
            "Worker": {"replicas": 2, "template": _tmpl("pass")},
        })
        specs, hook = self._specs(PyTorchJobController, job, tmp_path)
        assert specs[0].id == "master-0" and specs[0].env["RANK"] == "0"
        assert {s.env["RANK"] for s in specs} == {"0", "1", "2"}
        assert all(s.env["WORLD_SIZE"] == "3" for s in specs)
        assert all(s.env["MASTER_ADDR"] == "127.0.0.1" for s in specs)
        assert hook(0)["*"]["MASTER_PORT"].isdigit()

    def test_mpijob_hostfile_and_launcher_rewrite(self, tmp_path):
        job = _job("MPIJob", "m", "mpiReplicaSpecs", {
            "Launcher": {"replicas": 1, "template": _tmpl("pass")},
            "Worker": {"replicas": 2, "template": _tmpl("pass")},
        })
        job.spec["slotsPerWorker"] = 2
        wd = tmp_path / "wd"
        wd.mkdir()
        cp_ = ControlPlane(home=str(tmp_path / "h"), worker_platform="cpu")
        ctrl = next(c for c in cp_.manager.controllers.values()
                    if isinstance(c, MPIJobController))
        specs, _ = ctrl.build_specs(job, str(wd))
        cp_.stop()
        hosts = (wd / "hostfile").read_text()
        assert hosts == "worker-0 slots=2\nworker-1 slots=2\n"
        launcher = specs[0]
        assert launcher.id == "launcher-0"
        assert launcher.env["KFX_MPI_WORLD_SIZE"] == "4"
        workers = [s for s in specs if s.replica_type == "Worker"]
        assert [w.env["OMPI_COMM_WORLD_RANK"] for w in workers] == ["0", "2"]

    def test_mpirun_is_routed_through_shim(self):
        argv = MPIJobController._launcher_argv(
            ["mpirun", "-np", "4", "python", "train.py"])
        assert argv[:3] == [sys.executable, "-m",
                            "kubeflow_tpu.runners.mpi_launcher"]
        assert argv[3:] == ["-np", "4", "python", "train.py"]


class TestJobLifecycle:
    def test_jaxjob_succeeds(self, cp):
        job = _job("JAXJob", "ok", "jaxReplicaSpecs", {"Worker": {
            "replicas": 2,
            "template": _tmpl("import os; print('rank', os.environ['KFX_PROCESS_ID'])")}})
        cp.apply([job])
        final = cp.wait_for_job("JAXJob", "ok", timeout=30)
        assert final.has_condition(T.JOB_SUCCEEDED)
        assert not final.has_condition(T.JOB_RUNNING)
        assert final.status["replicaStatuses"]["worker"]["succeeded"] == 2
        assert "completionTime" in final.status
        log = cp.job_logs("JAXJob", "ok")
        assert "rank 0" in log

    def test_failure_with_backoff_and_restart_count(self, cp):
        job = _job("JAXJob", "bad", "jaxReplicaSpecs",
                   {"Worker": {"replicas": 1, "restartPolicy": "OnFailure",
                               "template": _tmpl("raise SystemExit(3)")}},
                   run_policy={"backoffLimit": 2})
        cp.apply([job])
        final = cp.wait_for_job("JAXJob", "bad", timeout=30)
        assert final.has_condition(T.JOB_FAILED)
        assert final.status["restartCount"] == 2
        assert final.status["replicaStatuses"]["worker"]["failed"] == 1

    def test_restart_policy_never(self, cp):
        job = _job("JAXJob", "never", "jaxReplicaSpecs",
                   {"Worker": {"replicas": 1, "restartPolicy": "Never",
                               "template": _tmpl("raise SystemExit(3)")}})
        cp.apply([job])
        final = cp.wait_for_job("JAXJob", "never", timeout=30)
        assert final.has_condition(T.JOB_FAILED)
        assert final.status.get("restartCount", 0) == 0

    def test_chief_success_tears_down_ps(self, cp):
        """TFJob: PS never exits; chief exit 0 + cleanPodPolicy=Running must
        still complete the job (reference tf-operator semantics)."""
        job = _job("TFJob", "tf", "tfReplicaSpecs", {
            "Chief": {"replicas": 1, "template": _tmpl("print('chief done')")},
            "PS": {"replicas": 1, "template": _tmpl(
                "import time\nwhile True: time.sleep(1)")},
        }, run_policy={"cleanPodPolicy": "Running"})
        cp.apply([job])
        final = cp.wait_for_job("TFJob", "tf", timeout=30)
        assert final.has_condition(T.JOB_SUCCEEDED)

    def test_delete_kills_gang(self, cp):
        job = _job("JAXJob", "del", "jaxReplicaSpecs", {"Worker": {
            "replicas": 1,
            "template": _tmpl("import time\nwhile True: time.sleep(1)")}})
        cp.apply([job])
        cp.wait_for_condition("JAXJob", "del", T.JOB_RUNNING, timeout=30)
        gang = cp.gangs.get("jaxjob/default/del")
        assert gang is not None
        pid = next(iter(gang.status().replicas.values())).pid
        cp.store.delete("JAXJob", "del")
        _wait(lambda: not _alive(pid), what="process death")

    def test_suspend_and_resume(self, cp):
        job = _job("JAXJob", "susp", "jaxReplicaSpecs", {"Worker": {
            "replicas": 1,
            "template": _tmpl("import time; time.sleep(0.3)")}},
            run_policy={"suspend": True})
        cp.apply([job])
        cp.wait_for_condition("JAXJob", "susp", T.JOB_SUSPENDED, timeout=30)
        assert cp.gangs.get("jaxjob/default/susp") is None
        # Resume: clear the flag via apply.
        fresh = cp.store.get("JAXJob", "susp")
        fresh.spec["runPolicy"]["suspend"] = False
        cp.store.update(fresh)
        final = cp.wait_for_job("JAXJob", "susp", timeout=30)
        assert final.has_condition(T.JOB_SUCCEEDED)

    def test_ttl_garbage_collection(self, cp):
        job = _job("JAXJob", "ttl", "jaxReplicaSpecs",
                   {"Worker": {"replicas": 1, "template": _tmpl("pass")}},
                   run_policy={"ttlSecondsAfterFinished": 1})
        cp.apply([job])
        cp.wait_for_job("JAXJob", "ttl", timeout=30)
        _wait(lambda: cp.store.try_get("JAXJob", "ttl") is None,
              timeout=10, what="ttl deletion")

    def test_active_deadline(self, cp):
        job = _job("JAXJob", "dl", "jaxReplicaSpecs", {"Worker": {
            "replicas": 1, "restartPolicy": "Never",
            "template": _tmpl("import time\nwhile True: time.sleep(1)")}},
            run_policy={"activeDeadlineSeconds": 1})
        cp.apply([job])
        final = cp.wait_for_job("JAXJob", "dl", timeout=30)
        assert final.has_condition(T.JOB_FAILED)
        failed = next(c for c in final.conditions if c.type == "Failed")
        assert failed.reason in ("GangFailed",)

    def test_mpijob_launcher_shim_runs_ranks(self, cp):
        """`mpirun -np 2 python -c ...` through the shim: both ranks run and
        the job succeeds when the launcher exits 0."""
        rank_prog = ("import os; print('mpirank',"
                     " os.environ['OMPI_COMM_WORLD_RANK'])")
        job = _job("MPIJob", "mpi", "mpiReplicaSpecs", {
            "Launcher": {"replicas": 1, "template": {"spec": {"containers": [{
                "name": "l",
                "command": ["mpirun", "-np", "2", PY, "-c", rank_prog]}]}}},
            "Worker": {"replicas": 2, "template": _tmpl(
                "import time\nwhile True: time.sleep(1)")},
        })
        cp.apply([job])
        final = cp.wait_for_job("MPIJob", "mpi", timeout=30)
        assert final.has_condition(T.JOB_SUCCEEDED)
        log = cp.job_logs("MPIJob", "mpi")
        assert "mpirank 0" in log and "mpirank 1" in log


def _alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


class TestKfxCLI:
    def test_run_get_describe_logs(self, tmp_path, capsys):
        from kubeflow_tpu.cli import main as kfx_main

        manifest = tmp_path / "job.yaml"
        manifest.write_text(f"""
apiVersion: kubeflow.org/v1
kind: JAXJob
metadata:
  name: cli-job
spec:
  jaxReplicaSpecs:
    Worker:
      replicas: 1
      template:
        spec:
          containers:
          - name: main
            command: ["{PY}", "-c", "print('hello from job')"]
""")
        home = str(tmp_path / "home")
        rc = kfx_main(["--home", home, "run", "-f", str(manifest)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "jaxjob/cli-job created" in out
        assert "hello from job" in out
        assert "jaxjob/cli-job succeeded" in out

        # State persisted via the journal: get/describe work in a new process.
        rc = kfx_main(["--home", home, "get", "jaxjobs"])
        out = capsys.readouterr().out
        assert rc == 0 and "cli-job" in out and "Succeeded" in out

        rc = kfx_main(["--home", home, "describe", "jaxjob", "cli-job"])
        out = capsys.readouterr().out
        assert rc == 0 and "kind: JAXJob" in out

        rc = kfx_main(["--home", home, "logs", "jaxjob", "cli-job"])
        out = capsys.readouterr().out
        assert rc == 0 and "hello from job" in out

        rc = kfx_main(["--home", home, "delete", "jaxjob", "cli-job"])
        out = capsys.readouterr().out
        assert rc == 0 and "deleted" in out


@pytest.mark.slow
class TestDistributedE2E:
    def test_two_worker_jaxjob_trains_mnist(self, cp):
        """The north-star slice (SURVEY.md §7 step 4): a 2-worker JAXJob
        where workers rendezvous via jax.distributed, train data-parallel,
        and the job completes via the reconcile loop."""
        job = _job("JAXJob", "mnist-e2e", "jaxReplicaSpecs", {"Worker": {
            "replicas": 2,
            "template": {"spec": {"containers": [{
                "name": "jax",
                "command": [PY, "-m", "kubeflow_tpu.runners.jax_runner",
                            "--model=mlp", "--dataset=mnist", "--steps=8",
                            "--batch-size=64", "--log-every=4",
                            "--no-checkpoint"],
            }]}}}})
        cp.apply([job])
        final = cp.wait_for_job("JAXJob", "mnist-e2e", timeout=180)
        assert final.has_condition(T.JOB_SUCCEEDED), \
            cp.job_logs("JAXJob", "mnist-e2e")
        log = cp.job_logs("JAXJob", "mnist-e2e")
        assert "world=2" in log
        assert "train_done steps=8" in log

    @pytest.mark.slow
    def test_parameter_server_tfjob_trains_mnist(self, cp):
        """Live ParameterServerStrategy TFJob (the reference tf-operator's
        original flagship mode, SURVEY.md §2.1/§2.3): the chief drives a
        ClusterCoordinator, two workers execute scheduled steps, and the
        PS task serves every model/optimizer variable. ps and worker
        servers never exit; chief success + cleanPodPolicy=Running reaps
        them and completes the job."""
        runner = [PY, "-m", "kubeflow_tpu.runners.tf_runner",
                  "--dataset=mnist", "--steps=60", "--batch-size=128",
                  "--log-every=20", "--eval-samples=512"]
        tmpl = {"spec": {"containers": [{"name": "tf", "command": runner}]}}
        job = _job("TFJob", "ps-e2e", "tfReplicaSpecs", {
            "Chief": {"replicas": 1, "template": tmpl},
            "Worker": {"replicas": 2, "template": tmpl},
            "PS": {"replicas": 1, "template": tmpl},
        }, run_policy={"cleanPodPolicy": "Running"})
        cp.apply([job])
        final = cp.wait_for_job("TFJob", "ps-e2e", timeout=300)
        log = cp.job_logs("TFJob", "ps-e2e")  # chief replica
        assert final.has_condition(T.JOB_SUCCEEDED), log
        assert "mode=ps role=chief:0" in log
        assert "mode=ps role=ps:0 server=started" in cp.job_logs(
            "TFJob", "ps-e2e", replica="ps-0")
        assert "mode=ps role=worker:1 server=started" in cp.job_logs(
            "TFJob", "ps-e2e", replica="worker-1")
        # Every variable (6 model params + 12 Adam slots) genuinely lives
        # on the PS server.
        assert "variables_total=18 variables_on_ps=18" in log
        assert "/job:ps" in log
        assert "train_done steps=60" in log
        # Converging, not just running: eval accuracy well above the 0.1
        # chance floor after 60 steps.
        evals = [ln for ln in log.splitlines() if ln.startswith("accuracy=")]
        assert evals, log
        assert float(evals[-1].split("=")[1]) > 0.4, evals
