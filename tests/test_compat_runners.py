"""Compat-stack E2E: the three baseline configs' runners actually train.

Config #1 TFJob/tf.distribute, #2 PyTorchJob/gloo DDP, #3 MPIJob/Horovod-env
→ jax.distributed (BASELINE.md). Steps are tiny — these assert the
rendezvous + train + metrics contract works per framework, not model
quality (that's the full configs in bench).
"""

import os
import subprocess
import sys

import pytest

PY = sys.executable
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra=None):
    env = dict(os.environ)
    prior = env.get("PYTHONPATH")
    env["PYTHONPATH"] = REPO_ROOT + (os.pathsep + prior if prior else "")
    env.update(extra or {})
    return env


def _run(argv, extra_env=None, timeout=300):
    return subprocess.run(argv, env=_env(extra_env), capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
class TestCompatRunners:
    def test_tf_runner_single_worker(self):
        out = _run([PY, "-m", "kubeflow_tpu.runners.tf_runner",
                    "--dataset=mnist", "--steps=10", "--batch-size=64",
                    "--log-every=5", "--eval-samples=256"])
        assert out.returncode == 0, out.stdout + out.stderr
        assert "framework=tf" in out.stdout
        assert "train_done steps=10" in out.stdout
        assert "accuracy=" in out.stdout

    def test_torch_runner_two_worker_gloo(self, tmp_path):
        from kubeflow_tpu.utils.net import free_port

        port = str(free_port())
        procs = []
        for rank in range(2):
            procs.append(subprocess.Popen(
                [PY, "-m", "kubeflow_tpu.runners.torch_runner",
                 "--dataset=mnist", "--steps=10", "--batch-size=64",
                 "--log-every=5", "--eval-samples=256", "--backend=gloo"],
                env=_env({"MASTER_ADDR": "127.0.0.1", "MASTER_PORT": port,
                          "WORLD_SIZE": "2", "RANK": str(rank)}),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        outs = [p.communicate(timeout=300)[0] for p in procs]
        assert all(p.returncode == 0 for p in procs), "\n".join(outs)
        assert "rank=0 world=2" in outs[0]
        assert "train_done steps=10" in outs[0]

    def test_mpi_jax_runner_two_ranks_via_shim(self):
        out = _run([PY, "-m", "kubeflow_tpu.runners.mpi_launcher", "-np", "2",
                    PY, "-m", "kubeflow_tpu.runners.mpi_jax_runner",
                    "--model=mlp", "--dataset=mnist", "--steps=6",
                    "--batch-size=64", "--log-every=3", "--no-checkpoint"],
                   extra_env={"JAX_PLATFORMS": "cpu",
                              "XLA_FLAGS":
                              "--xla_force_host_platform_device_count=4"})
        assert out.returncode == 0, out.stdout + out.stderr
        assert "world=2" in out.stdout
        assert "train_done steps=6" in out.stdout

    def test_tf_runner_two_worker_mwms(self):
        """MultiWorkerMirroredStrategy: grads all-reduce, so workers print
        identical synchronized losses."""
        import json as _json

        from kubeflow_tpu.utils.net import free_port

        ports = [free_port(), free_port()]
        cluster = {"worker": [f"127.0.0.1:{p}" for p in ports]}
        procs = []
        for i in range(2):
            env = _env({"TF_CONFIG": _json.dumps(
                {"cluster": cluster,
                 "task": {"type": "worker", "index": i}}),
                "CUDA_VISIBLE_DEVICES": "-1"})
            procs.append(subprocess.Popen(
                [PY, "-m", "kubeflow_tpu.runners.tf_runner",
                 "--dataset=mnist", "--steps=6", "--batch-size=64",
                 "--log-every=3", "--eval-samples=128"], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        outs = [p.communicate(timeout=300)[0] for p in procs]
        assert all(p.returncode == 0 for p in procs), "\n".join(outs)
        step_lines = [
            [ln.split(" step_time=")[0] for ln in o.splitlines()
             if ln.startswith("step=")]
            for o in outs]
        # identical synchronized loss/accuracy on both workers
        assert step_lines[0] == step_lines[1] and step_lines[0]
