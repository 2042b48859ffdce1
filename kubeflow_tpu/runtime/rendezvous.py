"""Rendezvous bootstrap: the env/config each worker process receives.

This is the one job the reference operators do for distributed comms
(SURVEY.md §2.3): tf-operator writes ``TF_CONFIG``, pytorch-operator sets
``MASTER_ADDR``/``RANK``/..., mpi-operator writes a hostfile. The TPU-native
path (JAXJob) replaces all of that with ``jax.distributed.initialize``
coordinates; XLA collectives over ICI/DCN do the rest.

Everything here is pure (dict in → env dict out), which is exactly how the
reference unit-tests this layer (SURVEY.md §4: "assert the generated
TF_CONFIG/hostfile is correct").
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

# Env names for the JAX-native rendezvous. The runner passes these straight
# into jax.distributed.initialize(...).
ENV_COORDINATOR = "KFX_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "KFX_NUM_PROCESSES"
ENV_PROCESS_ID = "KFX_PROCESS_ID"
ENV_REPLICA_TYPE = "KFX_REPLICA_TYPE"
ENV_REPLICA_INDEX = "KFX_REPLICA_INDEX"
ENV_JOB_NAME = "KFX_JOB_NAME"
ENV_JOB_NAMESPACE = "KFX_JOB_NAMESPACE"
ENV_WORKDIR = "KFX_WORKDIR"
ENV_CHECKPOINT_DIR = "KFX_CHECKPOINT_DIR"


def apply_startup_chaos() -> float:
    """Fault point ``rendezvous.delay``: a straggling worker. Runners
    call this before ``jax.distributed.initialize`` (workers inherit
    KFX_CHAOS through the gang env), so an injected delay exercises the
    coordinator's tolerance for late joiners — the barrier must wait,
    not split-brain. Returns the seconds slept. An injected sleep is
    recorded as a ``rendezvous.chaos`` span so the straggler shows up
    on the `kfx trace` waterfall exactly where the gap is."""
    import time

    from .. import chaos
    from ..obs import trace as obs_trace

    rtype = os.environ.get(ENV_REPLICA_TYPE, "")
    index = os.environ.get(ENV_REPLICA_INDEX, "")
    t0 = time.time()
    slept = chaos.maybe_delay("rendezvous.delay",
                              target=f"{rtype.lower()}-{index}")
    if slept > 0:
        obs_trace.record_span("rendezvous.chaos", t0, slept,
                              replica=f"{rtype.lower()}-{index}")
    return slept


def flatten_replicas(replica_counts: List[Tuple[str, int]]) -> List[Tuple[str, int, int]]:
    """[(type, count)] -> [(type, index, global_rank)] in declaration order."""
    out = []
    rank = 0
    for rtype, count in replica_counts:
        for i in range(count):
            out.append((rtype, i, rank))
            rank += 1
    return out


def jax_env(job_name: str, namespace: str, coordinator: str,
            num_processes: int, process_id: int, rtype: str, index: int,
            workdir: str, platform: str = "") -> Dict[str, str]:
    """JAXJob worker env: jax.distributed coordinates (the NCCL-rendezvous
    replacement) plus job identity for checkpoints/metrics.

    ``platform`` pins JAX_PLATFORMS for the worker. A multi-process gang
    on ``cpu`` also selects gloo CPU collectives so XLA collectives
    actually span processes.
    """
    env = {
        ENV_COORDINATOR: coordinator,
        ENV_NUM_PROCESSES: str(num_processes),
        ENV_PROCESS_ID: str(process_id),
        ENV_REPLICA_TYPE: rtype,
        ENV_REPLICA_INDEX: str(index),
        ENV_JOB_NAME: job_name,
        ENV_JOB_NAMESPACE: namespace,
        ENV_WORKDIR: workdir,
        ENV_CHECKPOINT_DIR: f"{workdir}/checkpoints",
    }
    if platform:
        env["JAX_PLATFORMS"] = platform
    if platform == "cpu" and num_processes > 1:
        env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    return env


def tf_config(cluster: Dict[str, List[str]], task_type: str,
              task_index: int) -> str:
    """The TF_CONFIG JSON (reference tf-operator genTFConfig). Replica-type
    keys are lowercased as TF expects (Worker -> worker, PS -> ps)."""
    return json.dumps({
        "cluster": {k.lower(): v for k, v in cluster.items()},
        "task": {"type": task_type.lower(), "index": task_index},
        "environment": "cloud",
    }, sort_keys=True)


def tf_env(cluster: Dict[str, List[str]], rtype: str, index: int) -> Dict[str, str]:
    return {"TF_CONFIG": tf_config(cluster, rtype, index)}


def pytorch_env(master_addr: str, master_port: int, world_size: int,
                rank: int) -> Dict[str, str]:
    """PyTorchJob worker env (reference pytorch-operator SetPodEnv). The
    reference's NCCL backend becomes gloo on CPU; rendezvous contract is
    identical."""
    return {
        "MASTER_ADDR": master_addr,
        "MASTER_PORT": str(master_port),
        "WORLD_SIZE": str(world_size),
        "RANK": str(rank),
        # torchrun-era aliases some scripts read:
        "LOCAL_RANK": "0",
        "NODE_RANK": str(rank),
    }


def mpi_hostfile(worker_hosts: List[str], slots_per_worker: int = 1) -> str:
    """Hostfile content (reference mpi-operator's discover/kubexec model)."""
    return "".join(f"{h} slots={slots_per_worker}\n" for h in worker_hosts)


def mpi_worker_env(rank: int, size: int, local_rank: int = 0) -> Dict[str, str]:
    """OpenMPI-shaped env for workers launched directly by the gang (no
    mpirun binary in this environment; single-host process model)."""
    return {
        "OMPI_COMM_WORLD_RANK": str(rank),
        "OMPI_COMM_WORLD_SIZE": str(size),
        "OMPI_COMM_WORLD_LOCAL_RANK": str(local_rank),
        "OMPI_COMM_WORLD_LOCAL_SIZE": "1",
    }
