"""Cross-process SPMD correctness check.

On a real TPU pod the device mesh always spans processes (one per host);
the reference frameworks prove their multi-host story with NCCL/MPI
integration runs (SURVEY.md §2.3, §5.8). The TPU-native equivalent: the
SAME `LMTrainLoop` jitted step, with the SAME NamedShardings, run

  (a) in one process owning all devices of the mesh, and
  (b) as a JAXJob-style gang of N processes, each owning a slice of the
      mesh, rendezvoused through ``jax.distributed.initialize`` with gloo
      CPU collectives (the DCN stand-in on this host),

must produce per-step losses that agree to collective-reduction-order
tolerance. GSPMD guarantees the per-device program is identical; the only
legitimate difference is the order of cross-process reductions.

Variants (2 processes x 4 devices):
  * ``tp_fsdp`` — mesh (dp=4, tp=2): each process owns two dp rows, so
    the fsdp all-gathers/reduce-scatters and the loss psum cross the
    process boundary.
  * ``cp`` — mesh (dp=1, cp=2, tp=4): the "ctx" axis is the OUTER
    nontrivial axis, so ctx block 0 lives wholly in process 0 and block 1
    in process 1 — the ring-attention ppermutes themselves cross the
    process boundary (dp=2,cp=2 would keep the ring intra-process).
  * ``ep`` — MoE experts over the dp=4 "data" axis: experts 0-1 live in
    process 0 and 2-3 in process 1, so the token-routing all-to-alls
    cross the process boundary.
  * ``pp`` — mesh (pp=2, dp=2, tp=2), PipelinedLMTrainLoop: "stage" is
    the outermost mesh axis, so stage 0 is wholly process 0 and stage 1
    wholly process 1 — every per-microbatch activation ppermute at the
    stage boundary (forward AND its reversed backward) crosses the
    process boundary. This is exactly the transfer a single-process
    pipeline run never exercises (on a real pod the stage axis spans
    hosts).

The check is wired two ways:
  * ``__graft_entry__.dryrun_multichip`` runs it as its cross-process tier
    (2 processes x n/2 virtual CPU devices);
  * ``tests/test_spmd_multiprocess.py`` runs both variants as tests.

Data contract: the global batch is the concatenation of ``plan.dp``
deterministic disjoint shards (``LMDataset.batches(shard_index=d,
num_shards=dp)``). Each process feeds exactly the rows owned by its
devices along the "data" axis (read off the mesh, not assumed from rank)
through ``jax.make_array_from_process_local_data``; the single-process
reference concatenates all rows. Both modes therefore consume the
identical global batch — including the dp=1 case, where every process
feeds the full (replicated) batch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List

CHECK_STEPS = 4
GLOBAL_BATCH = 16
VOCAB = 128
SEQ = 32
# Per-step loss agreement bound. f32 loss/grad accumulation; the only
# divergence source is reduction order in the cross-process collectives.
RTOL = 2e-3

VARIANTS = ("tp_fsdp", "cp", "ep", "pp")


def _build_loop(variant: str, n_devices: int):
    from ..models.transformer import TransformerConfig
    from .lm_train import LMHyperParams, LMTrainLoop
    from .mesh import make_mesh

    kw = dict(vocab_size=VOCAB, d_model=32, n_heads=4, head_dim=8,
              n_layers=2, d_ff=64, max_seq_len=SEQ)
    hp = LMHyperParams(total_steps=CHECK_STEPS, warmup_steps=1)
    if variant == "cp":
        # cp outermost-nontrivial (dp=1): the ring crosses processes.
        tp = n_devices // 2
        mesh, plan = make_mesh(n_devices, tp=tp, cp=2, fsdp=True)
        cfg = TransformerConfig(cp=plan.cp, **kw)
    elif variant == "tp_fsdp":
        tp = 2 if n_devices % 2 == 0 else 1
        mesh, plan = make_mesh(n_devices, tp=tp, fsdp=True)
        cfg = TransformerConfig(**kw)
    elif variant == "ep":
        # MoE experts shard over "data" (dp=4 with 2 procs -> experts
        # 0-1 live in process 0, 2-3 in process 1): the token-routing
        # all-to-alls cross the process boundary.
        tp = 2 if n_devices % 2 == 0 else 1
        mesh, plan = make_mesh(n_devices, tp=tp, fsdp=True)
        cfg = TransformerConfig(n_experts=plan.dp, **kw)
    elif variant == "pp":
        # Stage axis outermost: with 2 processes each owning half the
        # devices, stage 0 IS process 0 and stage 1 IS process 1 — the
        # GPipe activation ppermutes cross the process boundary every
        # tick. n_layers=2 / pp=2 -> one layer per stage.
        from .pipeline import PipelinedLMTrainLoop

        tp = 2 if n_devices % 4 == 0 else 1
        mesh, plan = make_mesh(n_devices, pp=2, tp=tp, fsdp=True)
        return PipelinedLMTrainLoop(TransformerConfig(**kw), mesh, plan, hp)
    else:
        raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
    return LMTrainLoop(cfg, mesh, plan, hp)


def _owned_dp_rows(mesh, plan) -> List[int]:
    """dp rows of the global batch this process must feed: every row whose
    mesh block contains at least one of this process's devices (a fully
    replicated row — dp=1 — is owned, and fed, by every process)."""
    import jax

    pid = jax.process_index()
    arr = mesh.devices  # (pp, dp, cp, tp)
    return [d for d in range(plan.dp)
            if any(dev.process_index == pid for dev in arr[:, d].flat)]


def run_losses(variant: str) -> List[float]:
    """Train CHECK_STEPS steps; return the per-step losses.

    Single- or multi-process; the global batch consumed per step is
    identical in both modes (see module docstring)."""
    import jax
    import numpy as np

    from ..data.lm import LMDataset

    loop = _build_loop(variant, len(jax.devices()))
    dp = loop.plan.dp
    rows = (_owned_dp_rows(loop.mesh, loop.plan)
            if jax.process_count() > 1 else list(range(dp)))
    ds = LMDataset(vocab_size=VOCAB, seq_len=SEQ)
    # Generate every shard stream everywhere (they are seeded per
    # (step, shard), so this is cheap and keeps streams aligned); feed
    # only the owned rows.
    its = {d: ds.batches(GLOBAL_BATCH, shard_index=d, num_shards=dp)
           for d in range(dp)}
    state = loop.init_state()
    losses = []
    for _ in range(CHECK_STEPS):
        shards = {d: next(it) for d, it in its.items()}
        batch = np.concatenate([shards[d] for d in rows], axis=0)
        state, loss, _ = loop.train_step(state, batch)
        losses.append(float(loss))
    return losses


def assert_close(single: List[float], multi: List[float],
                 rtol: float = RTOL) -> None:
    if len(single) != len(multi):
        raise AssertionError(f"step counts differ: {single} vs {multi}")
    for i, (a, b) in enumerate(zip(single, multi)):
        if abs(a - b) > rtol * max(1.0, abs(a)):
            raise AssertionError(
                f"step {i}: single-process loss {a} vs cross-process {b} "
                f"(|delta|={abs(a - b):.3e} > rtol={rtol}); "
                f"full: {single} vs {multi}")


def cross_process_losses(variant: str, workdir: str, *, n_processes: int = 2,
                         devices_per_proc: int = 4,
                         timeout: float = 600.0) -> List[float]:
    """Run ``run_losses(variant)`` as an n-process JAXJob-style gang on the
    real gang runtime; returns rank 0's per-step losses."""
    from ..api import training as T
    from ..runtime import Gang, ProcessSpec, flatten_replicas, jax_env
    from ..utils.net import free_port
    from ..utils.proc import inject_pythonpath
    from ..vmeshenv import virtual_mesh_env

    out = os.path.join(workdir, "losses.json")
    specs = []
    for rtype, idx, rank in flatten_replicas([("Worker", n_processes)]):
        # The rendezvous address is supplied by fresh_coordinator below on
        # EVERY attempt (the gang runs the hook on attempt 0 too), so the
        # spec-level value is a placeholder that is always overridden.
        env = dict(virtual_mesh_env(devices_per_proc))
        env.update(jax_env("spmd-check", "default", "coordinator-from-hook",
                           n_processes, rank, rtype, idx, workdir,
                           platform="cpu"))
        inject_pythonpath(env)
        specs.append(ProcessSpec(
            replica_type=rtype, index=idx,
            argv=[sys.executable, "-m", "kubeflow_tpu.parallel.spmd_check",
                  "--variant", variant, "--out", out],
            env=env))

    def fresh_coordinator(attempt: int):
        # Every attempt — first launch and whole-gang restarts (e.g. a
        # rendezvous-port collision crash) — gets a freshly probed
        # coordinator port: the self-healing contract the training
        # operators use.
        return {"*": {"KFX_COORDINATOR_ADDRESS": f"127.0.0.1:{free_port()}"}}

    gang = Gang("spmd-check", specs, workdir, chief_replica_type="Worker",
                restart_policy=T.RESTART_ON_FAILURE, backoff_limit=2,
                restart_env_hook=fresh_coordinator)

    # The gang's preexec_fn (PDEATHSIG) forces subprocess down the
    # fork+exec path, which Python 3.12 warns about in multithreaded
    # processes (jax is). The child exec's immediately, so the warning is
    # noise — and it would dirty the driver's dryrun tail. Scoped: the
    # monitor thread launches (and restarts) workers only while we block
    # inside this context.
    import warnings

    try:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=r"os\.fork\(\) was called",
                category=RuntimeWarning)
            gang.start()
            deadline = time.time() + timeout
            while time.time() < deadline:
                st = gang.status()
                if st.phase in ("Succeeded", "Failed", "Killed"):
                    break
                time.sleep(0.2)
            else:
                raise TimeoutError(
                    f"spmd-check gang did not finish in {timeout}s")
    finally:
        gang.delete()
    if st.phase != "Succeeded":
        logs = "".join(
            open(gang.log_path(s.id)).read() for s in specs
            if os.path.exists(gang.log_path(s.id)))
        raise RuntimeError(
            f"spmd-check gang {st.phase}: {st.reason} {st.message}\n{logs}")
    with open(out) as f:
        return json.load(f)["losses"]


def check(variant: str, workdir: str, *, n_processes: int = 2,
          devices_per_proc: int = 4) -> List[float]:
    """Cross-process vs single-process loss comparison (the full check).

    Caller must already own ``n_processes * devices_per_proc`` devices
    (the single-process reference runs in-process)."""
    multi = cross_process_losses(variant, workdir, n_processes=n_processes,
                                 devices_per_proc=devices_per_proc)
    single = run_losses(variant)
    assert_close(single, multi)
    return multi


def check_attention_sharding(n_devices: int = 8, tp: int = 2, cp: int = 1,
                             fsdp: bool = True) -> dict:
    """Assert the chosen sharding has no accidental replication of the
    attention activations.

    The Megatron layout promises q/k/v (and the pre-projection mix) are
    sharded batch-over-"data" AND heads-over-"model" (plus seq-over-
    "ctx" when context parallel): a broken constraint or rules-table
    edit that lets GSPMD replicate them multiplies activation HBM by
    the tp width — the exact failure mode that silently caps batch size
    on real chips. The check runs the REAL ``Attention`` module (the
    activation_probe hook captures GSPMD's chosen shardings via
    jax.debug.inspect_array_sharding) and asserts every captured
    activation's per-device shard is its global size over
    dp * tp * cp. Returns {name: {"spec", "shard_fraction"}}.

    Wired into ``__graft_entry__.dryrun_multichip`` and tier-1
    (tests/test_parallel.py)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import transformer as TR
    from .mesh import AXIS_CTX, AXIS_DATA, AXIS_MODEL, make_mesh

    mesh, plan = make_mesh(n_devices, tp=tp, cp=cp, fsdp=fsdp)
    heads = 2 * plan.tp
    cfg_kw = dict(vocab_size=64, d_model=32, n_heads=heads, head_dim=8,
                  n_layers=1, d_ff=64, max_seq_len=32)
    cfg = TR.TransformerConfig(cp=plan.cp, **cfg_kw) if plan.cp > 1 \
        else TR.TransformerConfig(**cfg_kw)
    attn = TR.Attention(cfg)
    B = max(2 * plan.dp * max(plan.cp, 1), 4)
    S = 32
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(B, S, cfg.d_model)), np.float32)
    positions = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S))
    with jax.set_mesh(mesh):
        # Under the mesh: the cp path's ring shard_map needs an ambient
        # mesh even at init-trace time.
        params = attn.init(jax.random.PRNGKey(0), x, positions)["params"]

    embed_axis = AXIS_DATA if fsdp else None
    qkv_sh = NamedSharding(mesh, P(embed_axis, AXIS_MODEL, None))
    param_sh = {
        "query": {"kernel": qkv_sh},
        "key": {"kernel": qkv_sh},
        "value": {"kernel": qkv_sh},
        "out": {"kernel": NamedSharding(
            mesh, P(AXIS_MODEL, None, embed_axis))},
    }
    seq_axis = AXIS_CTX if plan.cp > 1 else None
    x_sh = NamedSharding(mesh, P(AXIS_DATA, seq_axis, None))
    pos_sh = NamedSharding(mesh, P(AXIS_DATA, seq_axis))

    captured: dict = {}
    shapes: dict = {}

    def probe(name, arr):
        shapes[name] = tuple(arr.shape)
        jax.debug.inspect_array_sharding(
            arr, callback=lambda s, n=name: captured.__setitem__(n, s))

    with jax.set_mesh(mesh):
        gp = jax.device_put(params, param_sh)
        gx = jax.device_put(x, x_sh)
        gpos = jax.device_put(positions, pos_sh)
        with TR.activation_probe(probe):
            out = jax.jit(
                lambda p, x, pos: attn.apply({"params": p}, x, pos)
            )(gp, gx, gpos)
        jax.block_until_ready(out)

    want_ways = plan.dp * plan.tp * max(plan.cp, 1)
    report = {}
    problems = []
    for name, shape in sorted(shapes.items()):
        sh = captured.get(name)
        if sh is None:
            problems.append(f"{name}: sharding not captured")
            continue
        per = int(np.prod(sh.shard_shape(shape)))
        frac = per / float(np.prod(shape))
        report[name] = {"spec": str(getattr(sh, "spec", sh)),
                        "shard_fraction": frac}
        if frac * want_ways > 1.0 + 1e-6:
            problems.append(
                f"{name} {shape}: per-device shard holds {frac:.3f} of "
                f"the global array — replicated beyond the "
                f"1/{want_ways} the dp{plan.dp}/tp{plan.tp}/cp{plan.cp} "
                f"layout promises (spec {report[name]['spec']})")
    if problems:
        raise AssertionError(
            "attention activation replication check failed:\n  "
            + "\n  ".join(problems))
    return report


def _worker_main(argv=None) -> int:
    p = argparse.ArgumentParser(description="spmd cross-process check worker")
    p.add_argument("--variant", choices=VARIANTS, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    from ..runners.jax_runner import initialize_distributed

    initialize_distributed()

    import jax

    losses = run_losses(args.variant)
    print(f"spmd_check_done rank={jax.process_index()} "
          f"world={jax.process_count()} losses={losses}", flush=True)
    if jax.process_index() == 0:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"variant": args.variant, "losses": losses}, f)
        os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(_worker_main())
