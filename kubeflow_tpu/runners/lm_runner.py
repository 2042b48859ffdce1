"""Flagship LM training worker: transformer over the full parallelism
stack (dp/fsdp/tp/sp/ep/pp) on a device mesh.

Same process contract as jax_runner (rendezvous env, checkpoint/resume,
stdout metric lines), but the model is the TransformerLM family and the
mesh plan is selectable from the manifest:

    python -m kubeflow_tpu.runners.lm_runner --preset=small --tp=4 --fsdp \
        --steps=1000 --batch-size=32 --seq-len=2048
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Wall-clock anchor for the runner.init span (covers interpreter +
# backend startup, same contract as jax_runner).
_PROC_START = time.time()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="kfx LM training runner")
    p.add_argument("--preset", default="tiny",
                   help="transformer size preset (tiny|small|base|large)")
    p.add_argument("--dataset", default="lm-tiny")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=0,
                   help="override dataset/preset sequence length")
    p.add_argument("--learning-rate", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=50)
    p.add_argument("--tp", type=int, default=0, help="tensor parallel ways")
    p.add_argument("--pp", type=int, default=1, help="pipeline stages")
    p.add_argument("--fsdp", action="store_true")
    p.add_argument("--sp", action="store_true", help="sequence parallelism")
    p.add_argument("--cp", type=int, default=1,
                   help="context parallel ways (ring attention over 'ctx')")
    p.add_argument("--experts", type=int, default=0, help="MoE experts (ep)")
    p.add_argument("--remat", action="store_true")
    # Not argparse-choices: the model owns the policy names (including
    # the save_flash* family and the free-form "save_names:a,b,..."
    # escape hatch) and rejects unknown ones with the full list.
    p.add_argument("--remat-policy", default="nothing",
                   help="what remat may KEEP (save_dense: fat matmul "
                        "outputs stay, only elementwise + the S^2 "
                        "block recompute; needs the linear-in-S saves "
                        "to fit HBM)")
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "flash", "naive", "xla", "ring"],
                   help="attention path; 'auto' picks the pallas flash "
                        "kernel inside --flash-window; 'naive' (alias "
                        "'xla') forces the dense oracle; 'ring' asserts "
                        "the sequence axis is sharded (--cp>1)")
    def flash_window(value: str):
        lo, _, hi = value.partition(":")
        try:
            return (int(lo), int(hi) if hi else None)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"expected MIN[:MAX] integers, got {value!r}") from None

    p.add_argument("--flash-window", default=None, type=flash_window,
                   help="MIN[:MAX] seq-len window where 'auto' uses "
                        "flash (default: the v5e-measured 2048:4096; "
                        "MAX 0 = unbounded). Re-measure per hardware.")
    p.add_argument("--microbatches", type=int, default=0)
    p.add_argument("--collective-overlap", action="store_true",
                   help="pass libtpu the async-collective + latency-"
                        "hiding-scheduler flags (LIBTPU_INIT_ARGS, "
                        "parallel/overlap.py) so grad all-reduces "
                        "overlap the backward; off until a chip "
                        "measurement shows the gain")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--checkpoint-every", type=int, default=200)
    p.add_argument("--keep-checkpoints", type=int, default=2)
    p.add_argument("--no-checkpoint", action="store_true")
    p.add_argument("--fail-at-step", type=int, default=-1)
    p.add_argument("--export-dir", default="",
                   help="after training, write a servable LM export here "
                        "(serving/lm_server.py format)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from ..obs import trace as obs_trace
    from ..runtime.lifetime import install_parent_watch

    install_parent_watch()
    from .jax_runner import (device_report, enable_compile_cache,
                             initialize_distributed, parallelism_from_env)

    enable_compile_cache()

    # Declarative JAXJob parallelism (operator-injected env) fills flag
    # defaults; explicit CLI flags win. Value casts are tolerant — the
    # operator validates at apply, so a malformed value here is stale
    # hand-set env, and parallelism_from_env's contract is that stale
    # env never kills a worker that was told its plan on the CLI.
    par = parallelism_from_env()

    def par_int(key, default):
        try:
            return int(par.get(key, default) or default)
        except (TypeError, ValueError):
            print(f"warning: ignoring non-integer KFX_PARALLELISM "
                  f"{key}={par.get(key)!r}", file=sys.stderr)
            return default

    if par:
        if not args.tp:
            args.tp = par_int("tensor", 0)
        if args.pp <= 1:
            args.pp = par_int("pipeline", 1)
        if args.cp <= 1:
            args.cp = par_int("context", 1)
        if not args.fsdp:
            args.fsdp = bool(par.get("fsdp", False))
        if not args.sp:
            args.sp = bool(par.get("sp", False))
        if not args.microbatches:
            args.microbatches = par_int("microbatches", 0)

    if args.collective_overlap:
        # libtpu reads its flags when the backend starts, which is
        # below.
        from ..parallel.overlap import apply_overlap_env

        apply_overlap_env(os.environ)

    with obs_trace.span("runner.init", ts=_PROC_START) as init_sp:
        with obs_trace.span("rendezvous.wait") as rdv_sp:
            rdv_sp.attrs["processes"] = os.environ.get(
                "KFX_NUM_PROCESSES", "1")
            initialize_distributed()

        import jax

        from ..profiling import maybe_start_profiler_server

        maybe_start_profiler_server()

        from ..data.lm import get_lm_dataset
        from ..models.transformer import attention_path, preset_config
        from ..parallel.lm_train import LMHyperParams, LMTrainLoop
        from ..parallel.mesh import make_mesh
        from ..training import Checkpointer

        rank = jax.process_index()
        world = jax.process_count()

    if args.sp and args.pp > 1:
        print("error: --sp with --pp>1 is not supported "
              "(sequence parallelism composes with tp in the non-pipelined "
              "loop only)", file=sys.stderr)
        return 2
    if args.cp > 1 and (args.pp > 1 or args.sp):
        print("error: --cp composes with dp/tp/fsdp/ep only (sp shards the "
              "same seq dim; pp runs the pipelined loop)", file=sys.stderr)
        return 2
    ds = get_lm_dataset(args.dataset, seed=args.seed,
                        seq_len=args.seq_len or None)
    flash_overrides = {}
    if args.flash_window is not None:
        lo, hi = args.flash_window
        flash_overrides["flash_min_seq"] = lo
        if hi is not None:
            flash_overrides["flash_max_seq"] = hi
    cfg = preset_config(
        args.preset,
        vocab_size=ds.vocab_size,
        max_seq_len=ds.seq_len,
        n_experts=args.experts,
        sp=args.sp,
        cp=args.cp,
        remat=args.remat,
        remat_policy=args.remat_policy,
        attn_impl=args.attn_impl,
        **flash_overrides,
    )
    mesh, plan = make_mesh(tp=args.tp or None, pp=args.pp, cp=args.cp,
                           fsdp=args.fsdp)
    if par_int("data", 0) and plan.dp != par_int("data", 0):
        # The declarative spec promised a data-parallel width the device
        # inventory cannot deliver — fail loudly rather than silently
        # training on a different global batch layout than declared.
        print(f"error: parallelism.data={par['data']} but the mesh "
              f"factorised dp={plan.dp} over {jax.device_count()} "
              f"device(s) (tp={plan.tp}, pp={plan.pp}, cp={plan.cp})",
              file=sys.stderr)
        return 2
    hp = LMHyperParams(learning_rate=args.learning_rate,
                       warmup_steps=args.warmup_steps,
                       total_steps=args.steps, seed=args.seed)
    if plan.pp > 1:
        from ..parallel.pipeline import PipelinedLMTrainLoop

        loop = PipelinedLMTrainLoop(cfg, mesh, plan, hp,
                                    n_microbatches=args.microbatches or None)
    else:
        loop = LMTrainLoop(cfg, mesh, plan, hp)

    n_params = None  # filled after init
    print(f"runner_start model=transformer-{args.preset} "
          f"dataset={args.dataset} rank={rank} world={world} "
          f"devices={jax.device_count()} plan=pp{plan.pp}/dp{plan.dp}/"
          f"tp{plan.tp}{'/fsdp' if plan.fsdp else ''}"
          f"{'/sp' if cfg.sp else ''}"
          f"{f'/cp{plan.cp}' if plan.cp > 1 else ''}"
          f"{f'/ep{cfg.n_experts}' if cfg.n_experts else ''} "
          f"seq_len={ds.seq_len}", flush=True)
    # What this process holds and which paths it took, in the worker's
    # own words: chip_smoke.py (and anyone reading a log) checks these
    # instead of trusting that no fallback happened.
    print(f"device {json.dumps(device_report())}", flush=True)
    print(f"attention path={attention_path(cfg, ds.seq_len)} "
          f"seq_len={ds.seq_len}", flush=True)

    state = loop.init_state()
    leaves = jax.tree.leaves(state.params)
    n_params = sum(x.size for x in leaves)
    print(f"model_params={n_params}", flush=True)
    per_device = {}
    for x in leaves:
        for shard in x.addressable_shards:
            per_device[shard.device.id] = \
                per_device.get(shard.device.id, 0) + shard.data.nbytes
    print("param_bytes " + json.dumps({
        "total": sum(x.nbytes for x in leaves),
        "per_device": {str(k): v for k, v in sorted(per_device.items())},
    }), flush=True)

    ckpt = None
    start_step = 0
    ckpt_dir = os.environ.get("KFX_CHECKPOINT_DIR", "")
    if ckpt_dir and not args.no_checkpoint:
        ckpt = Checkpointer(ckpt_dir, save_every=args.checkpoint_every,
                            keep=args.keep_checkpoints)
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state = restored
            start_step = int(jax.device_get(state.step))
            print(f"resumed_from_checkpoint step={start_step}", flush=True)

    it = ds.batches(args.batch_size, shard_index=rank, num_shards=world)
    for _ in range(start_step):
        next(it)

    t_start = time.time()
    t_last = t_start
    tokens_per_step = args.batch_size * ds.seq_len
    loss = acc = 0.0
    compile_recorded = False
    win_start, win_step0 = t_start, start_step
    last_log_step = start_step
    for step in range(start_step, args.steps):
        if step == args.fail_at_step:
            if ckpt is not None:
                ckpt.wait()
            print(f"fault_injection_crash step={step}", flush=True)
            os._exit(17)
        t_dispatch = time.time()
        state, loss, acc = loop.train_step(state, next(it))
        now = time.time()
        if not compile_recorded:
            # First dispatch pays the XLA compile; the spans that follow
            # measure steady state (same contract as jax_runner).
            obs_trace.record_span("xla.compile", t_dispatch,
                                  now - t_dispatch, start_step=str(step),
                                  model=f"transformer-{args.preset}")
            compile_recorded = True
            win_start, win_step0 = now, step + 1
            t_last = now
            last_log_step = step + 1
            # Not a `step=` metric line: this interval is the compile,
            # and collectors must not read it as a step time.
            print(f"first_step step={step + 1} "
                  f"compile_seconds={now - t_dispatch:.2f} "
                  f"loss={loss:.6f} accuracy={acc:.6f}", flush=True)
            # train.collective: the measured serialized cost of one
            # gradient reduction over the mesh's "data" axis — the
            # bound collective overlap hides. On the waterfall, compare
            # (this x steps) against train.window to read the overlap
            # headroom. Measured on a capped buffer and scaled
            # linearly; skipped on single-chip meshes.
            if plan.dp > 1:
                from ..parallel.overlap import (
                    grad_allreduce_bytes, measure_collective)

                full = grad_allreduce_bytes(state.params, plan)
                probe = min(full, 64 * 1024 * 1024)
                t_coll = time.time()
                measured = measure_collective(mesh, probe)
                est = measured * (full / probe) if probe else 0.0
                obs_trace.record_span(
                    "train.collective", t_coll, measured,
                    axis="data", ways=str(plan.dp),
                    grad_bytes=str(full), probe_bytes=str(probe),
                    est_step_seconds=f"{est:.6f}")
                print(f"collective_allreduce axis=data ways={plan.dp} "
                      f"grad_bytes={full} est_seconds_per_step={est:.6f}",
                      flush=True)
                # Re-stamp: the measurement's wall must not pollute the
                # first steady-state window's step_time.
                t_last = win_start = time.time()
        if ((step + 1) % args.log_every == 0 or step + 1 == args.steps) \
                and step + 1 > last_log_step:
            # step+1 == last_log_step happens when the log boundary IS
            # the compile step: the interval is empty (and on dp>1 it
            # would time measure_collective), so no metric line.
            now = time.time()
            dt = (now - t_last) / (step + 1 - last_log_step)
            tps = tokens_per_step / dt if dt > 0 else 0.0
            print(f"step={step + 1} loss={loss:.6f} accuracy={acc:.6f} "
                  f"step_time={dt:.4f} tokens_per_s={tps:.0f}", flush=True)
            t_last = now
            last_log_step = step + 1
            if step + 1 > win_step0:
                obs_trace.record_span(
                    "train.window", win_start, now - win_start,
                    start_step=str(win_step0), end_step=str(step + 1),
                    tokens_per_s=f"{tps:.0f}")
            win_start, win_step0 = now, step + 1
        if ckpt is not None:
            ckpt.maybe_save(step + 1, state)

    eval_toks = ds.eval_batch(args.batch_size)
    metrics = loop.evaluate(state, eval_toks)
    wall = time.time() - t_start
    print(f"train_done steps={args.steps} wall_seconds={wall:.2f}",
          flush=True)
    print(f"loss={metrics['loss']:.6f}", flush=True)
    print(f"accuracy={metrics['accuracy']:.6f}", flush=True)
    print(f"entropy_floor={ds.entropy_floor():.6f}", flush=True)

    if ckpt is not None:
        ckpt.maybe_save(args.steps, state, force=True)
        ckpt.close()
    if args.export_dir and rank == 0:
        from ..serving.lm_server import export_lm

        export_lm(args.export_dir, cfg, state.params)
        print(f"exported_lm dir={args.export_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
