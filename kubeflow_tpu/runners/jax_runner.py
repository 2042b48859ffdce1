"""JAXJob worker entrypoint.

The process the gang launches for every JAXJob replica. Contract with the
operator (SURVEY.md §5.8 — the NCCL-rendezvous replacement):

  * rendezvous: reads KFX_COORDINATOR_ADDRESS / KFX_NUM_PROCESSES /
    KFX_PROCESS_ID and calls ``jax.distributed.initialize`` before any
    backend use; XLA collectives over ICI/DCN do the rest;
  * checkpoint/resume: saves orbax checkpoints under KFX_CHECKPOINT_DIR and
    resumes from the latest on (re)start, so whole-gang restarts lose at
    most ``--checkpoint-every`` steps;
  * metrics: prints ``step=N loss=X accuracy=Y`` lines on stdout, which the
    metrics collector tails (Katib-parity observation pipeline);
  * exit 0 on completion — chief exit drives job success.

Usage (what example manifests put in containers[0].command):
    python -m kubeflow_tpu.runners.jax_runner --model=mlp --dataset=mnist \
        --steps=600 --batch-size=256 --learning-rate=1e-3
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# Wall-clock anchor for the runner.init span: captured at module import
# (before the heavy jax import in main), so the span covers interpreter
# + backend startup the spawn span's end otherwise leaves unaccounted.
_PROC_START = time.time()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="kfx JAX training runner")
    p.add_argument("--model", default="mlp")
    p.add_argument("--dataset", default="mnist")
    p.add_argument("--steps", type=int, default=600)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--learning-rate", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adam")
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=20)
    p.add_argument("--checkpoint-every", type=int, default=200)
    p.add_argument("--keep-checkpoints", type=int, default=2)
    p.add_argument("--eval-samples", type=int, default=2048)
    p.add_argument("--no-checkpoint", action="store_true")
    p.add_argument("--data-pipeline", default="auto",
                   choices=["auto", "device", "host"],
                   help="auto/device: generate synthetic batches ON "
                        "DEVICE inside the training scan (zero input "
                        "transfer); host: classic host feed + prefetch")
    p.add_argument("--scan-steps", type=int, default=1,
                   help="steps fused into one XLA dispatch via lax.scan "
                        "(amortises host↔device round-trips)")
    p.add_argument("--export-dir", default="",
                   help="After training, export params for serving here")
    p.add_argument("--fail-at-step", type=int, default=-1,
                   help="Fault injection: crash at this step (tests only)")
    return p.parse_args(argv)


def parallelism_from_env() -> dict:
    """The declarative JAXJob parallelism spec, operator-injected as the
    ``KFX_PARALLELISM`` JSON env var (api/training.py validates it at
    apply time): ``{"tensor": t, "pipeline": p, "data": d, "context": c,
    "fsdp": bool, "sp": bool, "microbatches": m}`` — every key optional.
    Runners treat it as flag defaults (explicit CLI flags win), so a
    manifest can declare its mesh once instead of duplicating it in
    argv. Returns {} when absent or malformed (a stale env must never
    kill a worker that was told its plan on the command line)."""
    import json

    raw = os.environ.get("KFX_PARALLELISM", "")
    if not raw:
        return {}
    try:
        d = json.loads(raw)
    except ValueError:
        return {}
    return d if isinstance(d, dict) else {}


def initialize_distributed() -> int:
    """Rendezvous via env. Returns process_id. Must run pre-backend-init."""
    from kubeflow_tpu.runtime.rendezvous import apply_startup_chaos

    apply_startup_chaos()
    num = int(os.environ.get("KFX_NUM_PROCESSES", "1"))
    if num <= 1:
        return 0
    import jax

    coord = os.environ["KFX_COORDINATOR_ADDRESS"]
    pid = int(os.environ["KFX_PROCESS_ID"])
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord, num_processes=num,
                               process_id=pid)
    return pid


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache for every process that compiles
    (workers and serving replicas call this before they import jax,
    which reads the variable at import): repeat jobs, restarts and
    replica starts skip the compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX uses it by itself and
    nothing is set here. Otherwise the cache lives at one fixed,
    git-ignored directory inside the checkout — the path is part of the
    cache key, so it is neither under ``$HOME`` nor a temp name — and is
    exported through the environment, so child processes agree.

    Not on the CPU backend: there a cache HIT of the donated-buffer
    train step corrupts the heap (malloc_consolidate aborts / segfaults
    — a fresh compile runs fine, the next process deserializing that
    entry dies), which turned every checkpoint-resume into a crash loop
    under the chaos soak, and CPU compiles are ~1s anyway."""
    if os.environ.get(COMPILE_CACHE_ENV) or \
            os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return
    if "jax" in sys.modules:
        raise RuntimeError(
            "enable_compile_cache() must run before the first jax import")
    os.environ[COMPILE_CACHE_ENV] = compile_cache_dir()


def compile_cache_dir() -> str:
    """Where this checkout's processes keep compiled programs."""
    from kubeflow_tpu.utils.proc import PKG_PARENT

    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        PKG_PARENT, ".kfx_cache", "jax")


def device_report() -> dict:
    """What JAX says this process holds — the triple every entry point
    prints about itself, so that a CPU fallback is read off the log
    instead of assumed away."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    args = parse_args(argv)
    from kubeflow_tpu.obs import trace as obs_trace
    from kubeflow_tpu.runtime.lifetime import install_parent_watch

    install_parent_watch()
    enable_compile_cache()
    # runner.init: interpreter start -> backend ready (rendezvous, jax
    # import, XLA client, model/state init, checkpoint restore — the
    # Checkpointer constructor pays the multi-second orbax import, so
    # it belongs inside, not as a waterfall gap). Backdated to
    # _PROC_START so the timeline shows the real distance between spawn
    # and first step; the context manager emits it status=error when a
    # startup failure unwinds, so a failed attempt's trace still shows
    # where its init died.
    with obs_trace.span("runner.init", ts=_PROC_START) as init_sp:
        with obs_trace.span("rendezvous.wait") as rdv_sp:
            rdv_sp.attrs["processes"] = os.environ.get(
                "KFX_NUM_PROCESSES", "1")
            initialize_distributed()

        import jax  # after distributed init

        # From here on this process's spans are in its profiler traces
        # too (obs/trace.py's bridge; `kfx profile` reads them).
        obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
        from kubeflow_tpu.profiling import maybe_start_profiler_server

        maybe_start_profiler_server()

        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop

        rank = jax.process_index()
        world = jax.process_count()
        is_chief = rank == 0

        def log(msg: str) -> None:
            # All ranks print (per-replica logs); collector reads the
            # chief's.
            print(msg, flush=True)

        # The gang exports the submission's trace ID (obs.trace);
        # echoing it makes this log joinable with `kfx events` on one
        # correlation ID.
        trace_id = os.environ.get("KFX_TRACE_ID", "")
        log(f"runner_start model={args.model} dataset={args.dataset} "
            f"rank={rank} world={world} devices={jax.device_count()} "
            f"platform={jax.devices()[0].platform}"
            + (f" trace={trace_id}" if trace_id else ""))

        dataset = get_dataset(args.dataset, split="train", seed=args.seed)
        model = get_model(args.model, num_classes=dataset.num_classes)
        loop = TrainLoop(model, learning_rate=args.learning_rate,
                         optimizer=args.optimizer,
                         weight_decay=args.weight_decay, seed=args.seed)
        state = loop.init_state(dataset.shape)
        init_sp.attrs.update(model=args.model, rank=str(rank),
                             world=str(world),
                             platform=jax.devices()[0].platform)

        ckpt = None
        start_step = 0
        ckpt_dir = os.environ.get("KFX_CHECKPOINT_DIR", "")
        if ckpt_dir and not args.no_checkpoint:
            ckpt = Checkpointer(ckpt_dir, save_every=args.checkpoint_every,
                                keep=args.keep_checkpoints)
            restored = ckpt.restore_latest(
                state, legacy_layouts=loop.legacy_checkpoint_layouts(state))
            if restored is not None:
                # CLI hyperparams override the checkpointed ones (the
                # checkpoint carries lr in opt_state via
                # inject_hyperparams).
                state = loop.reapply_hyperparams(restored)
                start_step = int(jax.device_get(state.step))
                log(f"resumed_from_checkpoint step={start_step}")

    t_start = time.time()
    t_last = t_start
    last_log_step = start_step
    # auto: on-device generation only where there is a transfer to save
    # (an accelerator backend). On the CPU backend host feeding is free
    # of transfer AND avoids XLA:CPU's very slow compiles of conv models
    # inside the generation scan (resnet18: minutes). --data-pipeline=
    # device forces it anywhere.
    device_capable = (hasattr(dataset, "device_batch_fn")
                      and (args.data_pipeline == "device"
                           or (args.data_pipeline == "auto"
                               and jax.default_backend() != "cpu")))
    if args.data_pipeline == "device" and \
            not hasattr(dataset, "device_batch_fn"):
        print(f"error: --data-pipeline=device but dataset "
              f"{args.dataset!r} has no device batch generator",
              file=sys.stderr)
        return 2
    if not device_capable:
        it = dataset.batches(args.batch_size, shard_index=rank,
                             num_shards=world, steps=None, epoch_seed=0)
        # Skip the batches already consumed before the restart so the
        # data stream continues where the checkpoint left off (device
        # mode needs no skip: keys fold in the absolute step).
        for _ in range(start_step):
            next(it)

    # Chunk size: constant K aligned to log/checkpoint/fault boundaries so
    # fused dispatch never skips a contract point (exactly one compiled
    # chunk shape in steady state). Checkpoint boundaries only bind when
    # checkpointing is actually on.
    k_target = max(1, args.scan_steps)
    ckpt_every = args.checkpoint_every if ckpt is not None else 0

    def _to_boundary(step: int, every: int) -> int:
        return every - step % every if every > 0 else k_target

    loss = acc = 0.0
    step = start_step
    import numpy as np

    if device_capable:
        log("data_pipeline=device (batches generated on device; zero "
            "input transfer per step)")
        batch_fn = dataset.device_batch_fn()

    # Host-side prefetch: the next chunk is generated while the device
    # runs the current one (hides input-pipeline latency behind compute).
    import queue as _queue
    import threading as _threading

    prefetch_q: "_queue.Queue" = _queue.Queue(maxsize=2)

    def _plan_chunks():
        s = start_step
        while s < args.steps:
            k = min(k_target, args.steps - s,
                    _to_boundary(s, args.log_every),
                    _to_boundary(s, ckpt_every))
            if args.fail_at_step > s:
                k = min(k, args.fail_at_step - s)
            yield s, k
            s += k

    def _prefetch():
        # Any failure is pushed through the queue and re-raised by the
        # consumer — a dead prefetch thread must never leave the main
        # loop blocked forever on an empty queue.
        try:
            for s, k in _plan_chunks():
                if k <= 1:
                    prefetch_q.put((s, k, next(it)))
                else:
                    batches = [next(it) for _ in range(k)]
                    prefetch_q.put(
                        (s, k, (np.stack([b[0] for b in batches]),
                                np.stack([b[1] for b in batches]))))
        except BaseException as e:
            prefetch_q.put(e)

    if not device_capable:
        _threading.Thread(target=_prefetch, daemon=True).start()
    chunks = _plan_chunks() if device_capable else None
    # Span bookkeeping: the FIRST dispatch (which pays the XLA compile
    # — also after a checkpoint resume: the jit cache is per-process
    # and the persistent cache is gated off on CPU) becomes an
    # `xla.compile` span; each log window after it becomes a
    # `train.window` span — the waterfall's answer to "where did the
    # steps go" without a span per step.
    compile_recorded = False
    win_start = time.time()
    win_step0 = start_step
    while step < args.steps:
        if step == args.fail_at_step:
            if ckpt is not None:
                # The injected fault models a crash *after* the last scheduled
                # save became durable; without this the async commit races the
                # exit and resume would nondeterministically lose it.
                ckpt.wait()
            log(f"fault_injection_crash step={step}")
            sys.stdout.flush()
            os._exit(17)
        if device_capable:
            s, k = next(chunks)
            assert s == step, f"chunk desync: {s} != {step}"
            t_dispatch = time.time()
            state, loss, acc = loop.train_steps_device(
                state, batch_fn, args.batch_size, s, k)
        else:
            got = prefetch_q.get()
            if isinstance(got, BaseException):
                raise RuntimeError("input prefetch thread failed") from got
            s, k, (images, labels) = got
            assert s == step, f"prefetch desync: {s} != {step}"
            # Timed AFTER the queue get: the first chunk's prefetch wait
            # is input-pipeline latency, and the xla.compile span below
            # must not absorb it.
            t_dispatch = time.time()
            if k <= 1:
                state, loss, acc = loop.train_step(state, images, labels)
            else:
                state, loss, acc = loop.train_steps(state, images, labels)
        step += k
        now = time.time()
        if not compile_recorded:
            obs_trace.record_span("xla.compile", t_dispatch,
                                  now - t_dispatch, start_step=str(s),
                                  steps=str(k), model=args.model)
            compile_recorded = True
            win_start, win_step0 = now, step
        if step % args.log_every == 0 or step == args.steps:
            # Divide by the steps actually elapsed since the last log —
            # the final partial interval (steps not a multiple of
            # log_every) must not report inflated throughput.
            dt = (now - t_last) / max(step - last_log_step, 1)
            # examples_per_sec rides the same stdout metric contract the
            # HPO collector parses; `kfx top` reads it live.
            eps = args.batch_size / dt if dt > 0 else 0.0
            log(f"step={step} loss={loss:.6f} accuracy={acc:.6f} "
                f"step_time={dt:.4f} examples_per_sec={eps:.1f}")
            t_last = now
            last_log_step = step
            if step > win_step0:
                obs_trace.record_span(
                    "train.window", win_start, now - win_start,
                    start_step=str(win_step0), end_step=str(step),
                    examples_per_sec=f"{eps:.1f}")
            win_start, win_step0 = now, step
        if ckpt is not None and ckpt.maybe_save(step, state):
            # Fault point: worker crash at a checkpoint boundary — the
            # deterministic injected-kill (chaos plans schedule it by
            # save ordinal via after/count, so a restart-resume-restart
            # sequence replays exactly). Same durability contract as
            # --fail-at-step: the save must be committed before dying,
            # or resume would nondeterministically lose it.
            from kubeflow_tpu import chaos

            if chaos.draw("runner.crash", target=f"step-{step}") is not None:
                ckpt.wait()
                log(f"chaos_crash step={step}")
                sys.stdout.flush()
                os._exit(137)

    # Final eval on a fixed set (sharded across processes).
    with obs_trace.span("runner.eval", samples=str(args.eval_samples)):
        eval_ds = get_dataset(args.dataset, split="eval", seed=args.seed)
        images, labels = eval_ds.eval_arrays(args.eval_samples)
        shard = slice(rank, None, world)
        metrics = loop.evaluate(state, images[shard], labels[shard])
    wall = time.time() - t_start
    log(f"train_done steps={args.steps} wall_seconds={wall:.2f}")
    log(f"loss={metrics['loss']:.6f}")
    log(f"accuracy={metrics['accuracy']:.6f}")

    if ckpt is not None:
        ckpt.maybe_save(args.steps, state, force=True)
        ckpt.close()

    if args.export_dir and is_chief:
        from kubeflow_tpu.serving.export import export_params

        with obs_trace.span("runner.export", dir=args.export_dir):
            export_params(args.export_dir, args.model, dataset.shape,
                          dataset.num_classes, state)
        log(f"exported_model dir={args.export_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
