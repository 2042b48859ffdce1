"""The training cells' worker: a JAXJob's ``command``.

``lm_runner`` reads only kfx's home-made presets, so the benchmark
brings its own worker, which a JAXJob may name like any user's. It
builds kfx's ``TransformerConfig`` from the configuration file, kfx's
mesh and ``LMTrainLoop``; makes the sharded state on the devices from
the seed in one jitted call (weights by ``benchmark.weights``, so the
reference can make them again); drives the loop's own compiled step
through the checked steps
(two: the reference follows them, and each costs it a whole batch); then steps for ``--seconds`` with the
input pipeline running, one synced step at a time, and writes what it
measured to ``<out>/worker.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_PROC_START = time.time()


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0,
                    help="steps of the window to trace (0: none)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--param-dtype", default="",
                    help="override the configuration's parameter type "
                         "(the lower-precision control)")
    ap.add_argument("--host-fallback", action="store_true",
                    help="tests on the CPU only: see trace_reduce")
    ap.add_argument("--break-step", action="store_true",
                    help="test only: a step that returns its state "
                         "unchanged")
    return ap.parse_args(argv)


def build(cfg, mix, training, mesh_kw, param_dtype=""):
    """(tcfg, loop, make_state) for a configuration file and a mix."""
    import jax
    import jax.numpy as jnp

    from benchmark import kfx_adapter as K
    from benchmark import weights as W
    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.parallel.lm_train import (
        LMHyperParams, LMTrainLoop, LMTrainState)
    from kubeflow_tpu.parallel.mesh import make_mesh

    pdtype = jnp.dtype(param_dtype or training["param_dtype"])
    tcfg = TransformerConfig(**K.transformer_kwargs(
        cfg, max_seq_len=mix["sequence_tokens"],
        dtype=jnp.dtype(training["dtype"]), param_dtype=pdtype,
        remat=training["remat"], remat_policy=training["remat_policy"],
        loss_chunk=training["loss_chunk"]))
    mesh, plan = make_mesh(**mesh_kw)
    hp = LMHyperParams(learning_rate=training["learning_rate"],
                       warmup_steps=training["warmup_steps"],
                       total_steps=training["total_steps"],
                       weight_decay=training["weight_decay"],
                       grad_clip=training["grad_clip"])
    loop = LMTrainLoop(tcfg, mesh, plan, hp)

    def params_from(key):
        return K.program_tree(
            lambda n, l: W.device_leaf(key, cfg, n, l, pdtype), cfg,
            stack=jnp.stack, concat=lambda xs: jnp.concatenate(xs, -1))

    def make_state(key):
        params = params_from(key)
        return LMTrainState(step=jnp.zeros((), jnp.int32), params=params,
                            opt_state=loop.tx.init(params))

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with jax.set_mesh(mesh):
        want = jax.eval_shape(loop._init_fn, key)
        got = jax.eval_shape(make_state, key)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got))):
        raise RuntimeError("kfx's parameter tree is no longer the one "
                           "benchmark/kfx_adapter.py builds")
    return tcfg, loop, make_state, params_from


def adam_moments(opt_state):
    """The (mu, nu) trees inside an optax chain's state."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state.mu, opt_state.nu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = adam_moments(part)
            if found is not None:
                return found
    return None


def main(argv=None) -> int:
    args = parse(argv)
    from kubeflow_tpu.runners.jax_runner import (
        device_report, enable_compile_cache, initialize_distributed,
        parallelism_from_env)
    from kubeflow_tpu.runtime.lifetime import install_parent_watch

    install_parent_watch()
    enable_compile_cache()
    initialize_distributed()
    import jax
    import jax.numpy as jnp

    from benchmark import kfx_adapter as K
    from benchmark import traffic, weights as W
    from benchmark.manifest import load_json
    from kubeflow_tpu.models.transformer import attention_path

    cfg, mix = load_json(args.config), load_json(args.traffic)
    training = cfg["training"]
    par = parallelism_from_env()
    mesh_kw = {"tp": int(par.get("tensor", 0) or 0) or None,
               "fsdp": bool(par.get("fsdp", False))}
    tcfg, loop, make_state, params_from = build(
        cfg, mix, training, mesh_kw, args.param_dtype)
    plan = loop.plan
    print(f"device {json.dumps(device_report())}", flush=True)
    print(f"worker_start plan=dp{plan.dp}/tp{plan.tp}"
          f"{'/fsdp' if plan.fsdp else ''} devices={jax.device_count()} "
          f"attention={attention_path(tcfg, mix['sequence_tokens'])} "
          f"param_dtype={jnp.dtype(tcfg.param_dtype).name} "
          f"reach_device_s={time.time() - _PROC_START:.1f}", flush=True)

    key = W.device_key(args.seed)
    t = time.monotonic()
    with jax.set_mesh(loop.mesh):
        state = jax.jit(make_state,
                        out_shardings=loop.state_shardings())(key)
        jax.block_until_ready(state)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"state_made s={time.monotonic() - t:.1f} params={n_params}",
          flush=True)

    batches = traffic.markov_batches(mix, cfg["vocab_size"], args.seed)
    tokens_per_step = mix["global_batch_sequences"] * mix["sequence_tokens"]

    def step(state):
        if args.break_step:
            _, loss, _ = loop.train_step(
                jax.tree_util.tree_map(jnp.copy, state), next(batches))
            return state, loss
        state, loss, _ = loop.train_step(state, next(batches))
        return state, loss

    # The checked steps, through the window's own call and feed.
    norms = jax.jit(lambda tree: K.published_norms(tree, cfg))
    t = time.monotonic()
    check = {"losses": []}
    for i in range(training["checked_steps"]):
        state, loss = step(state)
        check["losses"].append(loss)
        if i == 0:
            mu, _ = adam_moments(state.opt_state)
            check["first_grad_norms"] = {
                k: v / (1.0 - training["beta1"]) for k, v in K.flatten_norms(
                    jax.device_get(norms(mu))).items()}
            print(f"first_step compile_and_step_s="
                  f"{time.monotonic() - t:.1f} loss={loss:.6f}", flush=True)
    with jax.set_mesh(loop.mesh):
        delta = jax.jit(lambda p, k: K.published_norms(
            jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, params_from(k)), cfg))(state.params, key)
    check["param_change_norms"] = K.flatten_norms(jax.device_get(delta))
    print("checked_steps losses="
          + " ".join(f"{x:.6f}" for x in check["losses"]), flush=True)

    # The measured window.
    trace_dir = os.path.join(args.out, "trace")
    trace_steps = range(2, 2 + args.trace)
    durations, losses = [], []
    window_wall = time.time()
    print(f"window_open wall={window_wall:.3f}", flush=True)
    t0 = time.monotonic()
    while time.monotonic() - t0 < args.seconds:
        i = len(durations)
        if trace_steps and i == trace_steps[0]:
            jax.profiler.start_trace(trace_dir)
        ts = time.monotonic()
        state, loss = step(state)
        durations.append(time.monotonic() - ts)
        losses.append(loss)
        if trace_steps and i == trace_steps[-1]:
            jax.profiler.stop_trace()
        print(f"step={i + 1} loss={loss:.6f} step_time={durations[-1]:.4f}",
              flush=True)
    elapsed = time.monotonic() - t0
    print(f"window_closed steps={len(durations)} elapsed={elapsed:.3f}",
          flush=True)

    out = {"window_wall": window_wall, "elapsed_s": elapsed,
           "step_s": durations, "losses": losses, "check": check,
           "tokens_per_step": tokens_per_step, "n_params": n_params,
           "device": device_report(),
           "memory_peak_bytes": max(
               (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()),
           "plan": {"dp": plan.dp, "tp": plan.tp, "fsdp": plan.fsdp}}
    if args.trace:
        from benchmark import trace_reduce

        tr = trace_reduce.reduce_dir(trace_dir, args.host_fallback)
        tr["traced_steps"] = len(trace_steps)
        out["trace"] = tr
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "worker.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
