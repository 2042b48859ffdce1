"""The training cells' plain reference, as a child process that has the
chips to itself once the worker is gone.

From the seed alone: the same weights (``benchmark.weights``), the same
batches (``benchmark.traffic``), then the configuration's ``checked_steps`` optimizer steps of
``benchmark.reference`` in float32 ``highest`` over the cell's whole
batch, rows in blocks, parameters and moments sharded over the chips
only so that they fit. Prints each step's loss, the per-leaf norms of
the first gradient as AdamW gets it (after clipping) and of the
parameters' change after the steps.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    started = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import reference as R
    from benchmark import traffic, weights as W
    from benchmark.manifest import load_json

    devs = jax.devices()
    print("device " + json.dumps({"platform": devs[0].platform,
                                  "kind": devs[0].device_kind,
                                  "count": len(devs)}), flush=True)
    mark = lambda what: print(
        f"reference_phase {what} s={time.monotonic() - started:.1f}",
        flush=True)
    mark("device_reached")
    cfg, mix = load_json(args.config), load_json(args.traffic)
    hp = cfg["training"]
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    rows = NamedSharding(mesh, P("x", None))
    whole = NamedSharding(mesh, P())
    shard_of = lambda shape: rows if len(shape) == 2 and shape[0] % n == 0 \
        else whole
    key = W.device_key(args.seed)

    makers = {name: jax.jit(
        lambda k, layer, name=name: W.device_leaf(
            k, cfg, name, layer, jnp.float32).astype(jnp.float32),
        out_shardings=shard_of(W.leaf_shape(cfg, name)))
        for name in W.TOP_LEAVES + W.LAYER_LEAVES}

    def make(name, layer):
        return makers[name](key, np.int32(layer))

    def make_params():
        p = {name: make(name, -1) for name in W.TOP_LEAVES}
        p["layers"] = [{name: make(name, i) for name in W.LAYER_LEAVES}
                       for i in range(cfg["num_hidden_layers"])]
        return p

    params = make_params()
    mark("parameters_made")
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    # Adam's moments do not fit the chips beside the parameters, the
    # accumulated gradient and the backward pass's temporaries (AOT for
    # a described v5e, PR 24: 4.6 + 9.0 GiB a chip without them). The
    # clipped gradients of earlier steps wait on the host instead, and
    # the moments are made again from them, leaf by leaf, at an update.
    past = []

    with jax.default_matmul_precision("highest"):
        def add_into(acc, p, tokens):
            loss, g = jax.value_and_grad(R.loss_fn)(p, tokens, cfg,
                                                    hp["loss_chunk"])
            return jax.tree_util.tree_map(jnp.add, acc, g), loss
        add_into = jax.jit(add_into, donate_argnums=0)

    hp_key = tuple(sorted((k, hp[k]) for k in
                          ("beta1", "beta2", "eps", "weight_decay")))
    update = jax.jit(
        lambda p, g, m, v, t, lr: R.adamw_update(p, g, m, v, t, lr,
                                                 dict(hp_key)),
        donate_argnums=(0, 2, 3))
    moments = jax.jit(lambda m, v, g: (
        hp["beta1"] * m + (1.0 - hp["beta1"]) * g,
        hp["beta2"] * v + (1.0 - hp["beta2"]) * jnp.square(g)),
        donate_argnums=(0, 1))
    clip = jax.jit(lambda g: R.clip_by_global_norm(g, hp["grad_clip"]),
                   donate_argnums=0)

    batches = traffic.markov_batches(mix, cfg["vocab_size"], args.seed)
    block = n * max(1, hp["reference_rows_per_chip"])
    out = {"losses": []}
    steps = hp["checked_steps"]
    for t in range(1, steps + 1):
        tokens = next(batches)
        B = tokens.shape[0]
        acc, losses = zeros(), []
        for s in range(0, B, block):
            blk = jax.device_put(tokens[s:s + block], rows)
            acc, loss = add_into(acc, params, blk)
            losses.append(loss)
        n_blocks = len(losses)
        grads = clip(jax.tree_util.tree_map(lambda g: g / n_blocks, acc))
        out["losses"].append(float(sum(float(x) for x in losses)
                                   / n_blocks))
        if t == 1:
            out["first_grad_norms"] = R.leaf_norms(grads)
        mark(f"step_{t}_gradients")
        lr = R.learning_rate(t - 1, hp["learning_rate"], hp["warmup_steps"],
                             max(hp["total_steps"], hp["warmup_steps"] + 1))
        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = tree.flatten_up_to(grads)
        new_p = []
        for i, (p, g) in enumerate(zip(flat_p, flat_g)):
            mm, vv = jnp.zeros_like(p), jnp.zeros_like(p)
            for earlier in past:
                mm, vv = moments(mm, vv, jax.device_put(earlier[i],
                                                        p.sharding))
            new_p.append(update(p, g, mm, vv, float(t), lr)[0])
        if t < steps:
            past.append(jax.device_get(flat_g))   # every leaf at once
        params = tree.unflatten(new_p)
        del flat_g, flat_p, grads, acc
        print(f"reference_step {t} loss={out['losses'][-1]:.6f} "
              f"lr={lr:.3e}", flush=True)
        mark(f"step_{t}_updated")
    out["param_change_norms"] = {}
    for k, now in R.flat_leaves(params).items():
        name, _, layer = k.partition(".")
        fresh = make(name, int(layer) if layer else -1)
        out["param_change_norms"][k] = float(
            jnp.sqrt(jnp.sum(jnp.square(now - fresh))))
    out["seconds"] = time.monotonic() - started
    print("result " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
