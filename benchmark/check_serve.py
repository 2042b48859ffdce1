"""The serving cells' comparison with the plain reference, as a child
process that has the chip to itself once the replica is gone.

For each sampled request the reference scores the prompt and the served
continuation in one forward pass (float32, ``highest``); at every
generated position the served token's reference logit is held against
the reference's best. Greedy requests only. With ``--reduce-trace`` the
same process also reduces the run's profiler trace (it may import jax;
the harness may not).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", required=True)
    ap.add_argument("--pad-to", type=int, required=True)
    ap.add_argument("--reduce-trace", default="")
    ap.add_argument("--host-fallback", action="store_true",
                    help="tests on the CPU only: reduce a trace that "
                         "has no device plane from the host's XLA "
                         "threads")
    args = ap.parse_args(argv)

    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()  # the checkout's cache; sets only the env
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference as R
    from benchmark import weights as W
    from benchmark.manifest import load_json

    dev = jax.devices()
    print("device " + json.dumps({"platform": dev[0].platform,
                                  "kind": dev[0].device_kind,
                                  "count": len(dev)}), flush=True)
    cfg = load_json(args.config)
    sample = load_json(args.sample)
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    pool = ThreadPoolExecutor(max_workers=os.cpu_count())
    n_layers = cfg["num_hidden_layers"]

    def make_layer(layer):
        """One layer's leaves (the top's for -1), made on the host in
        the served type and sent to the device."""
        names = W.LAYER_LEAVES if layer >= 0 else W.TOP_LEAVES
        made = pool.map(
            lambda n: W.host_leaf(args.seed, cfg, n, layer, dtype), names)
        return {n: jax.device_put(w) for n, w in zip(names, made)}

    # The reference walks the layers in order: the next one is made
    # while the device works on this one. The top's leaves stay.
    ahead = ThreadPoolExecutor(max_workers=1)
    top = ahead.submit(make_layer, -1)
    queued = {0: ahead.submit(make_layer, 0)}
    held = {}

    def weights(name, layer):
        if layer < 0:
            return top.result()[name]
        if layer not in held:
            held.clear()
            held[layer] = queued.pop(layer).result()
            if layer + 1 < n_layers:
                queued[layer + 1] = ahead.submit(make_layer, layer + 1)
        return held[layer][name]

    t0 = time.monotonic()
    tokens = np.zeros((len(sample), args.pad_to), np.int32)
    rows, cols, served = [], [], []
    for i, s in enumerate(sample):
        full = (s["prompt"] + s["served"])[:args.pad_to]
        tokens[i, :len(full)] = full
        for j, tok in enumerate(s["served"]):
            at = len(s["prompt"]) + j - 1   # the position that predicts it
            if at + 1 < args.pad_to:
                rows.append(i), cols.append(at), served.append(tok)
    hidden = R.hidden_states(weights, cfg, jnp.asarray(tokens))

    @jax.jit
    def gaps(hidden, head, rows, cols, served):
        with jax.default_matmul_precision("highest"):
            logits = hidden[rows, cols] @ head                 # [T, V]
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        return logits.max(-1) - got, logits.std()

    gap, logit_std = gaps(
        hidden, weights("lm_head", -1).astype(jnp.float32),
        np.asarray(rows), np.asarray(cols), np.asarray(served))
    gap = np.asarray(gap)
    out = {"positions": len(served), "gap_max": float(gap.max()),
           "gap_mean": float(gap.mean()),
           "match_share": float((gap == 0).mean()),
           "logit_std": float(logit_std),
           "seconds": time.monotonic() - t0}
    if args.reduce_trace:
        from benchmark import trace_reduce

        tr = trace_reduce.reduce_dir(
            os.path.join(args.reduce_trace, "trace"), args.host_fallback)
        done = load_json(os.path.join(args.reduce_trace, "trace.done"))
        tr["memory_peak_bytes"] = done["memory_stats"].get(
            "peak_bytes_in_use")
        out["trace"] = tr
    print("result " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
