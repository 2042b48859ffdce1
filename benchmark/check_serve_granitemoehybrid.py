"""A ``granitemoehybrid`` serving cell's comparison with its plain
reference (``benchmark/reference_granitemoehybrid.py``), as a child
process that has the chip to itself once the replica is gone.

The measure is ``benchmark/check_serve.py``'s: for each sampled request
the reference scores the prompt and the served continuation in one
forward pass (float32, ``highest``; the recurrence a scan over the
tokens); at every generated position the served token's reference
logit is held against the reference's best. Greedy requests only. The
requests go as one batch, padded on the right to the longest in whole
``--pad-to`` tokens (a causal model's earlier positions do not see the
padding). A second measure sees what the first cannot, the precision
the recurrent state is held in (``state_gaps``): a token flips only
where two logits all but tie, but the state a request left in its slot
is the program's own numbers. With ``--reduce-trace`` the same process
also reduces the run's profiler trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor


def state_gaps(sample, states):
    """How far the state a request left in its slot (``state_file``,
    the program's own leaf, every served token fed) lies from the
    reference's after the same tokens: for every probed request, Mamba
    layer and head the distance of the two [P, N] states over the
    reference's norm. ``state_gap_max`` is the widest, and
    ``state_gap_mean`` the mean, of them all."""
    import numpy as np

    gaps = []
    for row, s in enumerate(sample):
        if not s.get("state_file"):
            continue
        got = np.load(s["state_file"])["state"].astype(np.float32)
        want = np.stack([np.asarray(layer[row]) for layer in states])
        gaps.append(np.sqrt(np.square(got - want).sum((2, 3))
                            / np.square(want).sum((2, 3))))   # [L, H]
    if not gaps:
        return {"state_probes": 0, "state_gap_max": float("inf"),
                "state_gap_mean": float("inf"), "state_gap_by_layer": [],
                "state_gap_by_request": []}
    gaps = np.stack(gaps)
    brief = lambda a: [float(f"{g:.3g}") for g in a]
    return {"state_probes": len(gaps), "state_gap_max": float(gaps.max()),
            "state_gap_mean": float(gaps.mean()),
            "state_gap_by_layer": brief(gaps.max((0, 2))),
            "state_gap_by_request": brief(gaps.mean((1, 2)))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", required=True)
    ap.add_argument("--pad-to", type=int, required=True)
    ap.add_argument("--reduce-trace", default="")
    ap.add_argument("--host-fallback", action="store_true")
    args = ap.parse_args(argv)

    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()  # the checkout's cache; sets only the env
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_granitemoehybrid as R
    from benchmark import weights_granitemoehybrid as W
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
        names = W.layer_leaves(cfg, layer)
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
    longest = max(len(s["prompt"]) + len(s["served"]) for s in sample)
    width = -(-longest // args.pad_to) * args.pad_to
    tokens = np.zeros((len(sample), width), np.int32)
    rows, cols, served = [], [], []
    for i, s in enumerate(sample):
        full = s["prompt"] + s["served"]
        tokens[i, :len(full)] = full
        for j, tok in enumerate(s["served"]):
            rows.append(i)
            cols.append(len(s["prompt"]) + j - 1)  # what predicts it
            served.append(tok)
    hidden, states = R.hidden_and_states(
        weights, cfg, jnp.asarray(tokens),
        [len(s["prompt"]) + len(s["served"]) for s in sample])

    @jax.jit
    def gaps(hidden, embedding, rows, cols, served):
        logits = R.logits(hidden[rows, cols], embedding, cfg)  # [T, V]
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        return logits.max(-1) - got, logits.std()

    gap, logit_std = gaps(hidden, weights("embed_tokens", -1),
                          np.asarray(rows), np.asarray(cols),
                          np.asarray(served))
    gap = np.asarray(gap)
    out = {"positions": len(served), "gap_max": float(gap.max()),
           "gap_mean": float(gap.mean()),
           "match_share": float((gap == 0).mean()),
           "match_by_request": [
               round(float((gap[np.asarray(rows) == i] == 0).mean()), 4)
               for i in range(len(sample))],
           "logit_std": float(logit_std)}
    out.update(state_gaps(sample, states))
    out["seconds"] = time.monotonic() - t0
    if args.reduce_trace:
        from benchmark import trace_reduce

        tr = trace_reduce.reduce_dir(
            os.path.join(args.reduce_trace, "trace"), args.host_fallback)
        done = load_json(os.path.join(args.reduce_trace, "trace.done"))
        tr["memory_peak_bytes"] = done["memory_stats"].get(
            "peak_bytes_in_use")
        # where the traced seconds lie, and what the replica counted
        # in them (benchmark/workers/traced_replica_scraped.py)
        tr.update({k: done.get(k)
                   for k in ("t_start", "t_stop", "counters")})
        out["trace"] = tr
    print("result " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
