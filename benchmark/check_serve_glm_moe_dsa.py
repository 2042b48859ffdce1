"""A ``glm_moe_dsa`` serving cell's comparison with its plain reference
(``benchmark/reference_glm_moe_dsa.py``), as a child process that has
the chip to itself once the replica is gone.

The measure is ``benchmark/check_serve.py``'s: for each sampled request
the reference scores the prompt and the served continuation in one
forward pass (float32, ``highest``), and at every generated position
the served token's reference logit is held against the reference's
best. Greedy requests only. What differs is the split. A top-k is
discontinuous: the program's bfloat16 activations move indexer scores
by parts in a thousand, near the ``index_topk``-th score positions swap
against the reference's choice, and each swap replaces a whole term of
the attention sum. So the gaps are reported twice: ``full`` over the
positions that predict from no more than ``index_topk`` cached tokens
(the selection is everything there, and the limit is a dense model's),
``sparse`` over the rest.

A request goes alone, padded on the right to the next of a few lengths
(a causal model's earlier positions do not see the padding), so that
one compiled layer serves several. ``--recent`` is a control: the
reference reads the ``index_topk`` most recent positions in the place
of the learned selection. With ``--reduce-trace`` the same process also
reduces the run's profiler trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor


def padded_length(n: int, longest: int, block: int) -> int:
    """The next power of two (from one block), or, past the last one
    under ``longest``, ``longest`` in whole blocks."""
    top = -(-longest // block) * block
    b = block
    while b < n:
        b *= 2
    return min(b, top)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", required=True)
    ap.add_argument("--recent", action="store_true")
    ap.add_argument("--reduce-trace", default="")
    ap.add_argument("--host-fallback", action="store_true")
    args = ap.parse_args(argv)

    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()  # the checkout's cache; sets only the env
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_glm_moe_dsa as R
    from benchmark import weights_glm_moe_dsa as W
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
        names = W.layer_leaves(cfg, layer)
        made = pool.map(
            lambda n: W.host_leaf(args.seed, cfg, n, layer, dtype), names)
        return {n: jax.device_put(w) for n, w in zip(names, made)}

    # Every request walks the layers in order; a layer is made once and
    # every request goes through it before the next is made (the
    # weights are the large thing, the hidden states the small one).
    t0 = time.monotonic()
    block = min(R.QUERY_BLOCK, cfg["index_topk"])
    longest = max(len(s["prompt"]) + len(s["served"]) for s in sample)
    hidden, rows = [], []
    top = make_layer(-1)
    embed = jax.jit(lambda e, t: e.astype(jnp.float32)[t])
    for s in sample:
        full = s["prompt"] + s["served"]
        tokens = np.zeros(padded_length(len(full), longest, block), np.int32)
        tokens[:len(full)] = full
        hidden.append(embed(top["embed_tokens"], jnp.asarray(tokens)))
    step = R.layer_step(cfg, args.recent)
    ahead = ThreadPoolExecutor(max_workers=1)
    nxt = ahead.submit(make_layer, 0)
    for layer in range(n_layers):
        weights = nxt.result()
        if layer + 1 < n_layers:
            nxt = ahead.submit(make_layer, layer + 1)
        hidden = [step(layer, weights, x) for x in hidden]
        jax.block_until_ready(hidden)
        del weights

    @jax.jit
    def gaps(x, norm, head, cols, served):
        with jax.default_matmul_precision("highest"):
            x = R.rms_norm(x[cols], norm.astype(jnp.float32),
                           cfg["rms_norm_eps"])
            logits = x @ head.astype(jnp.float32)              # [T, V]
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        return logits.max(-1) - got, jnp.square(logits).mean()

    gap, cached, square = [], [], []
    for s, x in zip(sample, hidden):
        n = len(s["served"])
        cols = len(s["prompt"]) - 1 + np.arange(n)  # the predicting rows
        # pad the rows asked for to whole blocks: one compile a length
        pad = -(-n // 64) * 64
        g, sq = gaps(x, top["norm"], top["lm_head"],
                     np.pad(cols, (0, pad - n), mode="edge"),
                     np.pad(np.asarray(s["served"]), (0, pad - n),
                            mode="edge"))
        gap += list(np.asarray(g)[:n])
        cached += list(cols + 1)
        square.append(float(sq))
    gap, cached = np.asarray(gap), np.asarray(cached)
    out = {"positions": int(gap.size),
           "match_share": float((gap == 0).mean()),
           "logit_std": float(np.sqrt(np.mean(square))),
           "seconds": time.monotonic() - t0}
    for part, pick in (("full", cached <= cfg["index_topk"]),
                       ("sparse", cached > cfg["index_topk"])):
        # A part with no position reads 0.0 here; how many positions a
        # part needs is the cell's to say (full_positions_min).
        out[part] = {"positions": int(pick.sum()),
                     "gap_max": float(gap[pick].max()) if pick.any() else 0.0,
                     "gap_mean": float(gap[pick].mean()) if pick.any()
                     else 0.0}
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
