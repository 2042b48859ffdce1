"""A ``smallthinker`` serving cell's comparison with its plain reference
(``benchmark/reference_smallthinker.py``), as a child process that has
the chip to itself once the replica is gone.

The measure is ``benchmark/check_serve.py``'s: for each sampled request
the reference scores the prompt and the served continuation in one
forward pass (float32, ``highest``), and at every generated position
the served token's reference logit is held against the reference's
best. Greedy requests only. The gaps are reported twice: ``short`` over
the positions whose whole context lies within one window (no page of
the row has been given back, and a window layer reads what a full one
would), ``long`` over the positions beyond it (the window layers read
the last ``sliding_window_size`` positions from a table that slides).

``--kv-probe`` names what the run read back of one more request while
its row was decoding (``serve_mixedctx_cell.kv_probe``): the keys and
values its pages held in some layers. The reference computes the same
layers' keys and values of the probe's prompt and served tokens, and
a position's distance is ||got - want|| / ||want|| over its key, and
over its value; ``kv`` reports, a page class, the median over the
positions the pages held: the cache's own precision, which the served
tokens show only where it flips the largest logit.

A request goes alone, padded on the right to one of two lengths (a
causal model's earlier positions do not see the padding), so that one
compiled layer serves several; a layer's weights are made once and
every request goes through it before the next is made (a layer is 1.6
GB in float32). ``--full-window`` and ``--late-router`` are controls:
the reference's window layers see every earlier position; its router
reads the FFN's normed input. With ``--reduce-trace`` the same process
also reduces the run's profiler trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor


def padded_length(n: int, longest: int, block: int) -> int:
    """Two blocks, or ``longest`` in whole blocks: two lengths, two
    compiled layers."""
    top = -(-longest // block) * block
    return 2 * block if n <= 2 * block < top else top


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", required=True)
    ap.add_argument("--kv-probe", default="")
    ap.add_argument("--full-window", action="store_true")
    ap.add_argument("--late-router", action="store_true")
    ap.add_argument("--reduce-trace", default="")
    ap.add_argument("--host-fallback", action="store_true")
    args = ap.parse_args(argv)

    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()  # the checkout's cache; sets only the env
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_smallthinker as R
    from benchmark import weights_smallthinker as W
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

    t0 = time.monotonic()
    block = min(R.QUERY_BLOCK, cfg["sliding_window_size"])
    longest = max(len(s["prompt"]) + len(s["served"]) for s in sample)
    hidden = []
    top = make_layer(-1)
    embed = jax.jit(lambda e, t: e.astype(jnp.float32)[t])
    for s in sample:
        full = s["prompt"] + s["served"]
        tokens = np.zeros(padded_length(len(full), longest, block), np.int32)
        tokens[:len(full)] = full
        hidden.append(embed(top["embed_tokens"], jnp.asarray(tokens)))
    step = R.layer_step(cfg, full_window=args.full_window,
                        late_router=args.late_router)
    # The probe's prompt goes through the layers up to the last one read
    # back, at a sampled request's padded length where it fits one.
    probe = load_json(args.kv_probe) if args.kv_probe else {"layers": {}}
    probed = {int(l): np.load(f) for l, f in probe["layers"].items()}
    kv_of, kv = R.layer_kv(cfg), {}
    if probed:
        full = probe["prompt"] + probe["served"]
        n = len(full)
        tokens = np.zeros(padded_length(n, max(n, longest), block), np.int32)
        tokens[:n] = full
        probe_x = embed(top["embed_tokens"], jnp.asarray(tokens))
    ahead = ThreadPoolExecutor(max_workers=1)
    nxt = ahead.submit(make_layer, 0)
    for layer in range(n_layers):
        weights = nxt.result()
        if layer + 1 < n_layers:
            nxt = ahead.submit(make_layer, layer + 1)
        if layer in probed:
            got = probed[layer]
            held = got["positions"] < n       # (the last served is not in)
            at = got["positions"][held]
            gaps_here = [
                np.linalg.norm(got[name][held] - want[at], axis=-1)
                / np.linalg.norm(want[at], axis=-1)
                for name, want in zip(("key", "value"), map(
                    np.asarray, kv_of(layer, weights, probe_x)))]
            cls = "window" if W.is_window_layer(cfg, layer) else "full"
            # (no position held reads 0.0, as a part with none below:
            # how many it needs is the cell's to say)
            kv[cls] = {"layer": layer, "positions": int(held.sum()),
                       "gap_median": float(np.median(gaps_here))
                       if held.any() else 0.0,
                       "gap_max": float(np.max(gaps_here))
                       if held.any() else 0.0}
        if layer < max(probed, default=-1):
            probe_x = step(layer, weights, probe_x)
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

    gap, held, square = [], [], []
    # (every request's rows padded to the longest answer's: one compile)
    pad = -(-max(len(s["served"]) for s in sample) // 64) * 64
    for s, x in zip(sample, hidden):
        n = len(s["served"])
        cols = len(s["prompt"]) - 1 + np.arange(n)  # the predicting rows
        g, sq = gaps(x, top["norm"], top["lm_head"],
                     np.pad(cols, (0, pad - n), mode="edge"),
                     np.pad(np.asarray(s["served"]), (0, pad - n),
                            mode="edge"))
        gap += list(np.asarray(g)[:n])
        held += list(cols + 1)
        square.append(float(sq))
    gap, held = np.asarray(gap), np.asarray(held)
    out = {"positions": int(gap.size), "kv": kv,
           "match_share": float((gap == 0).mean()),
           "logit_std": float(np.sqrt(np.mean(square))),
           "seconds": time.monotonic() - t0}
    for part, pick in (("short", held <= cfg["sliding_window_size"]),
                       ("long", held > cfg["sliding_window_size"])):
        # A part with no position reads 0.0 here; how many positions a
        # part needs is the cell's to say.
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
        # where the traced seconds lie, and what the replica counted
        # in them (benchmark/workers/traced_replica_scraped.py)
        tr.update({k: done.get(k)
                   for k in ("t_start", "t_stop", "counters")})
        out["trace"] = tr
    print("result " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
