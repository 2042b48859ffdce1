"""Write a ``smallthinker`` cell's LM export: as
``benchmark/workers/export_writer.py`` does for the one dense block,
with this block's weights (``benchmark/weights_smallthinker.py``)
filled into kfx's tree of runs (``benchmark/kfx_adapter_smallthinker
.py``) and streamed into the msgpack format with no copy. Runs as a
child with ``JAX_PLATFORMS=cpu`` before the replica exists."""

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
    ap.add_argument("--out", required=True)
    ap.add_argument("--max-seq-len", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import kfx_adapter_smallthinker as A
    from benchmark.manifest import load_json
    from benchmark.workers.export_writer import stream_msgpack
    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.serving.lm_server import PARAMS_FILE, export_lm

    cfg = load_json(args.config)
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    t0 = time.monotonic()
    tree, views = A.host_views(cfg, dtype)
    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        list(pool.map(lambda kv: A.fill(args.seed, cfg, kv[0][0], kv[0][1],
                                        kv[1]), views.items()))
    t1 = time.monotonic()
    tcfg = TransformerConfig(**A.transformer_kwargs(
        cfg, max_seq_len=args.max_seq_len,
        dtype=jnp.dtype(cfg["serving"]["dtype"]), param_dtype=dtype))
    export_lm(args.out, tcfg, {})           # the configuration file
    with open(os.path.join(args.out, PARAMS_FILE), "wb") as f:
        stream_msgpack(tree, f)
    leaves = jax.tree_util.tree_leaves(tree)
    n_bytes = sum(x.nbytes for x in leaves)
    print(f"exported dir={args.out} params={sum(x.size for x in leaves)} "
          f"bytes={n_bytes} make_s={t1 - t0:.1f} "
          f"write_s={time.monotonic() - t1:.1f}", flush=True)
    print("result " + json.dumps({"param_bytes": n_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
