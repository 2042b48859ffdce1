"""Write a cell's LM export: weights the benchmark makes from the seed,
in the type they are served in, in kfx's export format.

Runs as a child with ``JAX_PLATFORMS=cpu`` before the replica exists:
the harness never imports jax, and nothing here touches the chip.

kfx's own ``export_lm`` writes the export's configuration. The
parameter file it would write (``flax.serialization.to_bytes``) copies
every array three times on its way to the file, which at 7.5 GB was
most of a run's set-up; so the parameters are filled in place into
kfx's tree layout (``kfx_adapter.host_views``) and streamed into the
same msgpack wire format with no copy (``stream_msgpack``). kfx's
``load_lm`` reads the result like any export; benchmark/tests checks
that on a tiny one.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import msgpack
import numpy as np


def stream_msgpack(tree, f) -> None:
    """flax's msgpack encoding of a nested dict of numpy arrays, written
    to ``f`` as it goes: a map per dict, and per array ExtType 1 holding
    the msgpack triple (shape, dtype name, raw bytes)."""
    pack = msgpack.Packer(use_bin_type=True)
    if isinstance(tree, dict):
        f.write(pack.pack_map_header(len(tree)))
        for key, value in tree.items():
            f.write(pack.pack(key))
            stream_msgpack(value, f)
        return
    arr = np.ascontiguousarray(tree)
    head = (b"\x93" + pack.pack(tuple(arr.shape)) + pack.pack(arr.dtype.name)
            + b"\xc6" + struct.pack(">I", arr.nbytes))
    if len(head) + arr.nbytes >= 1 << 32:
        raise ValueError("a leaf over 4 GiB needs flax's chunked form")
    f.write(b"\xc9" + struct.pack(">I", len(head) + arr.nbytes) + b"\x01")
    f.write(head)
    f.write(arr.reshape(-1).view(np.uint8).data)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--max-seq-len", type=int, required=True)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from benchmark import kfx_adapter as K
    from benchmark import weights as W
    from benchmark.manifest import load_json
    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.serving.lm_server import PARAMS_FILE, export_lm

    cfg = load_json(args.config)
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    t0 = time.monotonic()
    tree, views = K.host_views(cfg, dtype)
    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        list(pool.map(lambda kv: W.host_fill(
            args.seed, cfg, kv[0][0], kv[0][1], kv[1]), views.items()))
    t1 = time.monotonic()
    tcfg = TransformerConfig(**K.transformer_kwargs(
        cfg, max_seq_len=args.max_seq_len,
        dtype=jnp.dtype(cfg["serving"]["dtype"]), param_dtype=dtype))
    export_lm(args.out, tcfg, {})           # the configuration file
    with open(os.path.join(args.out, PARAMS_FILE), "wb") as f:
        stream_msgpack(tree, f)
    leaves = [v for v in views.values()]
    n_bytes = sum(x.nbytes for x in leaves)
    print(f"exported dir={args.out} params={sum(x.size for x in leaves)} "
          f"bytes={n_bytes} make_s={t1 - t0:.1f} "
          f"write_s={time.monotonic() - t1:.1f}", flush=True)
    print("result " + json.dumps({"param_bytes": n_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
