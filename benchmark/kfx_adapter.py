"""The one place where the benchmark's published names meet kfx's own.

A configuration file speaks the published ``config.json`` keys and
``benchmark.weights`` the published leaf names; kfx has a
``TransformerConfig`` and a scanned flax parameter tree (layers stacked
on a leading axis, q/k/v kernels as [D, H, hd], ``wi`` = gate || up).
This module translates, in both directions, and writes the manifests
(InferenceService, JAXJob) a cell applies to the plane. It imports the
program lazily, inside functions that run in a process that may.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Callable, Dict, List

import numpy as np

from .weights import LAYER_LEAVES


def transformer_kwargs(cfg: Dict[str, Any], **settings) -> Dict[str, Any]:
    """Keywords of kfx's ``TransformerConfig`` for a published config.
    The block kfx computes has eps 1e-6 and RoPE base 10 000 built in:
    a configuration that states anything else cannot run on it."""
    if cfg["rms_norm_eps"] != 1e-6 or cfg.get("rope_theta", 10000.0) != 10000.0:
        raise ValueError("kfx's block fixes rms_norm_eps 1e-6 and rope "
                         "base 10000; this configuration states "
                         f"{cfg['rms_norm_eps']} / {cfg.get('rope_theta')}")
    if cfg.get("num_key_value_heads", cfg["num_attention_heads"]) \
            != cfg["num_attention_heads"] or cfg.get("tie_word_embeddings"):
        raise ValueError("kfx's block has one KV head per query head and "
                         "an untied lm_head")
    kw = dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
              n_heads=cfg["num_attention_heads"],
              head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
              n_layers=cfg["num_hidden_layers"],
              d_ff=cfg["intermediate_size"],
              max_seq_len=cfg["max_position_embeddings"])
    kw.update(settings)
    return kw


# -- published leaves -> kfx's parameter tree --------------------------------

def program_tree(leaf: Callable[[str, int], Any], cfg: Dict[str, Any],
                 stack: Callable[[List[Any]], Any] = np.stack,
                 concat: Callable[[List[Any]], Any] =
                 lambda xs: np.concatenate(xs, axis=-1)) -> Dict[str, Any]:
    """kfx's scanned tree from ``leaf(name, layer)`` (published names,
    [in, out] matrices). ``stack``/``concat`` are numpy's on the host
    and jax.numpy's inside a jitted maker."""
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = d // h
    per = {name: [leaf(name, i) for i in range(n)] for name in LAYER_LEAVES}
    heads_out = lambda ws: stack([w.reshape(d, h, hd) for w in ws])
    return {
        "embed": {"embedding": leaf("embed_tokens", -1)},
        "layers": {
            "ln1": {"scale": stack(per["input_layernorm"])},
            "ln2": {"scale": stack(per["post_attention_layernorm"])},
            "attn": {
                "query": {"kernel": heads_out(per["q_proj"])},
                "key": {"kernel": heads_out(per["k_proj"])},
                "value": {"kernel": heads_out(per["v_proj"])},
                "out": {"kernel": stack([w.reshape(h, hd, d)
                                         for w in per["o_proj"]])},
            },
            "mlp": {
                "wi": {"kernel": stack([concat([g, u]) for g, u in zip(
                    per["gate_proj"], per["up_proj"])])},
                "wo": {"kernel": stack(per["down_proj"])},
            },
        },
        "ln_f": {"scale": leaf("norm", -1)},
        "lm_head": {"kernel": leaf("lm_head", -1)},
    }


def host_views(cfg: Dict[str, Any], dtype):
    """kfx's tree as empty numpy arrays, and for every published leaf a
    view of where it lives in that tree, in its logical [in, out]
    shape: filling the views fills the tree, with no copy to stack."""
    n, d, f = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    h, v = cfg["num_attention_heads"], cfg["vocab_size"]
    hd = d // h
    e = lambda *shape: np.empty(shape, dtype)
    scale = lambda *shape: np.empty(shape, np.float32)
    lay = {
        "ln1": {"scale": scale(n, d)}, "ln2": {"scale": scale(n, d)},
        "attn": {"query": {"kernel": e(n, d, h, hd)},
                 "key": {"kernel": e(n, d, h, hd)},
                 "value": {"kernel": e(n, d, h, hd)},
                 "out": {"kernel": e(n, h, hd, d)}},
        "mlp": {"wi": {"kernel": e(n, d, 2 * f)},
                "wo": {"kernel": e(n, f, d)}},
    }
    tree = {"embed": {"embedding": e(v, d)}, "layers": lay,
            "ln_f": {"scale": scale(d)}, "lm_head": {"kernel": e(d, v)}}
    views = {("embed_tokens", -1): tree["embed"]["embedding"],
             ("norm", -1): tree["ln_f"]["scale"],
             ("lm_head", -1): tree["lm_head"]["kernel"]}
    for i in range(n):
        views.update({
            ("input_layernorm", i): lay["ln1"]["scale"][i],
            ("post_attention_layernorm", i): lay["ln2"]["scale"][i],
            ("q_proj", i): lay["attn"]["query"]["kernel"][i].reshape(d, d),
            ("k_proj", i): lay["attn"]["key"]["kernel"][i].reshape(d, d),
            ("v_proj", i): lay["attn"]["value"]["kernel"][i].reshape(d, d),
            ("o_proj", i): lay["attn"]["out"]["kernel"][i].reshape(d, d),
            ("gate_proj", i): lay["mlp"]["wi"]["kernel"][i][:, :f],
            ("up_proj", i): lay["mlp"]["wi"]["kernel"][i][:, f:],
            ("down_proj", i): lay["mlp"]["wo"]["kernel"][i],
        })
    return tree, views


def published_norms(tree: Dict[str, Any], cfg: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """Per published leaf, the L2 norm of kfx's tree (parameters,
    gradients or Adam moments): {"name": scalar, "name.layer": ...} as
    one dict of jax scalars/vectors, computed where the tree lives."""
    import jax.numpy as jnp

    f = cfg["intermediate_size"]
    sq = lambda x, axes: jnp.sum(jnp.square(x.astype(jnp.float32)), axes)
    rest = lambda x: tuple(range(1, x.ndim))
    lay = tree["layers"]
    wi = lay["mlp"]["wi"]["kernel"]
    stacked = {
        "input_layernorm": sq(lay["ln1"]["scale"], (1,)),
        "post_attention_layernorm": sq(lay["ln2"]["scale"], (1,)),
        "q_proj": sq(lay["attn"]["query"]["kernel"], (1, 2, 3)),
        "k_proj": sq(lay["attn"]["key"]["kernel"], (1, 2, 3)),
        "v_proj": sq(lay["attn"]["value"]["kernel"], (1, 2, 3)),
        "o_proj": sq(lay["attn"]["out"]["kernel"], (1, 2, 3)),
        "gate_proj": sq(wi[..., :f], rest(wi)),
        "up_proj": sq(wi[..., f:], rest(wi)),
        "down_proj": sq(lay["mlp"]["wo"]["kernel"], (1, 2)),
    }
    out = {"embed_tokens": sq(tree["embed"]["embedding"], None),
           "norm": sq(tree["ln_f"]["scale"], None),
           "lm_head": sq(tree["lm_head"]["kernel"], None)}
    out.update(stacked)
    return {k: jnp.sqrt(v) for k, v in out.items()}


def flatten_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    """{"name": x, "name.layer": x} of plain floats from published_norms
    (after device_get)."""
    out: Dict[str, float] = {}
    for k, v in norms.items():
        v = np.asarray(v)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            out.update({f"{k}.{i}": float(x) for i, x in enumerate(v)})
    return out


# -- manifests ---------------------------------------------------------------

ISVC = """
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata: {{name: {name}, namespace: default}}
spec:
  predictor: {predictor}
"""

JAXJOB = """
apiVersion: kubeflow.org/v1
kind: JAXJob
metadata: {{name: {name}, namespace: default}}
spec:
  runPolicy: {{backoffLimit: 0}}
  parallelism: {parallelism}
  jaxReplicaSpecs:
    Worker:
      replicas: 1
      restartPolicy: Never
      template:
        spec:
          containers:
          - name: jax
            command: {argv}
"""


def inference_service(name: str, export_dir: str, serving: Dict[str, Any],
                      traced_argv: List[str] = ()) -> str:
    """The InferenceService of a serving cell: one replica, the stock
    predictor on the export, or (a traced run) the benchmark's wrapper
    as a custom container. ``serving`` is the configuration file's
    group: slots, speculative, quantization."""
    pred: Dict[str, Any] = {"minReplicas": 1, "maxReplicas": 1}
    if traced_argv:
        pred["containers"] = [{"name": "traced",
                               "command": list(traced_argv)}]
    else:
        pred["jax"] = {"storageUri": f"file://{export_dir}"}
        # maxLatencyMs 0 keeps the classifier micro-batcher off; the
        # operator passes maxBatchSize on as the engine's slot count.
        pred["batcher"] = {"maxBatchSize": int(serving["slots"]),
                           "maxLatencyMs": 0}
        for key in ("speculative", "quantization"):
            if serving.get(key):
                pred[key] = serving[key]
    return ISVC.format(name=name, predictor=json.dumps(pred))


def jaxjob(name: str, parallelism: Dict[str, Any], argv: List[str]) -> str:
    return JAXJOB.format(name=name, parallelism=json.dumps(parallelism),
                         argv=json.dumps([sys.executable] + list(argv)))


def replica_env(serving: Dict[str, Any]) -> Dict[str, str]:
    """The LMPredictor's environment knobs for engine settings the
    InferenceService spec has no field for (replicas inherit the
    plane's environment), and, for the traced wrapper, those the
    operator would have derived from the spec."""
    env = {"KFX_LM_ENGINE_CHUNK": str(serving["decode_chunk"]),
           "KFX_LM_KV_PAGE_SIZE": str(serving["kv_page_size"]),
           "KFX_LM_PREFILL_CHUNK": str(serving["prefill_chunk"])}
    if serving.get("kv_pages"):
        env["KFX_LM_KV_PAGES"] = str(serving["kv_pages"])
    if serving.get("prefix_cache") is False:
        env["KFX_LM_PREFIX_CACHE"] = "0"
    return env


def spec_env(serving: Dict[str, Any]) -> Dict[str, str]:
    """What ``operators/serving._spec_env/_quant_env`` derive from the
    spec, for the custom-container path that skips them."""
    env = {}
    if (serving.get("speculative") or {}).get("enabled") is False:
        env["KFX_LM_SPEC"] = "0"
    q = serving.get("quantization") or {}
    if q.get("weights") == "int8":
        env["KFX_LM_QUANT"] = "int8"
    if q.get("kv") == "int8":
        env["KFX_LM_KV_QUANT"] = "int8"
    return env
