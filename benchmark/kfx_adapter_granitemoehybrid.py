"""Where the published names of ``granitemoehybrid`` meet kfx's own: the
keywords of kfx's ``TransformerConfig`` for a configuration file, and
kfx's parameter tree (a scan a run of ``layer_types``, every leaf
stacked over its run's layers) as empty arrays with, for every published leaf, a view of where it lives
there. The manifests and the replica's environment are
``benchmark/kfx_adapter.py``'s, by import.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Tuple

import numpy as np

from . import weights_granitemoehybrid as W


def runs(cfg: Dict[str, Any]) -> List[Tuple[str, int]]:
    """The runs of one kind each that ``layer_types`` is made of."""
    return [(k, len(list(g))) for k, g in
            itertools.groupby(cfg["layer_types"])]


def transformer_kwargs(cfg: Dict[str, Any], **settings) -> Dict[str, Any]:
    if cfg.get("model_type") != "granitemoehybrid" \
            or cfg["num_local_experts"] or cfg["attention_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["hidden_act"] != "silu" \
            or cfg["normalization_function"] != "rmsnorm" \
            or len(cfg["layer_types"]) != cfg["num_hidden_layers"] \
            or set(cfg["layer_types"]) - {"mamba", "attention"}:
        raise ValueError(
            "not the granitemoehybrid block kfx computes: Mamba-2 and "
            "grouped attention layers without position term or bias "
            "(but the convolution's), RMSNorm, a shared SwiGLU, no "
            "routed experts")
    s = W.sizes(cfg)
    kw = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=s["head_dim"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["shared_intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=cfg["rms_norm_eps"], rope=False,
        tie_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        layer_pattern=tuple(runs(cfg)),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        ssm_state_dtype=cfg["serving"]["state_dtype"])
    if not kw["tie_embeddings"]:
        raise ValueError("this adapter maps a tied head only")
    kw.update(settings)
    return kw


def host_views(cfg: Dict[str, Any], dtype):
    """kfx's tree as empty numpy arrays, and for every published leaf
    (name, layer) a view of where it lives in that tree, in its logical
    [in, out] shape: filling the views fills the tree."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    tcfg = TransformerConfig(**transformer_kwargs(cfg))
    d, v, f = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["shared_intermediate_size"])
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = W.sizes(cfg)
    hd, heads, taps = s["head_dim"], cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    e = lambda *shape: np.empty(shape, dtype)
    f32 = lambda *shape: np.empty(shape, np.float32)

    def run(kind, n):
        out = {"ln1": {"scale": f32(n, d)}, "ln2": {"scale": f32(n, d)},
               "mlp": {"wi": {"kernel": e(n, d, 2 * f)},
                       "wo": {"kernel": e(n, f, d)}}}
        if kind == "mamba":
            out["ssm"] = {
                "in_proj": {"kernel": e(n, d, s["in_proj"] - heads)},
                "dt_proj": {"kernel": e(n, d, heads)},
                "conv_kernel": e(n, taps, s["conv"]),
                "conv_bias": e(n, s["conv"]),
                "dt_bias": f32(n, heads), "A_log": f32(n, heads),
                "D": f32(n, heads), "norm_scale": f32(n, s["inner"]),
                "out_proj": {"kernel": e(n, s["inner"], d)}}
        else:
            out["attn"] = {"query": {"kernel": e(n, d, h, hd)},
                           "key": {"kernel": e(n, d, kv, hd)},
                           "value": {"kernel": e(n, d, kv, hd)},
                           "out": {"kernel": e(n, h, hd, d)}}
        return out

    tree = {name: run(kind, n) for name, kind, n in tcfg.layer_runs}
    tree.update({"embed": {"embedding": e(v, d)},
                 "ln_f": {"scale": f32(d)}})
    views = {("embed_tokens", -1): tree["embed"]["embedding"],
             ("norm", -1): tree["ln_f"]["scale"]}
    for layer in range(cfg["num_hidden_layers"]):
        at = layer
        for name, kind, n in tcfg.layer_runs:
            if at < n:
                break
            at -= n
        lay = tree[name]
        views.update({
            ("input_layernorm", layer): lay["ln1"]["scale"][at],
            ("post_attention_layernorm", layer): lay["ln2"]["scale"][at],
            ("shared_mlp.input_linear", layer):
                lay["mlp"]["wi"]["kernel"][at],
            ("shared_mlp.output_linear", layer):
                lay["mlp"]["wo"]["kernel"][at]})
        if kind == "mamba":
            m = lay["ssm"]
            views.update({
                # one published matrix [z | xBC | dt], two kernels
                ("mamba.in_proj", layer): (m["in_proj"]["kernel"][at],
                                           m["dt_proj"]["kernel"][at]),
                # kfx holds the taps as [taps, channels]
                ("mamba.conv1d.weight", layer): m["conv_kernel"][at].T,
                ("mamba.conv1d.bias", layer): m["conv_bias"][at],
                ("mamba.dt_bias", layer): m["dt_bias"][at],
                ("mamba.A_log", layer): m["A_log"][at],
                ("mamba.D", layer): m["D"][at],
                ("mamba.norm", layer): m["norm_scale"][at],
                ("mamba.out_proj", layer): m["out_proj"]["kernel"][at]})
        else:
            a = lay["attn"]
            views.update({
                ("self_attn.q_proj", layer):
                    a["query"]["kernel"][at].reshape(d, h * hd),
                ("self_attn.k_proj", layer):
                    a["key"]["kernel"][at].reshape(d, kv * hd),
                ("self_attn.v_proj", layer):
                    a["value"]["kernel"][at].reshape(d, kv * hd),
                ("self_attn.o_proj", layer):
                    a["out"]["kernel"][at].reshape(h * hd, d)})
    return tree, views


def fill(seed: int, cfg: Dict[str, Any], name: str, layer: int, view) -> None:
    """Fill one published leaf into its view(s). ``mamba.in_proj`` is
    one published matrix and two kernels of kfx's tree ([z | xBC] and
    dt: models/ssm.py): made whole, then split."""
    if name != "mamba.in_proj":
        return W.host_fill(seed, cfg, name, layer, view)
    zx, dt = view
    whole = np.empty(W.leaf_shape(cfg, name), zx.dtype)
    W.host_fill(seed, cfg, name, layer, whole)
    zx[...], dt[...] = whole[:, :zx.shape[1]], whole[:, zx.shape[1]:]
