"""Where the published names of ``smallthinker`` meet kfx's own: the
keywords of kfx's ``TransformerConfig`` for a configuration file, and
kfx's parameter tree (a scan a run of one layer kind, ``full_layers``,
``window_layers``, ``full_layers2``, ..., every leaf stacked over its
run's layers, and the routed experts' two stacks over all layers
beside them) as empty arrays with, for every published leaf, a view of
where it lives there. The manifests and the replica's environment are
``benchmark/kfx_adapter.py``'s, by import.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Tuple

import numpy as np

from . import weights_smallthinker as W


def runs(cfg: Dict[str, Any]) -> List[Tuple[str, int]]:
    """The runs of one kind each that the first ``num_hidden_layers``
    entries of ``sliding_window_layout`` are made of."""
    kinds = ["window" if W.is_window_layer(cfg, i) else "full"
             for i in range(cfg["num_hidden_layers"])]
    return [(k, len(list(g))) for k, g in itertools.groupby(kinds)]


def transformer_kwargs(cfg: Dict[str, Any], **settings) -> Dict[str, Any]:
    n = cfg["num_hidden_layers"]
    if not cfg["model_name"].startswith("smallthinker") \
            or cfg["rope_layout"][:n] != cfg["sliding_window_layout"][:n] \
            or not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] \
            or cfg["tie_word_embeddings"]:
        raise ValueError(
            "not the smallthinker block kfx computes: window layers "
            "that rotate beside full layers with no position term, a "
            "softmax router over the chosen, plain rope, untied head")
    e = cfg["moe_num_primary_experts"]
    kw = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=n, d_ff=cfg["moe_ffn_hidden_size"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=cfg["rms_norm_eps"], rope_base=float(cfg["rope_theta"]),
        layer_pattern=tuple(runs(cfg)), window=cfg["sliding_window_size"],
        n_routed_experts=e, held_experts=(0, e),
        expert_top_k=cfg["moe_num_active_primary_experts"],
        expert_d_ff=cfg["moe_ffn_hidden_size"], router="softmax",
        early_router=True, expert_act="relu")
    kw.update(settings)
    return kw


def host_views(cfg: Dict[str, Any], dtype):
    """kfx's tree as empty numpy arrays, and for every published leaf
    (name, layer) a view of where it lives in that tree, in its logical
    [in, out] shape: filling the views fills the tree."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    tcfg = TransformerConfig(**transformer_kwargs(cfg))
    d, v, f = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["moe_ffn_hidden_size"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n_layers, n_experts = (cfg["num_hidden_layers"],
                           cfg["moe_num_primary_experts"])
    e = lambda *shape: np.empty(shape, dtype)
    f32 = lambda *shape: np.empty(shape, np.float32)

    def run(n):
        return {"ln1": {"scale": f32(n, d)}, "ln2": {"scale": f32(n, d)},
                "attn": {"query": {"kernel": e(n, d, h, hd)},
                         "key": {"kernel": e(n, d, kv, hd)},
                         "value": {"kernel": e(n, d, kv, hd)},
                         "out": {"kernel": e(n, h, hd, d)}},
                "moe": {"gate": f32(n, d, n_experts)}}

    tree = {name: run(n) for name, _, n in tcfg.layer_runs}
    tree.update({"embed": {"embedding": e(v, d)},
                 "expert_wi": e(n_layers, n_experts, d, 2 * f),
                 "expert_wo": e(n_layers, n_experts, f, d),
                 "ln_f": {"scale": f32(d)}, "lm_head": {"kernel": e(d, v)}})
    views = {("embed_tokens", -1): tree["embed"]["embedding"],
             ("norm", -1): tree["ln_f"]["scale"],
             ("lm_head", -1): tree["lm_head"]["kernel"]}
    for layer in range(n_layers):
        at = layer
        for name, _, n in tcfg.layer_runs:
            if at < n:
                break
            at -= n
        lay = tree[name]
        a = lay["attn"]
        views.update({
            ("input_layernorm", layer): lay["ln1"]["scale"][at],
            ("post_attention_layernorm", layer): lay["ln2"]["scale"][at],
            ("self_attn.q_proj", layer):
                a["query"]["kernel"][at].reshape(d, h * hd),
            ("self_attn.k_proj", layer):
                a["key"]["kernel"][at].reshape(d, kv * hd),
            ("self_attn.v_proj", layer):
                a["value"]["kernel"][at].reshape(d, kv * hd),
            ("self_attn.o_proj", layer):
                a["out"]["kernel"][at].reshape(h * hd, d),
            (W.ROUTER, layer): lay["moe"]["gate"][at]})
        # every layer is an expert layer: the stacks go by its number
        for ex in range(n_experts):
            views.update({
                (W.expert_leaf(ex, "gate"), layer):
                    tree["expert_wi"][layer, ex][:, :f],
                (W.expert_leaf(ex, "up"), layer):
                    tree["expert_wi"][layer, ex][:, f:],
                (W.expert_leaf(ex, "down"), layer):
                    tree["expert_wo"][layer, ex]})
    return tree, views


fill = W.host_fill
