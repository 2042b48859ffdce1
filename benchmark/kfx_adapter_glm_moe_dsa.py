"""Where the published names of ``glm_moe_dsa`` meet kfx's own: the
keywords of kfx's ``TransformerConfig`` for a configuration file, and
kfx's parameter tree (two scanned runs, ``dense_layers`` and
``expert_layers``, each leaf stacked over its run, and the routed
experts' two stacks beside them) as empty arrays with,
for every published leaf, a view of where it lives there. The one dense
block has ``benchmark/kfx_adapter.py``; the manifests and the replica's
environment are that module's, by import.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import weights_glm_moe_dsa as W


def transformer_kwargs(cfg: Dict[str, Any], **settings) -> Dict[str, Any]:
    if cfg.get("model_type") != "glm_moe_dsa" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid" \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_parameters"]["rope_type"] != "default" \
            or cfg["num_nextn_predict_layers"] or cfg["attention_bias"]:
        raise ValueError("not the glm_moe_dsa block kfx computes: sigmoid "
                         "router without group limit, normalised top-k, "
                         "default rope, untied head, no bias, no MTP module")
    dense = cfg["first_k_dense_replace"]
    experts = W.held_experts(cfg)
    kw = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["qk_head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=cfg["rms_norm_eps"],
        rope_base=float(cfg["rope_parameters"]["rope_theta"]),
        layer_pattern=(("dense", dense),
                       ("expert", cfg["num_hidden_layers"] - dense)),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        n_routed_experts=W.router_width(cfg),
        held_experts=(experts.start, len(experts)),
        expert_top_k=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"])
    kw.update(settings)
    return kw


def host_views(cfg: Dict[str, Any], dtype):
    """kfx's tree as empty numpy arrays, and for every published leaf
    (name, layer) a view of where it lives in that tree, in its logical
    [in, out] shape: filling the views fills the tree."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, rq, c = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"])
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held = W.held_experts(cfg)
    e = lambda *shape: np.empty(shape, dtype)
    f32 = lambda *shape: np.empty(shape, np.float32)

    def run(n):
        return {
            "ln1": {"scale": f32(n, d)}, "ln2": {"scale": f32(n, d)},
            "attn": {
                "q_a": {"kernel": e(n, d, rq)},
                "q_norm": {"scale": f32(n, rq)},
                "q_b": {"kernel": e(n, rq, h, nope + rd)},
                "kv_a": {"kernel": e(n, d, c + rd)},
                "kv_norm": {"scale": f32(n, c)},
                "k_up": e(n, c, h, nope), "v_up": e(n, c, h, vd),
                "out": {"kernel": e(n, h, vd, d)},
                "index_q": {"kernel": e(n, rq, hi, di)},
                "index_k": {"kernel": e(n, d, di)},
                "index_k_norm": {"scale": f32(n, di)},
                "index_w": {"kernel": e(n, d, hi)}}}

    n_dense = cfg["first_k_dense_replace"]
    n_expert = cfg["num_hidden_layers"] - n_dense
    dense, expert = run(n_dense), run(n_expert)
    dense["mlp"] = {"wi": {"kernel": e(n_dense, d, 2 * f)},
                    "wo": {"kernel": e(n_dense, f, d)}}
    expert["moe"] = {
        "gate": f32(n_expert, d, W.router_width(cfg)),
        "gate_bias": f32(n_expert, W.router_width(cfg)),
        "shared": {"wi": {"kernel": e(n_expert, d, 2 * fs)},
                   "wo": {"kernel": e(n_expert, fs, d)}}}
    # The held routed experts of every expert layer lie outside the
    # scanned run, one stack a matrix (models/experts.py).
    tree = {"embed": {"embedding": e(v, d)}, "dense_layers": dense,
            "expert_layers": expert,
            "expert_wi": e(n_expert, len(held), d, 2 * fe),
            "expert_wo": e(n_expert, len(held), fe, d), "ln_f": {"scale": f32(d)},
            "lm_head": {"kernel": e(d, v)}}
    views = {("embed_tokens", -1): tree["embed"]["embedding"],
             ("norm", -1): tree["ln_f"]["scale"],
             ("lm_head", -1): tree["lm_head"]["kernel"]}
    for layer in range(cfg["num_hidden_layers"]):
        lay, i = (dense, layer) if layer < n_dense \
            else (expert, layer - n_dense)
        a = lay["attn"]
        views.update({
            ("input_layernorm", layer): lay["ln1"]["scale"][i],
            ("post_attention_layernorm", layer): lay["ln2"]["scale"][i],
            ("q_a_proj", layer): a["q_a"]["kernel"][i],
            ("q_a_layernorm", layer): a["q_norm"]["scale"][i],
            ("q_b_proj", layer): a["q_b"]["kernel"][i].reshape(
                rq, h * (nope + rd)),
            ("kv_a_proj_with_mqa", layer): a["kv_a"]["kernel"][i],
            ("kv_a_layernorm", layer): a["kv_norm"]["scale"][i],
            ("kv_b_proj", layer): (a["k_up"][i], a["v_up"][i]),
            ("o_proj", layer): a["out"]["kernel"][i].reshape(h * vd, d),
            ("indexer.wq_b", layer): a["index_q"]["kernel"][i].reshape(
                rq, hi * di),
            ("indexer.wk", layer): a["index_k"]["kernel"][i],
            ("indexer.k_norm", layer): a["index_k_norm"]["scale"][i],
            ("indexer.weights_proj", layer): a["index_w"]["kernel"][i]})
        if layer < n_dense:
            m = lay["mlp"]
            views.update({
                ("mlp.gate_proj", layer): m["wi"]["kernel"][i][:, :f],
                ("mlp.up_proj", layer): m["wi"]["kernel"][i][:, f:],
                ("mlp.down_proj", layer): m["wo"]["kernel"][i]})
            continue
        m = lay["moe"]
        views.update({
            ("mlp.gate", layer): m["gate"][i],
            ("mlp.gate.bias", layer): m["gate_bias"][i],
            ("mlp.shared_experts.gate_proj", layer):
                m["shared"]["wi"]["kernel"][i][:, :fs],
            ("mlp.shared_experts.up_proj", layer):
                m["shared"]["wi"]["kernel"][i][:, fs:],
            ("mlp.shared_experts.down_proj", layer):
                m["shared"]["wo"]["kernel"][i]})
        for j, ex in enumerate(held):
            views.update({
                (f"mlp.experts.{ex}.gate_proj", layer):
                    tree["expert_wi"][i, j][:, :fe],
                (f"mlp.experts.{ex}.up_proj", layer):
                    tree["expert_wi"][i, j][:, fe:],
                (f"mlp.experts.{ex}.down_proj", layer):
                    tree["expert_wo"][i, j]})
    return tree, views


def fill(seed: int, cfg: Dict[str, Any], name: str, layer: int, view) -> None:
    """Fill one published leaf into its view(s). ``kv_b_proj`` is one
    published matrix [C, H * (nope + v)], a head's W^UK then its W^UV,
    and two leaves of kfx's tree: made whole, then split."""
    if name != "kv_b_proj":
        return W.host_fill(seed, cfg, name, layer, view)
    k_up, v_up = view
    c, h, nope = k_up.shape
    whole = np.empty(W.leaf_shape(cfg, name), k_up.dtype)
    W.host_fill(seed, cfg, name, layer, whole)
    whole = whole.reshape(c, h, -1)
    k_up[...], v_up[...] = whole[..., :nope], whole[..., nope:]
