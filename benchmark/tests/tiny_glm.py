"""A throw-away tiny ``glm_moe_dsa`` configuration with every mechanism
of the real one present: one leading dense layer and two expert layers,
32 routed experts of which this share holds 8 (from the 8th), top-4, a
shared expert, latent 32 + rotary 8, an indexer that selects 16
positions. What the program's tests and the new kind's rehearsal drive
on the CPU in float32."""

from __future__ import annotations

import copy
from typing import Any, Dict

import numpy as np

TINY_GLM = {
    "source": "throw-away", "model_type": "glm_moe_dsa",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 128, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "qk_head_dim": 32, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 16, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False, "attention_bias": False,
    "num_nextn_predict_layers": 0, "max_position_embeddings": 128,
    "reduced": {"n_routed_experts": {"published": 32, "here": 8}},
    "share": {"first_expert": 8},
}


def config(**changes) -> Dict[str, Any]:
    cfg = copy.deepcopy(TINY_GLM)
    cfg.update(changes)
    return cfg


def program(cfg: Dict[str, Any], seed: int, dtype=np.float32, **settings):
    """(kfx's TransformerConfig, its parameter tree) of ``cfg`` with the
    benchmark's seeded weights, as the export writer makes them."""
    import jax.numpy as jnp

    from benchmark import kfx_adapter_glm_moe_dsa as A
    from kubeflow_tpu.models.transformer import TransformerConfig

    tree, views = A.host_views(cfg, dtype)
    for (name, layer), view in views.items():
        A.fill(seed, cfg, name, layer, view)
    kw = A.transformer_kwargs(cfg, dtype=jnp.dtype(dtype),
                              param_dtype=jnp.dtype(dtype))
    kw.update(settings)
    return TransformerConfig(**kw), tree


def reference_logits(cfg: Dict[str, Any], seed: int, tokens, **kw):
    """The reference's logits [S, V] of one sequence, float32."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_glm_moe_dsa as R
    from benchmark import weights_glm_moe_dsa as W

    weights = lambda n, l: W.host_leaf(seed, cfg, n, l, np.float32)
    hidden = R.forward(cfg, **kw)(weights, jnp.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden @ weights("lm_head", -1))
