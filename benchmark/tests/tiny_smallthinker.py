"""A throw-away tiny ``smallthinker`` configuration with every
mechanism of the real one present: two periods of (full, window x 3),
8 / 2 grouped heads, 8 softmax-routed ReGLU experts of which 3 a token,
the router on the layer's input, a window of 16. What the program's
tests and the new kind's rehearsal drive on the CPU in float32."""

from __future__ import annotations

import copy
from typing import Any, Dict

import numpy as np

TINY = {
    "source": "throw-away", "model_name": "smallthinker_tiny",
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 256,
    "moe_ffn_hidden_size": 48, "moe_num_active_primary_experts": 3,
    "moe_num_primary_experts": 8, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 8,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 3,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 3, "sliding_window_size": 16,
    "tie_word_embeddings": False, "vocab_size": 128,
}


def config(**changes) -> Dict[str, Any]:
    cfg = copy.deepcopy(TINY)
    cfg.update(changes)
    return cfg


def program(cfg: Dict[str, Any], seed: int, dtype=np.float32, **settings):
    """(kfx's TransformerConfig, its parameter tree) of ``cfg`` with the
    benchmark's seeded weights, as the export writer makes them."""
    import jax.numpy as jnp

    from benchmark import kfx_adapter_smallthinker as A
    from kubeflow_tpu.models.transformer import TransformerConfig

    tree, views = A.host_views(cfg, dtype)
    for (name, layer), view in views.items():
        A.fill(seed, cfg, name, layer, view)
    kw = A.transformer_kwargs(cfg, dtype=jnp.dtype(dtype),
                              param_dtype=jnp.dtype(dtype))
    kw.update(settings)
    return TransformerConfig(**kw), tree


def reference_logits(cfg: Dict[str, Any], seed: int, tokens, **controls):
    """The reference's logits [S, V] of one sequence, float32."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_smallthinker as R
    from benchmark import weights_smallthinker as W

    weights = lambda n, l: W.host_leaf(seed, cfg, n, l, np.float32)
    hidden = R.forward(cfg, **controls)(weights, jnp.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden @ weights("lm_head", -1))
