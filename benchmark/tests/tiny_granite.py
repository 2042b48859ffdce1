"""A throw-away tiny ``granitemoehybrid`` configuration with every
mechanism of the real one present: two periods of ``mamba, mamba,
attention, mamba`` (runs of 2, 1, 3, 1 and 1 layers), 4 query heads on 2 key/value heads, all four
multipliers other than 1, no position term, a tied head, a Mamba-2
mixer of 4 heads x 8 with a state of 16 and a chunk of 8 tokens (so a
prompt spans chunks). What the program's tests and the new kind's
rehearsal drive on the CPU in float32."""

from __future__ import annotations

import copy
from typing import Any, Dict

import numpy as np

TINY_GRANITE = {
    "source": "throw-away", "model_type": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.2,
    "embedding_multiplier": 3, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 64,
    # wide enough draws that the layers, not the token's own embedding
    # under the tied head, decide the logits at this size
    "initializer_range": 0.15,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "logits_scaling": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "max_position_embeddings": 128,
    "normalization_function": "rmsnorm", "num_attention_heads": 4,
    "num_experts_per_tok": 0, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.5,
    "rms_norm_eps": 1e-05, "shared_intermediate_size": 64,
    "tie_word_embeddings": True, "vocab_size": 128,
    "serving": {"dtype": "float32", "param_dtype": "float32",
                "state_dtype": "float32"},
}


def config(**changes) -> Dict[str, Any]:
    cfg = copy.deepcopy(TINY_GRANITE)
    cfg.update(changes)
    return cfg


def program(cfg: Dict[str, Any], seed: int, dtype=np.float32, **settings):
    """(kfx's TransformerConfig, its parameter tree) of ``cfg`` with the
    benchmark's seeded weights, as the export writer makes them."""
    import jax.numpy as jnp

    from benchmark import kfx_adapter_granitemoehybrid as A
    from kubeflow_tpu.models.transformer import TransformerConfig

    tree, views = A.host_views(cfg, dtype)
    for (name, layer), view in views.items():
        A.fill(seed, cfg, name, layer, view)
    kw = A.transformer_kwargs(cfg, dtype=jnp.dtype(dtype),
                              param_dtype=jnp.dtype(dtype))
    kw.update(settings)
    return TransformerConfig(**kw), tree


def reference_logits(cfg: Dict[str, Any], seed: int, tokens):
    """The reference's logits [S, V] of one sequence, float32."""
    import jax.numpy as jnp

    from benchmark import reference_granitemoehybrid as R
    from benchmark import weights_granitemoehybrid as W

    weights = lambda n, l: W.host_leaf(seed, cfg, n, l, np.float32)
    hidden = R.hidden_states(weights, cfg, jnp.asarray(tokens)[None])
    return np.asarray(R.logits(hidden[0], jnp.asarray(
        weights("embed_tokens", -1)), cfg))
