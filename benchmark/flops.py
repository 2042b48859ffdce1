"""Operations and bytes a step needs, from the published sizes alone.

The benchmark's own arithmetic (a copy in spirit of
``kubeflow_tpu/utils/flops.py``, which stays the program's and may
drift): matmul FLOPs the model requires, 2*m*n*k a matmul, attention
scored over the whole sequence for the model-FLOPs count (PaLM
appendix B; no causal discount), backward twice the forward, remat's
recomputation not credited. Kernel functions count what the kernel
itself must do (the flash kernels skip the blocks above the diagonal,
so theirs is the causal half).

``cfg`` is a configuration file's dictionary: the published keys
(``hidden_size``, ``num_attention_heads``, ...), not a program object.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of one decoder layer (norm scales left out)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hh = cfg["num_attention_heads"] * head_dim(cfg)
    return 4 * d * hh + 3 * d * f


def matrix_params(cfg: Dict[str, Any]) -> int:
    """Every matrix a forward pass multiplies by, lm_head included and
    the embedding table (a gather) left out."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def fwd_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    hh = cfg["num_attention_heads"] * head_dim(cfg)
    per_layer = 2 * layer_params(cfg) + 2 * 2 * seq_len * hh
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    return 3.0 * fwd_flops_per_token(cfg, seq_len)


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    hh = cfg["num_attention_heads"] * head_dim(cfg)
    return 2 * cfg["num_hidden_layers"] * hh * kv_itemsize


def decode_step_bytes(cfg: Dict[str, Any], cached_tokens: float,
                      weight_itemsize: int = 2,
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: every matrix once, and the K/V
    of the tokens actually cached in the live rows (not of the rows'
    whole capacity, which is what a gather over max_seq_len reads)."""
    return (matrix_params(cfg) * weight_itemsize
            + cached_tokens * kv_bytes_per_token(cfg, kv_itemsize))


# -- the flash-attention kernels (causal; [B, H, S, D] per call) -----------

def flash_kernel_cost(kind: str, batch: int, heads: int, seq: int,
                      dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal flash kernel call. ``kind`` is
    fwd (scores, mix), dq (scores, dp, dq) or dkv (scores, dp, dv, dk):
    2, 3 and 4 matmuls of 2*S*S*D over the causal half. Bytes are the
    tensors each kernel reads and writes once; the f32 rows (lse,
    delta) are counted, the score matrix never leaves the chip."""
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    flops = matmuls * 2.0 * batch * heads * seq * seq * dim / 2.0
    tensor = batch * heads * seq * dim * itemsize
    row = batch * heads * seq * 4
    tensors, rows = {"fwd": (4, 1), "dq": (5, 2), "dkv": (6, 2)}[kind]
    return flops, float(tensors * tensor + rows * row)


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take and which roof sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
