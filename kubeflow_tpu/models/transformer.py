"""Decoder-only transformer LM — the TPU-native flagship model family.

Design targets the MXU and GSPMD, not any reference implementation (the
reference has no model code at all — SURVEY.md §2.3):

  * all FLOPs live in einsums with static shapes; bf16 compute, f32 params;
  * heads/mlp dims annotated with logical axes so `parallel.mesh` rules
    shard them Megatron-style over the "model" axis (tp) and the embedding
    dim over "data" (fsdp);
  * optional mixture-of-experts FFN with dense one-hot dispatch (a matmul,
    so routing also rides the MXU) and experts sharded over "data" (ep);
  * `nn.scan` over a stacked layer body → one compiled block regardless of
    depth (compile time stays flat as layers grow);
  * `nn.remat` option for activation rematerialisation (HBM ↔ FLOPs);
  * RoPE positions, pre-LN, SwiGLU.

Logical axes used: vocab, embed, heads, kv, mlp, expert, expert_mlp,
layers. `param_logical_axes()` derives them from param paths so the train
loop can build NamedShardings without flax partitioning metadata plumbing.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_heads: int = 8
    head_dim: int = 64
    n_layers: int = 8
    d_ff: int = 2048
    max_seq_len: int = 2048
    # MoE: 0 = dense FFN; >0 = that many experts in every layer.
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    # "capacity": GShard-style fixed expert buffers [E, B, C, D] with
    # cumsum slotting and token dropping beyond capacity (O(E·C) expert
    # FLOPs — scales to large E). "dense": every expert sees every token,
    # masked (exact, O(E·tokens) FLOPs — only sane for tiny E).
    moe_dispatch: str = "capacity"
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # With remat on: what the checkpoint may KEEP instead of recompute.
    # "nothing" = classic full remat (lowest HBM, ~full fwd recompute in
    # bwd); "dots" = jax.checkpoint_policies.dots_saveable keeps matmul
    # outputs (incl. the S^2 scores — only fits smaller B*S); measure
    # per shape. Ignored when remat=False.
    remat_policy: str = "nothing"
    # Megatron-style sequence parallelism: between matmul regions the
    # residual stream is sharded over the "model" axis on the seq dim
    # (annotation only — XLA inserts the all-gather/reduce-scatter pairs).
    sp: bool = False
    # Context parallelism: >1 shards the sequence dim over the "ctx" mesh
    # axis for the whole layer stack, with exact causal ring attention
    # (parallel/ring_attention.py) rotating K/V chunks between ctx
    # neighbours. Mutually exclusive with sp (both shard the seq dim).
    cp: int = 1
    # Attention implementation: "auto" uses the pallas flash kernel
    # (ops/flash_attention.py) on TPU when shapes qualify, else the XLA
    # dense path; "flash"/"naive" force one ("xla" is the legacy
    # spelling of "naive" — the dense O(S^2) reference path, kept as
    # the numerics oracle); "ring" asserts the sequence axis is sharded
    # (requires cp>1). cp>1 always rides ring attention regardless (it
    # is the only seq-sharded kernel), so "ring" is documentation +
    # validation that the config really is context-parallel.
    attn_impl: str = "auto"
    # The seq-len window where "auto" picks flash. The defaults are a
    # MEASUREMENT, not a law: on this environment's v5e (base preset,
    # b16, matched save policies) dense wins at S=512 (0.415 vs 0.362 —
    # kernel-launch overhead dominates the small S^2 block) and flash
    # wins from S=1024 (0.351 vs 0.338; 0.336 vs 0.309 at S=2048) —
    # the round-5 save_flash remat composition moved the crossover
    # down from 2048, because only flash can skip its forward re-run
    # in the backward. Above 4096 this environment's compiler rejects
    # scan+remat+kernel. On other hardware re-measure and set these
    # (or force attn_impl="flash"); flash_max_seq=0 means no upper
    # bound.
    flash_min_seq: int = 1024
    flash_max_seq: int = 4096
    # Sequence-chunked cross-entropy: >0 makes the train loop apply
    # lm_head + softmax per chunk of this many tokens, in one lax.scan
    # that makes the gradients beside the loss (a hand-written rule:
    # parallel/lm_train.py ``_chunked_ce``), so the [B, S, vocab] f32
    # logits never materialise whole — at base/b8/S=2048 that transient
    # is ~3G of the 15.75G HBM, exactly the headroom the save_flash
    # remat policy needs — and each chunk's logits are computed once,
    # not again in the backward. 0 = whole-sequence logits.
    loss_chunk: int = 0
    # Autoregressive decoding: every attention layer keeps a KV cache
    # ("cache" collection) of max_seq_len slots and calls attend the new
    # tokens against it. Position ids must be passed explicitly (pads are
    # -1 and masked out of the cache). Built via models.generate.
    decode: bool = False
    # Paged decode cache (vLLM-style): kv_page_size > 0 replaces the
    # dense per-row [B, max_seq_len] KV layout with one global pool of
    # ``kv_pages`` fixed-size pages shared by every request slot; the
    # caller passes per-row block tables mapping logical block index ->
    # physical page (-1 = unallocated). Cache shapes become batch-
    # INDEPENDENT (no per-row cursor — the write location IS the token's
    # position id), which is what lets prefill (B=1) and decode
    # (B=n_slots) share one pool. 0 = dense legacy layout (the one-shot
    # oracle path). Requires kv_page_size | max_seq_len so the gathered
    # view is exactly [B, max_seq_len] and stays bit-identical to dense.
    kv_page_size: int = 0
    kv_pages: int = 0
    # Weight quantization: "int8" switches the attention/MLP/lm_head
    # projections to per-output-channel symmetric int8 kernels with f32
    # scales (QuantDenseGeneral below; params produced by
    # ``quantize_params_int8``). The matmul consumes the int8 kernel
    # directly and the scale is applied to the OUTPUT — mathematically
    # identical to dequantizing the kernel for symmetric per-channel
    # scales, and the weights stream from HBM as int8. Embeddings,
    # norms and MoE experts stay in param_dtype. "" = unquantized (the
    # f32 oracle path, byte-identical to pre-quantization builds).
    quant: str = ""
    # KV-cache quantization (paged layout only): "int8" stores the
    # paged pool's K/V entries as int8 with one f32 scale per cached
    # token per pool (scale planes [kv_pages, page_size] beside the
    # pool) — quantize-on-write in the scatter, dequant-on-gather.
    # Halves (vs bf16; 4x vs f32) the pool's HBM per token, so the
    # same byte budget admits ~2x the concurrent requests. Independent
    # of ``quant``. Requires kv_page_size > 0 (the dense one-shot
    # oracle stays full-precision).
    kv_quant: str = ""
    # LoRA fine-tuning (Hu et al., 2021): rank > 0 adds trainable
    # low-rank ``<proj>_lora_a`` / ``<proj>_lora_b`` factor params on
    # the attention q/k/v/out and dense-MLP wi/wo projections —
    # ``y = base(x) + (x @ A) @ B * (alpha / rank)`` with B
    # zero-initialised, so a fresh fine-tune starts byte-identical to
    # the base model and only the factors need training (the base
    # stays frozen; training/lora.py owns that loop). Train-time knob
    # only: SERVING many adapters over one base goes through the
    # batched-gather ``lora``/``adapter_ids`` call arguments below
    # (serving/adapters.py stacks), never through these params.
    # Dense FFN only (MoE experts are not LoRA targets).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # What every norm adds under the root, and the rotary base.
    norm_eps: float = 1e-6
    rope_base: float = 10_000.0
    # Layer pattern: () = ``n_layers`` blocks of one kind under one scan
    # named "layers" (``n_experts`` decides its FFN). Otherwise runs of
    # one kind each, in order, e.g. (("dense", 1), ("expert", 5)): a
    # scan a run, named "<kind>_layers", params and cache stacked per
    # run. "dense" is the SwiGLU FFN of width ``d_ff``; "expert" the
    # routed experts below (models/experts.py).
    layer_pattern: Tuple[Tuple[str, int], ...] = ()
    # Latent attention (MLA; models/latent.py), on when kv_lora_rank >
    # 0: queries through a ``q_lora_rank`` bottleneck, per head
    # ``qk_nope_head_dim`` + ``qk_rope_head_dim`` (the rotary part) and
    # values of ``v_head_dim``; the cache holds kv_lora_rank latent +
    # qk_rope_head_dim rotary numbers a token a layer, no heads.
    # ``n_heads`` counts the heads; ``head_dim`` is not read.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Learned sparse attention (DSA), on when index_topk > 0: an
    # indexer of ``index_n_heads`` x ``index_head_dim`` scores every
    # cached token (its key is a second cache leaf) and the main
    # attention reads the index_topk best of a row, all of them while
    # there are no more.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Routed experts of an "expert" layer: a float32 sigmoid router
    # over ``n_routed_experts`` with a bias that enters the choice
    # only, ``expert_top_k`` chosen, their scores normalised and scaled
    # by ``routed_scaling_factor``; experts of width ``expert_d_ff``,
    # ``n_shared_experts`` of them always on. ``held_experts`` (first,
    # count) is this program's share of an expert-parallel layer: it
    # holds and computes those, routes over all, and what the others
    # would add is not in its result.
    n_routed_experts: int = 0
    held_experts: Tuple[int, int] = (0, 0)
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    # Grouped key/value heads: ``n_kv_heads`` of them (0 = n_heads),
    # each shared by n_heads / n_kv_heads query heads; the cache holds
    # n_kv_heads x head_dim a token a layer.
    n_kv_heads: int = 0
    # The four multipliers of a scaled block (each 1.0, and 0.0 for
    # the attention's, is the program without them): the embedding's
    # rows times ``embedding_multiplier``; scores ``q . k`` times
    # ``attention_multiplier`` (0 = 1 / sqrt(head_dim)); every mixer's
    # and FFN's result times ``residual_multiplier`` before it joins
    # the residual; logits divided by ``logits_scaling``.
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # False: no rotary (or any) position term in attention.
    rope: bool = True
    # True: the head is the embedding, transposed; no ``lm_head``.
    tie_embeddings: bool = False
    # "mamba" layers (models/ssm.py; "attention" is their companion
    # kind: the attention above with the SwiGLU FFN; runs of these two
    # kinds may recur in ``layer_pattern``, (("mamba", 5), ("attention",
    # 1), ("mamba", 9), ...): a kind's later runs are scans named
    # "<kind>_layers2", "<kind>_layers3", ...): ``ssm_heads`` x
    # ``ssm_head_dim`` inputs, a state of ``ssm_state`` numbers each,
    # ``ssm_groups`` B/C groups, a causal convolution of ``ssm_conv``
    # taps, the chunked form at ``ssm_chunk`` tokens. A row's state is
    # held in ``ssm_state_dtype``, in leaves indexed by slot:
    # ``state_slots`` of them beside a paged pool (the serving engine
    # sets it, as it sets ``kv_pages``); the dense layout has the
    # batch's rows.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_state_dtype: Any = jnp.float32
    state_slots: int = 0
    # "full" and "window" layers: runs of these two kinds may recur in
    # ``layer_pattern`` like "mamba" / "attention". A "full" layer's
    # attention sees every earlier position and has no position term;
    # a "window" layer's rotates q and k (``rope_base``) and sees the
    # last ``window`` positions, the query's own among them. Either
    # takes the FFN the configuration states: the routed experts where
    # ``n_routed_experts`` > 0, else the SwiGLU of width ``d_ff``.
    # Paged, the window runs' leaves are a pool of their own,
    # ``window_pages`` pages indexed by position (the serving engine
    # sets it, as it sets ``kv_pages``, and frees a row's pages behind
    # its window).
    window: int = 0
    window_pages: int = 0
    # The router's form: "sigmoid" as above; "softmax": float32 logits
    # with no bias, the ``expert_top_k`` largest chosen by logit, their
    # weights the softmax over the chosen. ``early_router``: the router
    # reads the residual stream as it enters the layer, before ``ln1``
    # and attention, not the FFN's normalised input. ``expert_act``
    # gates an expert: "silu" or "relu".
    router: str = "sigmoid"
    early_router: bool = False
    expert_act: str = "silu"

    def __post_init__(self):
        # A configuration read back from JSON brings lists.
        object.__setattr__(self, "layer_pattern", tuple(
            (str(k), int(n)) for k, n in self.layer_pattern))
        object.__setattr__(self, "held_experts",
                           tuple(int(n) for n in self.held_experts))
        object.__setattr__(self, "ssm_state_dtype",
                           jnp.dtype(self.ssm_state_dtype))
        kinds = [k for k, _ in self.layer_pattern]
        if self.layer_pattern:
            once = [k for k in kinds if k in ("dense", "expert")]
            if (set(kinds) - {"dense", "expert", "mamba", "attention",
                              "full", "window"}
                    or len(set(once)) != len(once)
                    or any(a == b for a, b in zip(kinds, kinds[1:]))
                    or any(n < 1 for _, n in self.layer_pattern)
                    or sum(n for _, n in self.layer_pattern)
                    != self.n_layers):
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: runs of "
                    "'dense' / 'expert' (each kind once) or 'mamba' / "
                    "'attention' / 'full' / 'window' (a kind may recur, "
                    "never twice in a row), counts >= 1 adding up to "
                    f"n_layers {self.n_layers}")
            if "mamba" in kinds:
                if min(self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                       self.ssm_groups, self.ssm_chunk) < 1 \
                        or self.ssm_conv < 2 \
                        or self.ssm_heads % self.ssm_groups:
                    raise ValueError(
                        "a 'mamba' layer needs ssm_heads (a multiple of "
                        "ssm_groups), ssm_head_dim, ssm_state, ssm_chunk "
                        "and ssm_conv >= 2")
                if self.lora_rank or self.quant or self.kv_lora_rank \
                        or "expert" in kinds:
                    raise ValueError(
                        "'mamba' layers have no LoRA targets and no int8 "
                        "weights, and stand beside plain attention and "
                        "dense FFNs only (lora_rank, quant, kv_lora_rank "
                        "unset; no 'expert' run)")
            if {"full", "window"} & set(kinds):
                if "window" in kinds and self.window < 1:
                    raise ValueError(
                        "a 'window' layer needs window >= 1 (the "
                        "positions a query sees, its own among them)")
                if self.lora_rank or self.quant or self.kv_lora_rank \
                        or self.cp > 1 \
                        or set(kinds) - {"full", "window"}:
                    raise ValueError(
                        "'full' / 'window' layers stand beside each "
                        "other alone, with plain attention (lora_rank, "
                        "quant, kv_lora_rank, cp unset)")
        if self.expert_layers:
            first, count = self.held_experts
            if (self.n_routed_experts < 1 or self.expert_d_ff < 1
                    or count < 1 or first < 0
                    or first + count > self.n_routed_experts
                    or not 1 <= self.expert_top_k
                    <= self.n_routed_experts):
                raise ValueError(
                    "a layer with routed experts needs n_routed_experts, "
                    "expert_d_ff, expert_top_k and held_experts "
                    "(first, count) inside the routed range; got "
                    f"{self.n_routed_experts}, {self.expert_d_ff}, "
                    f"{self.expert_top_k}, {self.held_experts}")
            if self.router not in ("sigmoid", "softmax") \
                    or self.expert_act not in ("silu", "relu"):
                raise ValueError(
                    f"router {self.router!r} is 'sigmoid' or 'softmax', "
                    f"expert_act {self.expert_act!r} 'silu' or 'relu'")
        if self.tie_embeddings and (self.quant or self.loss_chunk):
            raise ValueError(
                "tie_embeddings has no lm_head for int8 weights or the "
                "chunked loss to read")
        if self.n_kv_heads < 0 or (
                self.n_kv_heads and self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} must divide n_heads "
                f"{self.n_heads} (0 = one a query head)")
        if self.kv_heads != self.n_heads and (
                self.kv_lora_rank or self.lora_rank or self.quant
                or self.cp > 1):
            raise ValueError(
                "grouped key/value heads (n_kv_heads < n_heads) are "
                "served by the plain attention alone: not with latent "
                "attention, LoRA, int8 weights or ring attention")
        if self.kv_lora_rank > 0:
            if min(self.q_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) < 1 \
                    or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs q_lora_rank, "
                    "qk_nope_head_dim, v_head_dim and an even "
                    "qk_rope_head_dim")
            if self.lora_rank or self.quant:
                raise ValueError(
                    "latent attention has no LoRA targets and no int8 "
                    "weights (lora_rank / quant must be unset; "
                    "kv_quant is served)")
        if self.index_topk > 0 and (
                self.kv_lora_rank < 1 or self.index_n_heads < 1
                or self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError(
                "index_topk selects for latent attention: it needs "
                "kv_lora_rank, index_n_heads and an index_head_dim of "
                "at least qk_rope_head_dim")
        if self.attn_impl not in ("auto", "flash", "xla", "naive", "ring"):
            raise ValueError(
                f"unknown attn_impl {self.attn_impl!r} (expected 'auto', "
                "'flash', 'naive'/'xla' or 'ring')")
        if self.attn_impl == "ring" and self.cp <= 1:
            raise ValueError(
                "attn_impl='ring' needs the sequence axis sharded: set "
                "cp>1 (ring attention rotates K/V over the 'ctx' mesh "
                "axis; with cp=1 there is no ring)")
        if min(self.kv_page_size, self.kv_pages, self.window_pages) < 0:
            raise ValueError(
                "kv_page_size / kv_pages / window_pages must be >= 0")
        if self.kv_page_size > 0:
            if self.max_seq_len % self.kv_page_size:
                raise ValueError(
                    f"kv_page_size {self.kv_page_size} must divide "
                    f"max_seq_len {self.max_seq_len} (the gathered view "
                    "must tile exactly)")
            if self.kv_pages < 1:
                raise ValueError(
                    "kv_pages must be >= 1 when kv_page_size > 0")
            if "window" in kinds and self.window_pages < 1:
                raise ValueError(
                    "'window' layers are cached in a pool of their own: "
                    "window_pages must be >= 1 when kv_page_size > 0")
        if self.quant not in ("", "int8"):
            raise ValueError(
                f"unknown quant {self.quant!r} (expected '' or 'int8')")
        if self.kv_quant not in ("", "int8"):
            raise ValueError(
                f"unknown kv_quant {self.kv_quant!r} "
                "(expected '' or 'int8')")
        if self.kv_quant and self.kv_page_size == 0:
            raise ValueError(
                "kv_quant requires the paged cache (kv_page_size > 0): "
                "the dense one-shot layout is the full-precision oracle")
        if self.lora_rank < 0:
            raise ValueError("lora_rank must be >= 0 (0 = no LoRA)")
        if self.lora_rank > 0 and self.n_experts > 0:
            raise ValueError(
                "lora_rank targets the dense FFN (mlp.wi/wo); MoE "
                "expert weights are not LoRA targets — fine-tune a "
                "dense config or set lora_rank=0")

    @property
    def qkv_features(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def layer_runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """(scan name, kind, layers) of every run of the stack, in
        order; one run "layers" of kind "" where no pattern is set. A
        kind's second run is "<kind>_layers2"."""
        if not self.layer_pattern:
            return (("layers", "", self.n_layers),)
        runs, seen = [], {}
        for k, n in self.layer_pattern:
            seen[k] = seen.get(k, 0) + 1
            runs.append((f"{k}_layers" + (str(seen[k]) if seen[k] > 1
                                          else ""), k, n))
        return tuple(runs)

    def runs_experts(self, kind: str) -> bool:
        """Whether a run of ``kind`` carries the routed experts."""
        return kind == "expert" or (kind in ("full", "window")
                                    and self.n_routed_experts > 0)

    @property
    def expert_layers(self) -> int:
        """Layers with routed experts, over all runs: the length of the
        ``expert_wi`` / ``expert_wo`` stacks."""
        return sum(n for k, n in self.layer_pattern
                   if self.runs_experts(k))

    @property
    def has_window_pages(self) -> bool:
        """Whether the paged cache has a second class of page: the
        pool of the "window" runs."""
        return any(k == "window" for k, _ in self.layer_pattern)

    @property
    def has_slot_state(self) -> bool:
        """Whether a row carries state beside its pages: the leaves of
        its "mamba" layers, indexed by slot."""
        return any(k == "mamba" for k, _ in self.layer_pattern)


def rope(x: jnp.ndarray, positions: jnp.ndarray, base: float = 10_000.0
         ) -> jnp.ndarray:
    """Rotary embeddings over the last dim. x: [B, S, H, D]."""
    d = x.shape[-1]
    half = d // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


def flash_window_ok(cfg: "TransformerConfig", seq_len: int) -> bool:
    """Whether ``seq_len`` falls in the configured attn_impl="auto"
    flash window (flash_max_seq <= 0 means unbounded above)."""
    if seq_len < cfg.flash_min_seq:
        return False
    return cfg.flash_max_seq <= 0 or seq_len < cfg.flash_max_seq


# spmd_check hook: when set, Attention calls it as fn(name, array) on
# its q/k/v projections and pre-projection output so the checker can
# capture their GSPMD shardings (jax.debug.inspect_array_sharding)
# without instrumented test doubles. None in normal operation.
_activation_probe = None


def _probe(name: str, x):
    if _activation_probe is not None:
        _activation_probe(name, x)
    return x


@contextlib.contextmanager
def activation_probe(fn):
    """Scope ``fn(name, array)`` as the attention activation probe
    (parallel/spmd_check.py's no-accidental-replication assertion)."""
    global _activation_probe
    prev = _activation_probe
    _activation_probe = fn
    try:
        yield
    finally:
        _activation_probe = prev


class QuantDenseGeneral(nn.Module):
    """Per-output-channel symmetric int8 projection: an int8 ``kernel``
    plus an f32 ``scale`` of the output-feature shape, with the scale
    applied to the MATMUL OUTPUT — ``y = (x @ W_q) * s`` — never to the
    kernel. For symmetric per-output-channel scales the two are
    mathematically identical (``x @ (W_q * s) == (x @ W_q) * s`` when
    ``s`` varies only over output channels), but this form lets the
    weights stream from HBM as int8: the int8→dtype convert rides the
    dot's operand fusion on TPU (the MXU reads converted tiles from
    registers, HBM traffic is the int8 bytes). On XLA:CPU the convert
    materializes, so there is no wall-clock win there — docs/serving.md
    records the measurement.

    Param init is STRUCTURAL (zero kernel, unit scales): real
    quantized params come from ``quantize_params_int8`` over a trained
    f32 tree; a from-scratch init of a quant model is shape-correct
    but degenerate, which is fine for eval_shape/cache plumbing."""

    features: Tuple[int, ...]
    axis: Tuple[int, ...] = (-1,)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        axis = tuple(a % x.ndim for a in self.axis)
        in_shape = tuple(x.shape[a] for a in axis)
        kernel = self.param("kernel", nn.initializers.zeros,
                            in_shape + tuple(self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           tuple(self.features), jnp.float32)
        y = jax.lax.dot_general(
            x, kernel.astype(self.dtype),
            ((axis, tuple(range(len(axis)))), ((), ())))
        # Scale in f32 (a per-channel rescale must not round through
        # bf16 twice), then back to the compute dtype.
        return (y.astype(jnp.float32) * scale).astype(self.dtype)


# Module paths quantize_params_int8 rewrites (and QuantDenseGeneral
# consumes when cfg.quant == "int8"): path suffix -> number of
# OUTPUT-channel axes in that kernel (the scale's shape; every other
# non-layer axis is a contraction axis the per-channel max reduces
# over). Embeddings and norms stay full-precision (they are gathers /
# elementwise, not weight-streaming matmuls); MoE expert weights are
# not covered (quant + n_experts serves unquantized experts).
_QUANT_SUFFIXES: Dict[Tuple[str, ...], int] = {
    ("attn", "query"): 2,
    ("attn", "key"): 2,
    ("attn", "value"): 2,
    ("attn", "out"): 1,
    ("mlp", "wi"): 1,
    ("mlp", "wo"): 1,
    ("lm_head",): 1,
}


def _quant_suffix(path: Tuple[str, ...]) -> Optional[int]:
    for suffix, n_out in _QUANT_SUFFIXES.items():
        if path[-len(suffix):] == suffix:
            return n_out
    return None


def quantize_leaf_int8(w, n_out: int, lead: int = 0):
    """THE per-channel symmetric int8 scheme, in one place: reduce
    max|w| over the contraction axes (everything between ``lead``
    layer-stack axes and the last ``n_out`` output-channel axes),
    ``scale = amax / 127`` (all-zero channels get scale 1 so dequant
    is exact), values round-clip to [-127, 127]. Returns
    ``(q int8, scale f32)``. Shared by the transformer param
    transform below and the generic classifier-export quantizer
    (serving/export.py) — one formula, no drift."""
    w = jnp.asarray(w, jnp.float32)
    red = tuple(range(lead, w.ndim - n_out))
    amax = jnp.max(jnp.abs(w), axis=red)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / jnp.expand_dims(scale, red)),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_leaf_int8(q, scale, n_out: int, lead: int = 0):
    """Inverse of ``quantize_leaf_int8`` (up to quantization error):
    ``q * scale`` with the scale broadcast back over the contraction
    axes. Returns f32."""
    q = jnp.asarray(q)
    red = tuple(range(lead, q.ndim - n_out))
    return (q.astype(jnp.float32)
            * jnp.expand_dims(jnp.asarray(scale, jnp.float32), red))


def quantize_params_int8(params):
    """f32/bf16 TransformerLM params -> the ``quant="int8"`` structure:
    each covered projection's ``{"kernel": w}`` becomes
    ``{"kernel": int8, "scale": f32}`` with one symmetric scale per
    output channel (``scale = max|w| / 127`` over the contraction
    axes). Layer-stacked kernels (under the nn.scan "layers"
    collection) quantize per layer per channel — exactly the leading
    axis the scanned QuantDenseGeneral params carry. Everything else
    (embed, norms, MoE) passes through unchanged; the input tree is
    not mutated."""
    def walk(node, path):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            p = path + (k,)
            n_out = _quant_suffix(p)
            if (isinstance(v, dict) and "kernel" in v
                    and n_out is not None
                    and jnp.asarray(v["kernel"]).dtype != jnp.int8):
                q, scale = quantize_leaf_int8(
                    v["kernel"], n_out, lead=1 if "layers" in p else 0)
                nv = {kk: vv for kk, vv in v.items() if kk != "kernel"}
                nv["kernel"] = q
                nv["scale"] = scale
                out[k] = nv
            else:
                out[k] = walk(v, p)
        return out

    return walk(params, ())


def dequantize_params_int8(params):
    """Inverse of ``quantize_params_int8`` (up to the quantization
    error): int8 kernels expand back to f32 ``kernel = q * scale`` and
    the scale leaves disappear — the ``KFX_LM_QUANT=0`` escape hatch
    that serves an int8 export through the full-precision path."""
    def walk(node, path):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            p = path + (k,)
            n_out = _quant_suffix(p)
            if (isinstance(v, dict) and "kernel" in v and "scale" in v
                    and n_out is not None
                    and jnp.asarray(v["kernel"]).dtype == jnp.int8):
                w = dequantize_leaf_int8(
                    v["kernel"], v["scale"], n_out,
                    lead=1 if "layers" in p else 0)
                out[k] = {kk: vv for kk, vv in v.items()
                          if kk not in ("kernel", "scale")}
                out[k]["kernel"] = w
            else:
                out[k] = walk(v, p)
        return out

    return walk(params, ())


def params_quantized(params) -> bool:
    """Whether a param tree carries int8 kernels (the load-time
    auto-detection the export's quant block corroborates)."""
    return any(jnp.asarray(x).dtype == jnp.int8
               for x in jax.tree_util.tree_leaves(params))


def lora_gather_delta(x, entry, adapter_ids, dtype):
    """Batched-gather LoRA (S-LoRA / Punica): one projection's
    low-rank correction for a batch where EVERY ROW may wear a
    different adapter. ``entry`` is the serving stack for this
    projection — ``{"a": [n_adapter_slots, d_in, r],
    "b": [n_adapter_slots, r, d_out]}`` (the per-adapter alpha/rank
    scale is folded into ``b`` at pool load time, serving/adapters.py)
    — and ``adapter_ids`` [B] selects each row's slot (-1 = base-only:
    the row's delta is masked to exactly 0, so its output is the base
    projection's bit pattern up to the identity ``y + 0``). x is the
    projection INPUT [B, S, d_in]; returns the delta [B, S, d_out] in
    the compute dtype. Two thin einsums, so the whole correction rides
    the MXU inside the same fused decode dispatch as the base matmul —
    no per-adapter dispatch, no weight swap."""
    ids = jnp.maximum(adapter_ids, 0)
    a = jnp.take(entry["a"], ids, axis=0).astype(dtype)  # [B, d_in, r]
    b = jnp.take(entry["b"], ids, axis=0).astype(dtype)  # [B, r, d_out]
    h = jnp.einsum("bsd,bdr->bsr", x.astype(dtype), a)
    d = jnp.einsum("bsr,bro->bso", h, b)
    return jnp.where((adapter_ids >= 0)[:, None, None], d,
                     jnp.zeros_like(d))


def _lora_apply(mdl, cfg, name, y, inp, lora, adapter_ids):
    """Add every configured LoRA correction for projection ``name`` to
    its base output ``y`` (any trailing feature shape): the TRAIN-time
    per-module ``<name>_lora_a``/``<name>_lora_b`` params when
    ``cfg.lora_rank > 0``, and the SERVING-time batched-gather stacks
    when ``lora`` carries an entry for ``name``. ``inp`` is the
    projection input (flattened to [B, S, d_in] here). With neither
    configured this is an exact no-op — the traced graph is identical
    to a pre-LoRA build."""
    entry = (lora or {}).get(name)
    if cfg.lora_rank <= 0 and entry is None:
        return y
    B, S = y.shape[0], y.shape[1]
    flat_in = inp.reshape(B, S, -1)
    d_out = 1
    for n in y.shape[2:]:
        d_out *= n
    delta = None
    if cfg.lora_rank > 0:
        r = cfg.lora_rank
        a = mdl.param(f"{name}_lora_a", nn.initializers.normal(0.02),
                      (flat_in.shape[-1], r), jnp.float32)
        # B starts at zero: step 0 of a fine-tune IS the base model.
        b = mdl.param(f"{name}_lora_b", nn.initializers.zeros,
                      (r, d_out), jnp.float32)
        h = jnp.einsum("bsd,dr->bsr", flat_in.astype(cfg.dtype),
                       a.astype(cfg.dtype))
        delta = (jnp.einsum("bsr,ro->bso", h, b.astype(cfg.dtype))
                 * (cfg.lora_alpha / r)).astype(cfg.dtype)
    if entry is not None:
        g = lora_gather_delta(flat_in, entry, adapter_ids, cfg.dtype)
        delta = g if delta is None else delta + g
    return y + delta.reshape(y.shape).astype(y.dtype)


def attention_path(cfg: TransformerConfig, seq_len: int,
                   window: int = 0) -> str:
    """The attention implementation a training or prefill forward over
    ``seq_len`` tokens takes — "ring", "flash" or "dense". The one rule
    ``Attention`` dispatches on; runners log it, so which kernel ran is
    read off the worker's log. ``window`` > 0 is a "window" layer's."""
    if cfg.cp > 1:
        # Context-parallel: the only seq-sharded kernel.
        return "ring"
    if not Attention(cfg)._use_flash(seq_len):
        return "dense"
    if window:
        raise ValueError(
            f"the flash kernels are causal only: a 'window' layer "
            f"(window {window}) at {seq_len} tokens needs "
            "attn_impl='naive' (the band is a term of the dense mask "
            "and of the decode cache's)")
    if cfg.kv_heads != cfg.n_heads:
        raise ValueError(
            f"the flash kernels take one key/value head a query head: "
            f"n_kv_heads {cfg.kv_heads} of n_heads {cfg.n_heads} at "
            f"{seq_len} tokens needs attn_impl='naive' (grouped heads "
            "are served through the decode cache, not trained)")
    return "flash"


class Attention(nn.Module):
    cfg: TransformerConfig
    # A layer kind's own facts ("full" / "window" runs): whether q and
    # k rotate (None: ``cfg.rope``), and how far a query sees (0:
    # every earlier position; n: the last n, its own among them).
    rotate: Optional[bool] = None
    window: int = 0

    def _use_flash(self, seq_len: int) -> bool:
        cfg = self.cfg
        if cfg.attn_impl in ("xla", "naive", "ring"):
            # "ring" only reaches here when cp<=1, which the config
            # rejects at construction; the dense fallback keeps a
            # stale-config trace honest rather than crashing.
            return False
        if cfg.attn_impl == "flash" and cfg.head_dim % 64:
            raise ValueError(
                f"attn_impl='flash' needs head_dim%64==0, "
                f"got D={cfg.head_dim}")
        from ..ops.flash_attention import supported

        ok = supported(seq_len, cfg.head_dim)
        if cfg.attn_impl == "flash":
            # Sub-block traces (e.g. the 8-token init sample) ride the
            # dense path; real sequences use the kernel.
            return ok
        # auto: flash inside the configured window (see
        # flash_min_seq/flash_max_seq — measured defaults, overridable
        # per hardware). tp composes (heads shard over "model"); sp
        # composes (attention input is full-S).
        return (ok and jax.default_backend() == "tpu"
                and flash_window_ok(cfg, seq_len))

    @nn.compact
    def __call__(self, x, positions, block_tables=None,
                 write_locations=None, lora=None, adapter_ids=None,
                 layer=0):
        cfg = self.cfg
        B, S, _ = x.shape
        if cfg.quant == "int8":
            proj = lambda name, feats: QuantDenseGeneral(
                feats if isinstance(feats, tuple) else (feats,),
                axis=(-1,), dtype=cfg.dtype, name=name)
        else:
            proj = lambda name, feats: nn.DenseGeneral(
                feats, axis=-1, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
        # checkpoint_name tags mark the fat matmul outputs for the
        # "save_dense"/"save_flash" remat policies: keep these, recompute
        # only the cheap elementwise chain and the attention internals.
        # Tagged FLAT ([B, S, H*D]) and reshaped after: a saved
        # [B, S, H, D] buffer puts head_dim on the 128-lane tile, and at
        # D=64 XLA pads it 2x — measured 1.5G instead of 768M PER TENSOR
        # per save at base/b8/S=2048 (the round-5 HBM ladder); the flat
        # layout's minor dim is H*D, tile-aligned, no padding.
        def tagged_heads(name, y):
            B_, S_, H_, D_ = y.shape
            tp = 1
            mesh_ = jax.sharding.get_abstract_mesh()
            if not mesh_.empty:
                from ..parallel.mesh import AXIS_MODEL

                tp = mesh_.shape.get(AXIS_MODEL, 1) or 1
            if D_ % 128 == 0 and (H_ // tp) % 8 == 0:
                # Tile-aligned in BOTH minor dims per shard (lanes: D;
                # sublanes: the per-tp-shard head count): the 4D layout
                # wastes nothing and tags in place — the flat
                # round-trip measured ~2% slower at d2048 (relayout
                # copies). Misaligned shapes (D=64, or tp slicing heads
                # below the 8-sublane tile) save flat: a padded save
                # costs 2x HBM per tensor (measured 1.5G vs 768M).
                return checkpoint_name(y, name)
            y = checkpoint_name(y.reshape(B_, S_, H_ * D_), name)
            return y.reshape(B_, S_, H_, D_)

        # LoRA corrections land at the PROJECTION OUTPUT — before rope
        # and the head scaling — exactly where a merged-weight kernel
        # (W + scale·A·B) would put them, so the dense merged oracle
        # and the batched-gather path compute the same function.
        def hproj(name, heads=cfg.n_heads):
            y = proj(name, (heads, cfg.head_dim))(x)
            return _lora_apply(self, cfg, name, y, x, lora, adapter_ids)

        q = tagged_heads("attn_q", hproj("query"))
        k = tagged_heads("attn_k", hproj("key", cfg.kv_heads))
        v = tagged_heads("attn_v", hproj("value", cfg.kv_heads))
        if cfg.rope if self.rotate is None else self.rotate:
            # RoPE with absolute positions (pads carry -1; their rows
            # are masked out of every decode-mode attention, so the
            # garbage rotation never contributes).
            q = rope(q, jnp.maximum(positions, 0), cfg.rope_base)
            k = rope(k, jnp.maximum(positions, 0), cfg.rope_base)
        if cfg.attention_multiplier:
            q = q * cfg.attention_multiplier
        else:
            q = q / np.sqrt(cfg.head_dim)
        _probe("attn_q", q)
        _probe("attn_k", k)
        _probe("attn_v", v)

        path = "decode" if cfg.decode \
            else attention_path(cfg, S, self.window)
        if path == "decode":
            out = self._decode_attend(q, k, v, positions, block_tables,
                                      write_locations, layer)
        elif path == "ring":
            # Context-parallel path: seq sharded over "ctx", heads over
            # "model" (each head attends independently, so tp composes),
            # exact causal ring attention rotating K/V between neighbours.
            import functools

            from ..parallel.mesh import AXIS_CTX, AXIS_DATA, AXIS_MODEL
            from ..parallel.ring_attention import ring_attention
            from jax.sharding import PartitionSpec as P

            spec = P(AXIS_DATA, AXIS_CTX, AXIS_MODEL, None)
            out = jax.shard_map(
                functools.partial(ring_attention, axis_name=AXIS_CTX),
                in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        elif path == "flash":
            from ..ops import flash_attention as fa

            # The kernels compile for the backend or the run fails:
            # nothing here falls back to the Pallas interpreter (a test
            # on CPU sets fa.INTERPRET itself; the VMA tracker rejects
            # the interpreted kernel's dynamic slices inside shard_map,
            # hence check_vma below).
            mesh = jax.sharding.get_abstract_mesh()
            if not mesh.empty:
                # Under GSPMD a pallas call must be per-shard: batch rides
                # "data", heads ride "model" (tp), seq/feature whole.
                from ..parallel.mesh import AXIS_DATA, AXIS_MODEL
                from jax.sharding import PartitionSpec as P

                spec = P(AXIS_DATA, None, AXIS_MODEL, None)
                o, lse = jax.shard_map(
                    fa.flash_attention_fwd, in_specs=(spec, spec, spec),
                    out_specs=(spec, spec),
                    check_vma=not fa.INTERPRET)(q, k, v)
            else:
                o, lse = fa.flash_attention_fwd(q, k, v)
            # Tagged OUTSIDE the shard_map so remat policies see the
            # names: "save_flash" keeps the kernel's O(B·S·H·D) output
            # and its log-sum-exp rows — the linear-in-S residuals that
            # are flash attention's entire memory story — so the remat
            # backward runs only the flash backward kernels, never the
            # forward one (the re-run full remat forces). Flat-tagged
            # like q/k/v (see tagged_heads): the [B,S,H,D] layout pads
            # D=64 to the 128-lane tile, doubling the save.
            o = tagged_heads("flash_o", o)
            lse = checkpoint_name(lse, "flash_lse")
            if not mesh.empty:
                out = jax.shard_map(
                    fa.flash_attention_apply,
                    in_specs=(spec, spec, spec, spec, spec),
                    out_specs=spec,
                    check_vma=not fa.INTERPRET)(q, k, v, o, lse)
            else:
                out = fa.flash_attention_apply(q, k, v, o, lse)
        else:
            # Dense causal attention (XLA fuses the softmax chain).
            if cfg.kv_heads != cfg.n_heads:
                k, v = (jnp.repeat(a, cfg.n_heads // cfg.kv_heads, 2)
                        for a in (k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            mask = nn.make_causal_mask(jnp.zeros((B, S)), dtype=jnp.bool_)
            if self.window:
                at = jnp.arange(S)
                mask = mask & (at[None, :] > at[:, None] - self.window)
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype), v)
        _probe("attn_mix", out)
        if cfg.quant == "int8":
            mix = QuantDenseGeneral((x.shape[-1],), axis=(-2, -1),
                                    dtype=cfg.dtype, name="out")
        else:
            mix = nn.DenseGeneral(x.shape[-1], axis=(-2, -1),
                                  use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype, name="out")
        y = _lora_apply(self, cfg, "out", mix(out), out, lora,
                        adapter_ids)
        return checkpoint_name(y, "attn_out")

    def _decode_attend(self, q, k, v, positions, block_tables=None,
                       write_locations=None, layer=0):
        """KV-cache attention. Two cache layouts behind one mask rule —
        per-slot validity is the cached position id (-1 = empty/pad),
        never the cache location, so both layouts stay exact for left-
        or right-padded prompts and greedy outputs agree byte-for-byte.

        Every cache leaf holds ALL layers ([n_layers, ...], made by
        ``init_cache``) and ``layer`` says which one this call is: the
        layer scan carries the cache (``TransformerLM``), so a layer
        writes its tokens into the stack where it lies
        (``at[layer, ...]``) and reads its own slice by dynamic index.
        Scanned in and stacked out instead, every token-step sliced
        each layer's pool out of the stack, wrote it into a second
        stack and copied that one back: half the decode program. The
        shapes below leave the leading layers axis out.

        Dense (kv_page_size == 0): one [B, max_seq_len] KV row per
        batch row, written at a PER-ROW cursor ([B], not a shared
        scalar): the serving engine used to run one cache row per
        request slot, and slots prefill/retire independently, so row
        cursors diverge. Out-of-bounds scatter updates (an idle slot
        whose cursor marched past L) are dropped by XLA's scatter
        semantics. The one-shot generate path keeps every cursor equal,
        where the scatter degenerates to a dynamic_update_slice.

        Paged (kv_page_size > 0, vLLM-style): ONE global pool of
        ``kv_pages`` fixed-size pages shared by every request slot,
        batch-independent — prefill (B=1) and decode (B=n_slots)
        mutate the same pool, which is what lets the serving engine
        prefill directly into a slot's pages with no row copy. The
        caller passes per-row block tables [B, max_seq_len/page_size]
        mapping logical block -> physical page (-1 = unallocated).
        There is no in-cache cursor: each token's write LOCATION in the
        row's logical space (page = table[loc // P], slot = loc % P)
        is ``write_locations`` — defaulting to the position id, which
        is exact for prefill; the engine's decode chunks pass the
        dense-equivalent cursor location (prompt bucket + step) so the
        logical layout, pad gaps included, reproduces the dense cache
        byte-for-byte (an unwritten gap entry and a written pad both
        mask to probability exactly 0, so the attention sums are
        bit-identical to the dense layout's). Writes to pad positions
        (-1), negative locations, or unallocated blocks are dropped;
        gathered entries from unallocated blocks read as position -1
        (masked). Page recycling across requests relies on the pool
        owner invalidating freed pages' position ids — see
        serving/engine.py.

        Multi-token query windows (S > 1 with explicit, per-token
        ``write_locations``) are first-class, not just a prefill
        special case: writes land before the gather and the mask is
        causal BY POSITION (``kp <= qp``), so query i of a window
        attends the window's own earlier tokens plus the cache — the
        contract speculative decoding's verify dispatch relies on (the
        engine feeds the pending token + k draft proposals as one
        window and reads k+1 next-token distributions back; rejection
        rolls the cursor back and stamps the tail's position ids to
        -1, no page copies).

        Which operand the paged layout streams is read off the shapes
        (``attends_pool_in_place``): where the pool holds no more
        slots than the batch's logical view (``B * L >= N * P``: every
        decode and verify dispatch of an engine whose pool is no
        larger than ``slots x blocks``), the queries are scored
        against the pool where it lies, each row masked to the pages
        its block table names, and no per-row view is built. Where the
        pool is the larger (prefill, B = 1), each row's logical view
        is gathered through its table. Same rule, same numbers up to
        the order of the float32 sums.

        The parts carry ``jax.named_scope`` names — ``kv_write``,
        ``kv_gather`` (gathered form) or ``kv_member`` (in place),
        ``scores``, ``pv`` — under the module's own ``attn`` scope
        (flax names every module call), so a profiler trace or the
        compiled HLO attributes an op to its part by name
        (``.../attn/attn._decode_attend/kv_gather/...``) and not by
        fusion number."""
        cfg = self.cfg
        B, S, H, D = q.shape
        L = cfg.max_seq_len

        def leaf(name):
            # A carried collection cannot grow inside the scan.
            if not self.has_variable("cache", name):
                raise ValueError(
                    f"decode needs the cache made by init_cache(): no "
                    f"{name!r} in the 'cache' collection")
            return self.variable("cache", name)

        def own(var, flat=False):
            """This layer's slice of a stacked leaf. ``flat`` merges a
            paged leaf's page and slot axes ([N*P, ...]) BEFORE the
            slice: the v5e compiler then slices straight into what the
            score and value matmuls read; sliced as pages it adds a
            relayout copy of the layer's pool in between."""
            value = var.value
            if flat:
                value = value.reshape(value.shape[0], -1, *value.shape[3:])
            return jax.lax.dynamic_index_in_dim(value, layer, 0,
                                                keepdims=False)

        ck, cv, cpos = (leaf("cached_key"), leaf("cached_value"),
                        leaf("cached_pos"))
        # Grouped heads lie side by side in their leaves (_attn_cache):
        # heads() of what is read, entry() of what is written.
        heads = entry = lambda x: x
        if cfg.kv_heads != H:
            heads = lambda x: x.reshape(x.shape[:-1] + (cfg.kv_heads, D))
            entry = lambda x: x.reshape(x.shape[:-2] + (-1,))
        if cfg.kv_page_size > 0:
            # A "window" layer's leaves are the window runs' own pool,
            # its tables and locations that pool's (TransformerLM).
            P = cfg.kv_page_size
            N = cfg.window_pages if self.window else cfg.kv_pages
            if block_tables is None:
                raise ValueError(
                    "paged decode (kv_page_size > 0) requires block_tables")
            int8_kv = cfg.kv_quant == "int8"
            if int8_kv:
                # Per-token symmetric scales, stored as one f32 plane
                # per pool beside the pages ([N, P]: page x slot). The
                # scale is derived from each written token's own K/V
                # row at write time (scale = max|k| / 127), so there is
                # no calibration pass and page recycling needs no
                # rescale — a recycled entry's stale scale is dead the
                # moment its position id is -1.
                ksc, vsc = leaf("key_scale"), leaf("value_scale")
            pos = positions  # [B, S]
            loc = pos if write_locations is None else write_locations
            ok = (pos >= 0) & (loc >= 0)
            blk = jnp.where(ok, loc // P, 0)
            page = jnp.take_along_axis(block_tables, blk, axis=1)  # [B, S]
            # Invalid (pad position, negative location, or block not
            # yet allocated) -> an out-of-range page index;
            # mode="drop" discards the update.
            page = jnp.where(ok & (page >= 0), page, N)
            slot = jnp.where(ok, loc % P, 0)

            def write(var, rows):
                var.value = var.value.at[layer, page, slot].set(
                    rows, mode="drop")

            with jax.named_scope("kv_write"):
                if int8_kv:
                    # Quantize-on-write: round each token's K/V row to
                    # int8 against its own max-abs scale. A zero row
                    # quantizes to zeros with scale 0 (dequant exact).
                    def q8(x):
                        xf = x.astype(jnp.float32)
                        s = jnp.max(jnp.abs(xf), axis=(-2, -1)) / 127.0
                        q = jnp.clip(
                            jnp.round(
                                xf / jnp.maximum(s, 1e-30)[..., None,
                                                           None]),
                            -127, 127).astype(jnp.int8)
                        return q, s
                    kq, ks = q8(k)
                    vq, vs = q8(v)
                    write(ck, entry(kq))
                    write(cv, entry(vq))
                    write(ksc, ks)
                    write(vsc, vs)
                else:
                    write(ck, entry(k.astype(cfg.dtype)))
                    write(cv, entry(v.astype(cfg.dtype)))
                write(cpos, pos)
            # A window layer's view is bounded: the blocks that hold
            # the last ``window`` positions of the row's first query,
            # through those of its last (a row's queries are S
            # positions in a row; locations are positions here).
            nblk = block_tables.shape[1]
            nview, in_place = paged_view(cfg, B, S, self.window, nblk)
            if in_place:
                if int8_kv:
                    # Dequant the pool where it lies: int8 entries x
                    # the per-token scale plane, in f32, then the
                    # compute dtype (what the gathered form does to
                    # each row's view).
                    with jax.named_scope("kv_dequant"):
                        pk = (heads(own(ck, flat=True)).astype(jnp.float32)
                              * own(ksc, flat=True)[..., None, None]
                              ).astype(cfg.dtype)
                        pv = (heads(own(cv, flat=True)).astype(jnp.float32)
                              * own(vsc, flat=True)[..., None, None]
                              ).astype(cfg.dtype)
                else:
                    pk, pv = (heads(own(ck, flat=True)),
                              heads(own(cv, flat=True)))
                return self._attend_pool(q, positions, block_tables, pk, pv,
                                         own(cpos))
            # Gather each row's logical view [L] through its table.
            # Unallocated blocks clamp to page 0 for K/V (their scores
            # are masked to exactly-0 probability via position -1, so
            # the garbage never contributes) and force position -1.
            with jax.named_scope("kv_gather"):
                if nview < nblk:
                    first = jnp.min(jnp.where(
                        pos >= 0, pos, jnp.iinfo(jnp.int32).max), 1)
                    first = jnp.clip((first - (self.window - 1)) // P,
                                     0, nblk - nview)          # [B]
                    block_tables = jnp.take_along_axis(
                        block_tables, first[:, None] + jnp.arange(
                            nview, dtype=first.dtype), axis=1)
                    L = nview * P
                pt = jnp.clip(block_tables, 0, N - 1)    # [B, nblk]

                def view(var):
                    rows = var.value[layer, pt]      # [B, nblk, P, ...]
                    return rows.reshape(B, L, *rows.shape[3:])

                gk, gv = heads(view(ck)), heads(view(cv))
                if int8_kv:
                    # Dequant-on-gather: int8 entries x the per-token
                    # scale plane, in f32 (one multiply per gathered
                    # element), then the compute dtype.
                    gk = (gk.astype(jnp.float32)
                          * view(ksc)[..., None, None]).astype(cfg.dtype)
                    gv = (gv.astype(jnp.float32)
                          * view(vsc)[..., None, None]).astype(cfg.dtype)
                gp = jnp.where((block_tables >= 0)[..., None],
                               cpos.value[layer, pt], -1).reshape(B, L)
        else:
            cur = leaf("cache_index")
            i = own(cur)  # [B]
            with jax.named_scope("kv_write"):
                rows = jnp.arange(B, dtype=jnp.int32)[:, None]  # [B, 1]
                at = i[:, None] + jnp.arange(
                    S, dtype=jnp.int32)[None]                   # [B, S]
                ck.value = ck.value.at[layer, rows, at].set(
                    entry(k.astype(cfg.dtype)))
                cv.value = cv.value.at[layer, rows, at].set(
                    entry(v.astype(cfg.dtype)))
                cpos.value = cpos.value.at[layer, rows, at].set(positions)
                cur.value = cur.value.at[layer].set(i + S)
            gk, gv, gp = heads(own(ck)), heads(own(cv)), own(cpos)

        if cfg.kv_heads != H:
            # Grouped heads: the view stays as it lies, a token's
            # key/value heads side by side (_blocks).
            with jax.named_scope("scores"):
                scores = jnp.einsum("bqhf,bkf->bhqk", self._blocks(q),
                                    gk.reshape(B, gk.shape[1], -1))
                mask = ((gp >= 0)[:, None, :]
                        & (gp[:, None, :] <= positions[:, :, None]))
                if self.window:
                    mask = mask & (gp[:, None, :]
                                   > positions[:, :, None] - self.window)
                scores = jnp.where(mask[:, None], scores,
                                   jnp.finfo(scores.dtype).min)
                probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
            with jax.named_scope("pv"):
                return self._own_block(jnp.einsum(
                    "bhqk,bkf->bqhf", probs.astype(cfg.dtype),
                    gv.reshape(B, gv.shape[1], -1)))
        with jax.named_scope("scores"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, gk)  # [B,H,S,L]
            kp = gp[:, None, None, :]                      # [B,1,1,L]
            qp = positions[:, None, :, None]               # [B,1,S,1]
            mask = (kp >= 0) & (kp <= qp)
            if self.window:
                mask = mask & (kp > qp - self.window)
            scores = jnp.where(mask, scores,
                               jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
        with jax.named_scope("pv"):
            return jnp.einsum("bhqk,bkhd->bqhd",
                              probs.astype(cfg.dtype), gv)

    def _blocks(self, q):
        """Grouped heads' queries against keys that lie side by side,
        n_kv_heads x D a token: q [B, S, H, D] -> [B, S, H, n_kv_heads
        x D], a head's numbers in its key/value head's block and zeros
        in the others, so that one product over the whole entry scores
        every head against its own keys. The entry is never split into
        heads: [8, 64] as minor dimensions pads every head to 128 lanes,
        and the chip's compiler made each gathered view over in that
        layout (2.6 ms a view a decode step at 64 rows: my chip run
        p1, PR 40). The zeros cost n_kv_heads times the products'
        operations, which a decode step does not feel."""
        B, S, H, D = q.shape
        n = self.cfg.kv_heads
        eye = jnp.eye(n, dtype=q.dtype)
        return jnp.einsum("bsngd,nm->bsngmd", q.reshape(B, S, n, H // n, D),
                          eye).reshape(B, S, H, n * D)

    def _own_block(self, mixed):
        """What ``_blocks``' queries mixed from values side by side,
        [B, S, H, n_kv_heads x D]: each head's own block, [B, S, H, D]."""
        B, S, H, F = mixed.shape
        n = self.cfg.kv_heads
        eye = jnp.eye(n, dtype=mixed.dtype)
        return jnp.einsum("bsngmd,nm->bsngd",
                          mixed.reshape(B, S, n, H // n, n, F // n),
                          eye).reshape(B, S, H, F // n)

    def _attend_pool(self, q, positions, block_tables, pk, pv, kpos):
        """Paged attention over the pool in place: ``pk``/``pv``
        [N*P, H, D] are ALL of the pool's slots, ``kpos`` [N, P] their
        cached position ids. A row sees a slot iff its page is in the
        row's block table AND the slot's position id is live and not
        ahead of the query — the gathered form's rule with membership
        in the place of the gather. Membership is a [B, N] matrix, not
        an owner per page (the prefix cache puts one page in several
        rows' tables), and is not optional: a freed page keeps its
        position ids until it is recycled, and other rows' pages are
        live. Scores accumulate in float32; a fully masked row (an
        inactive slot at position -1) stays finite."""
        N, P = kpos.shape
        with jax.named_scope("kv_member"):
            pages = jnp.arange(N, dtype=block_tables.dtype)
            member = (block_tables[:, :, None] == pages).any(1)  # [B, N]
            kp = kpos.reshape(N * P)
            qp = positions[:, None, :, None]                 # [B,1,S,1]
            mask = (jnp.repeat(member, P, axis=1)[:, None, None, :]
                    & (kp >= 0) & (kp <= qp))                # [B,1,S,NP]
            if self.window:
                mask = mask & (kp > qp - self.window)
        if pk.shape[1] != q.shape[2]:   # grouped heads (_blocks)
            with jax.named_scope("scores"):
                scores = jnp.einsum("bqhf,kf->bhqk", self._blocks(q),
                                    pk.reshape(N * P, -1),
                                    preferred_element_type=jnp.float32)
                scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(scores, -1)
            with jax.named_scope("pv"):
                return self._own_block(jnp.einsum(
                    "bhqk,kf->bqhf", probs.astype(self.cfg.dtype),
                    pv.reshape(N * P, -1)))
        with jax.named_scope("scores"):
            scores = jnp.einsum("bqhd,khd->bhqk", q, pk,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, -1)
        with jax.named_scope("pv"):
            return jnp.einsum("bhqk,khd->bqhd",
                              probs.astype(self.cfg.dtype), pv)


def score_bytes(cfg: TransformerConfig, window: int) -> float:
    """For ``attends_pool_in_place``: the bytes of scores a cached
    position costs a row (its query heads x the window's tokens,
    written and read as float32 and once as probabilities), over the
    bytes of the position's own keys and values."""
    item = 1 if cfg.kv_quant == "int8" else jnp.dtype(cfg.dtype).itemsize
    return (10.0 * cfg.n_heads * window
            / (2 * cfg.kv_heads * cfg.head_dim * item))


def paged_view(cfg: TransformerConfig, batch: int, seq: int, window: int,
               blocks: int) -> Tuple[int, bool]:
    """(blocks of a row's view, whether the pool is scored in place
    instead) of a paged attention call of ``seq`` queries a row over
    tables of ``blocks`` blocks; ``window`` > 0 is a "window" layer's,
    whose view holds the window of the row's first query through its
    last and whose pool is the window class's."""
    P = cfg.kv_page_size
    view = min(blocks, (seq + window - 2) // P + 2) if window else blocks
    return view, attends_pool_in_place(
        batch, view * P, cfg.window_pages if window else cfg.kv_pages, P,
        score_bytes(cfg, seq))


def attends_pool_in_place(batch: int, max_seq_len: int, kv_pages: int,
                          page_size: int, score_bytes: float) -> bool:
    """Whether paged decode attention scores the pool in place
    (``kv_pages * page_size`` K/V positions a query row) or gathers
    each row's logical view (``max_seq_len`` positions a row): the
    form that moves fewer bytes. In place every row scores the whole
    pool, so a position costs its keys and values once and ``batch``
    rows of scores (``score_bytes``, the function above); gathered it
    costs them three times (read, written into the view, read) and one
    row of scores. With a key/value head a query head and wide heads
    the scores hardly count, and the pool is scored in place until it
    is some three times the batch's logical view; with few key/value
    heads under many query heads and many rows the scores are the
    larger part (64 rows x 32 heads over 8 x 64-wide key/value heads:
    1 GB of float32 scores a layer a step in place) and the rows
    gather. Read off shapes that are static when the program is
    traced; the engine reports the outcome per program
    (``kfx_lm_attend_positions``)."""
    pool, view = kv_pages * page_size, batch * max_seq_len
    return view * (3.0 + score_bytes) >= pool * (1.0 + batch * score_bytes)


def init_cache(cfg: TransformerConfig, batch: int = 0):
    """The empty decode cache of ``cfg`` — the "cache" collection a
    decode-mode ``TransformerLM`` is applied with: zeros, every cached
    position id -1. Every leaf holds all layers (axis 0). Paged
    (``kv_page_size > 0``): the pools [layers, kv_pages, page, H, D],
    their position ids [layers, kv_pages, page] and, for int8 KV, the
    two scale planes; batch-independent. Dense: [layers, batch,
    max_seq_len, H, D] rows with a per-row cursor, so it needs
    ``batch``. Latent attention: per cached token the latent and rotary
    numbers in one leaf (``latent_entry_width``) and the indexer's key
    in a second
    (models/latent.py), paged. One subtree for every run of
    ``cfg.layer_runs``, each leaf that run's layers. Made out here
    because the layer scan carries the cache, and what a scan carries
    cannot come into being inside it."""
    if not cfg.has_slot_state:
        # The "window" runs' leaves are a pool of their own.
        return {name: {"attn": _attn_cache(
                    cfg, n, batch,
                    cfg.window_pages if kind == "window" else cfg.kv_pages)}
                for name, kind, n in cfg.layer_runs}
    from .ssm import init_state

    rows = cfg.state_slots if cfg.kv_page_size > 0 else batch
    if rows < 1:
        raise ValueError(
            "slot state beside a paged pool needs state_slots; the "
            "dense layout init_cache(cfg, batch)")
    return {name: {"ssm": init_state(cfg, n, rows)} if kind == "mamba"
            else {"attn": _attn_cache(cfg, n, batch, cfg.kv_pages)}
            for name, kind, n in cfg.layer_runs}


def latent_entry_width(cfg: TransformerConfig) -> int:
    """Numbers a cached token takes in the ``cached_latent`` leaf: the
    latent and the rotary key, padded to whole 128-lane tiles. The TPU
    tiles a leaf's minor dimension in 128 lanes whatever is declared,
    so the padding costs no memory there; declared unpadded (576 for
    GLM-5), the leaf cannot be written in place and every dispatch
    copies the whole pool first (2.1 GiB at the cell's size: AOT for
    the v5e, PR 36)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _attn_cache(cfg: TransformerConfig, n: int, batch: int, pages: int):
    """The attention cache leaves of a run of ``n`` layers; paged, a
    pool of ``pages`` pages."""
    H, D = cfg.kv_heads, cfg.head_dim
    if cfg.kv_lora_rank > 0:
        if cfg.kv_page_size < 1:
            raise ValueError("latent attention is cached in pages "
                             "(kv_page_size > 0)")
        rows = (n, pages, cfg.kv_page_size)
        int8_kv = cfg.kv_quant == "int8"
        attn = {"cached_latent": jnp.zeros(
                    rows + (latent_entry_width(cfg),),
                    jnp.int8 if int8_kv else cfg.dtype),
                "cached_pos": jnp.full(rows, -1, jnp.int32)}
        if int8_kv:
            attn["latent_scale"] = jnp.zeros(rows, jnp.float32)
        if cfg.index_topk > 0:
            attn["cached_index_key"] = jnp.zeros(
                rows + (cfg.index_head_dim,), cfg.dtype)
        return attn
    if cfg.kv_page_size > 0:
        rows = (n, pages, cfg.kv_page_size)
        int8_kv = cfg.kv_quant == "int8"
        kv_dtype = jnp.int8 if int8_kv else cfg.dtype
        attn = {}
        if int8_kv:
            attn = {"key_scale": jnp.zeros(rows, jnp.float32),
                    "value_scale": jnp.zeros(rows, jnp.float32)}
    else:
        if batch < 1:
            raise ValueError("the dense decode cache is per batch row: "
                             "init_cache(cfg, batch)")
        rows, kv_dtype = (n, batch, cfg.max_seq_len), cfg.dtype
        attn = {"cache_index": jnp.zeros((n, batch), jnp.int32)}
    # Grouped heads: a token's key/value heads side by side, in whole
    # 128-lane tiles (8 x 64 declared [8, 64] pads every head to 128).
    entry = (H, D) if H == cfg.n_heads else (H * D,)
    attn.update(cached_key=jnp.zeros(rows + entry, kv_dtype),
                cached_value=jnp.zeros(rows + entry, kv_dtype),
                cached_pos=jnp.full(rows, -1, jnp.int32))
    return attn


class DenseFFN(nn.Module):
    cfg: TransformerConfig
    d_ff: int = 0   # 0 = cfg.d_ff (a shared expert states its own)

    @nn.compact
    def __call__(self, x, lora=None, adapter_ids=None):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        if cfg.quant == "int8":
            dense = lambda name, feats: QuantDenseGeneral(
                (feats,), axis=(-1,), dtype=cfg.dtype, name=name)
        else:
            dense = lambda name, feats: nn.Dense(
                feats, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
        wi = _lora_apply(self, cfg, "wi", dense("wi", 2 * d_ff)(x),
                         x, lora, adapter_ids)
        wi = checkpoint_name(wi, "mlp_wi")
        gate, up = jnp.split(wi, 2, axis=-1)
        h = nn.silu(gate) * up  # SwiGLU
        wo = _lora_apply(self, cfg, "wo", dense("wo", x.shape[-1])(h),
                         h, lora, adapter_ids)
        return checkpoint_name(wo, "mlp_wo")


class MoEFFN(nn.Module):
    """Top-k routed experts, dispatch/combine as einsums against one-hot
    routing tensors — no gather/scatter, so the whole layer is MXU work
    and shards cleanly: experts over "data" (ep), expert mlp dim over
    "model" (tp).

    Default dispatch is GShard-style capacity routing: each batch row is a
    routing group; every expert owns a fixed buffer of C slots per group
    (C = ceil(capacity_factor · K · S / E)); tokens claim slots in
    sequence order via a cumsum, first choices before second, and tokens
    beyond capacity are dropped (their residual passes through untouched).
    Expert FLOPs are O(E · C) regardless of routing skew — this is what
    lets E grow past toy sizes. With C == S it is exact (== dense).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, D = x.shape
        E, K = cfg.n_experts, cfg.expert_top_k
        gate_logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                               param_dtype=jnp.float32, name="gate")(
            x.astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, -1)
        weights, idx = jax.lax.top_k(probs, K)
        weights = weights / jnp.sum(weights, -1, keepdims=True)
        one_hot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [B, S, K, E]

        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (E, D, 2 * cfg.d_ff), cfg.param_dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (E, cfg.d_ff, D), cfg.param_dtype)

        def expert_ffn(xe):
            """xe: [E, ..., D] per-expert token buffers."""
            h = checkpoint_name(
                jnp.einsum("e...d,edf->e...f", xe, wi.astype(cfg.dtype)),
                "moe_wi")
            gate_h, up = jnp.split(h, 2, axis=-1)
            return checkpoint_name(
                jnp.einsum("e...f,efd->e...d", nn.silu(gate_h) * up,
                           wo.astype(cfg.dtype)), "moe_wo")

        if cfg.moe_dispatch == "capacity":
            cap = int(np.ceil(cfg.capacity_factor * K * S / E))
            cap = max(1, min(cap, S))
            # Slot assignment: flatten choices k-major-last so every
            # token's first choice outranks any token's second choice,
            # then a cumsum per expert numbers the claimed slots.
            ohp = one_hot.transpose(0, 2, 1, 3).reshape(B, K * S, E)
            pos = jnp.cumsum(ohp, axis=1) - ohp  # [B, K*S, E]
            keep = (pos < cap) * ohp
            pos = pos.reshape(B, K, S, E).transpose(0, 2, 1, 3)
            keep = keep.reshape(B, K, S, E).transpose(0, 2, 1, 3)
            # Each (token, expert) pair is claimed by at most one k (top_k
            # indices are distinct), so fold k BEFORE the slot one_hot —
            # the biggest MoE activation stays [B, S, E, C], not K× that.
            pos_se = jnp.sum(pos * keep, axis=2)       # [B, S, E]
            keep_se = jnp.sum(keep, axis=2)            # 0/1 [B, S, E]
            w_se = jnp.sum(weights[..., None] * keep, axis=2)
            dispatch = (jax.nn.one_hot(pos_se, cap, dtype=cfg.dtype)
                        * keep_se.astype(cfg.dtype)[..., None])
            combine = w_se.astype(cfg.dtype)[..., None] * dispatch
            xe = jnp.einsum("bsd,bsec->ebcd", x, dispatch)  # [E, B, C, D]
            ye = expert_ffn(xe)
            y = jnp.einsum("ebcd,bsec->bsd", ye, combine)
        elif cfg.moe_dispatch == "dense":
            # Every expert sees every token, masked — exact at any
            # capacity but O(E·tokens) FLOPs; kept as the numerics oracle.
            combine = jnp.einsum("bsk,bske->bse", weights.astype(cfg.dtype),
                                 one_hot.astype(cfg.dtype))
            dispatch = (combine > 0).astype(cfg.dtype)
            xe = jnp.einsum("bsd,bse->ebsd", x, dispatch)
            ye = expert_ffn(xe)
            y = jnp.einsum("ebsd,bse->bsd", ye, combine)
        else:
            raise ValueError(
                f"unknown moe_dispatch {cfg.moe_dispatch!r} "
                "(expected 'capacity' or 'dense')")

        # Load-balancing auxiliary loss (Switch-style), stashed for the
        # train loop via a mutable collection.
        me = jnp.mean(one_hot[..., 0, :].astype(jnp.float32), axis=(0, 1))
        ce = jnp.mean(probs, axis=(0, 1))
        self.sow("aux_loss", "moe", E * jnp.sum(me * ce))
        return y


class Block(nn.Module):
    """One decoder layer. Scan-shaped: returns (carry, per-layer output)."""

    cfg: TransformerConfig
    # a run of cfg.layer_pattern: "dense", "expert", "mamba",
    # "attention", "full", "window"
    kind: str = ""
    # where this run's layers begin in the routed experts' stacks
    first_expert_layer: int = 0

    @nn.compact
    def __call__(self, x, positions, block_tables=None,
                 write_locations=None, lora=None, adapter_ids=None,
                 layer=0, experts=None, slots=None):
        cfg = self.cfg
        lora = lora or {}
        norm = lambda name: RMSNorm(cfg.dtype, cfg.norm_eps, name=name)
        # what a mixer or an FFN gives, as it joins the residual
        scaled = (lambda y: y) if cfg.residual_multiplier == 1.0 \
            else (lambda y: y * cfg.residual_multiplier)

        def sp_shard(y):
            """Sequence-dim activation sharding between matmul regions:
            over "model" for Megatron sp, over "ctx" when context-parallel
            (cp keeps the residual stream seq-sharded the whole way)."""
            if not cfg.sp and cfg.cp <= 1:
                return y
            from ..parallel.mesh import AXIS_CTX, AXIS_DATA, AXIS_MODEL
            from jax.sharding import PartitionSpec as P

            axis = AXIS_CTX if cfg.cp > 1 else AXIS_MODEL
            return jax.lax.with_sharding_constraint(
                y, P(AXIS_DATA, axis, None))

        x = sp_shard(x)
        counts = {}
        entered = x   # what an early router reads
        if self.kind in ("full", "window"):
            window = cfg.window if self.kind == "window" else 0
            with jax.named_scope("attn_window" if window else "attn_full"):
                x = x + scaled(Attention(
                    cfg, rotate=window > 0, window=window, name="attn")(
                        norm("ln1")(x), positions, block_tables,
                        write_locations, layer=layer))
            if window and cfg.decode:
                # What this layer's queries hold as context, what of
                # it they see, and the cached positions the call scored
                # for a live row: its view's width, whatever the row
                # holds (the engine's kfx_lm_window_* counters).
                held = jnp.where(positions >= 0, positions + 1, 0)
                scored = cfg.max_seq_len
                if cfg.kv_page_size > 0:
                    view, in_place = paged_view(cfg, *x.shape[:2], window,
                                                block_tables.shape[1])
                    scored = cfg.kv_page_size * (
                        cfg.window_pages if in_place else view)
                counts["window"] = jnp.stack(
                    [jnp.sum(held), jnp.sum(jnp.minimum(held, window)),
                     scored * jnp.sum(jnp.any(positions >= 0, 1))])
        elif self.kind == "mamba":
            from .ssm import Mamba2

            y, counts["ssm"] = Mamba2(cfg, name="ssm")(
                norm("ln1")(x), positions, slots, layer)
            x = x + scaled(y)
        elif cfg.kv_lora_rank > 0:
            from .latent import LatentAttention

            y, seen = LatentAttention(cfg, name="attn")(
                norm("ln1")(x), positions, block_tables, write_locations,
                layer)
            x = x + y
            if seen is not None:
                counts["sparse"] = seen
        else:
            x = x + scaled(Attention(cfg, name="attn")(
                norm("ln1")(x), positions, block_tables,
                write_locations, lora.get("attn"), adapter_ids, layer))
        x = sp_shard(x)
        h = norm("ln2")(x)
        if cfg.runs_experts(self.kind):
            from .experts import RoutedExperts

            y, counts["moe"] = RoutedExperts(cfg, name="moe")(
                h, positions >= 0, *experts,
                layer + self.first_expert_layer
                if self.first_expert_layer else layer,
                entered if cfg.early_router else None)
            return x + y, counts
        if cfg.n_experts > 0 and not self.kind:
            x = x + MoEFFN(cfg, name="moe")(h)
        else:
            x = x + scaled(DenseFFN(cfg, name="mlp")(
                h, lora.get("mlp"), adapter_ids))
        return x, counts or None


class TransformerLM(nn.Module):
    """Returns logits [B, S, vocab]. Call with tokens [B, S] (int32)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None,
                 return_hidden: bool = False, block_tables=None,
                 write_locations=None, lora=None, adapter_ids=None,
                 slots=None, window_tables=None):
        cfg = self.cfg
        # ``slots`` [B]: each row's slot in the leaves indexed by slot
        # (models/ssm.py; None: row i is slot i). ``window_tables``: a
        # row's block table in the "window" runs' own pool, indexed by
        # position (a page holds positions, not locations, there).
        # Multi-tenant LoRA serving args (serving/adapters.py): ``lora``
        # is the per-projection adapter STACK pytree (leaves carry a
        # leading layers axis the scan slices) and ``adapter_ids`` [B]
        # selects each batch row's slot (-1 = base-only). Empty/None
        # means no adapter machinery: the traced graph is byte-for-byte
        # the pre-adapter program.
        lora = lora or {}
        if lora and adapter_ids is None:
            adapter_ids = jnp.full((tokens.shape[0],), -1, jnp.int32)
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        if cfg.cp > 1:
            # Context-parallel lookup as a one-hot einsum instead of a
            # gather: with tokens pinned to the (data, ctx) layout and the
            # table sharded (vocab→model, embed→data under fsdp), SPMD
            # cannot partition the gather without involuntarily
            # rematerialising the full activation; the einsum shards
            # cleanly (contraction over vocab → psum over "model") and
            # rides the MXU besides.
            from ..parallel.mesh import AXIS_CTX, AXIS_DATA
            from jax.sharding import PartitionSpec as P

            tokens = jax.lax.with_sharding_constraint(
                tokens, P(AXIS_DATA, AXIS_CTX))
            one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
            x = jnp.einsum("bsv,vd->bsd", one_hot,
                           embed.embedding.astype(cfg.dtype))
            x = jax.lax.with_sharding_constraint(
                x, P(AXIS_DATA, AXIS_CTX, None))
        else:
            x = embed(tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)

        block = Block
        if cfg.remat:
            policies = {
                "nothing": None,
                "dots": jax.checkpoint_policies.dots_saveable,
                "dots_no_batch":
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                # Keep every fat matmul output, recompute the cheap
                # elementwise chain and the O(S^2) score block — the
                # sweet spot when full activations don't fit but the
                # linear-in-S tensors do.
                # Measured dead end, recorded to save the next tuner the
                # experiment: a narrower tag set (projections + FFN
                # outputs, skipping the fat mlp_wi) FITS at S=2048 but
                # measured ~1% SLOWER than full remat there — the flash
                # backward recomputes its own block regardless, so the
                # partial saves only add HBM traffic.
                "save_dense": jax.checkpoint_policies.save_only_these_names(
                    "attn_q", "attn_k", "attn_v", "attn_out",
                    "mlp_wi", "mlp_wo", "moe_wi", "moe_wo"),
                # Long-context policies, composed with the flash kernel:
                # keep the kernel's own residuals (output + log-sum-exp,
                # O(B·S·D) — the linear-in-S memory that is flash
                # attention's point) so the remat backward runs only the
                # two flash bwd kernels; full remat re-runs the fwd
                # kernel first, and save_dense's save set never included
                # (o, lse) so the fwd re-ran anyway. save_flash also
                # keeps the q/k/v projections the bwd kernels consume;
                # the wider set with attn_out+mlp_wo measured 18.02G —
                # 2.28G over the v5e's 15.75G at base/b8/S=2048
                # (BASELINE.md HBM table).
                "save_flash": jax.checkpoint_policies.save_only_these_names(
                    "attn_q", "attn_k", "attn_v", "flash_o", "flash_lse"),
                # Minimal variant: only the kernel residuals; q/k/v are
                # recomputed from the layer input (3 thin matmuls + rope).
                "save_flash_min":
                    jax.checkpoint_policies.save_only_these_names(
                        "flash_o", "flash_lse"),
                # Widest flash set that fits at base/b8/S=2048 (15.2G
                # measured — the flat [B,S,H*D] tags are what make it
                # fit; loss_chunk is NOT needed, the logits transient is
                # not at the HBM peak): backward recomputes only
                # ln/rope/SwiGLU elementwise and the mlp_wi matmul.
                "save_flash_full":
                    jax.checkpoint_policies.save_only_these_names(
                        "attn_q", "attn_k", "attn_v", "attn_out",
                        "mlp_wo", "flash_o", "flash_lse"),
            }
            if cfg.remat_policy.startswith("save_names:"):
                # Ad-hoc save set ("save_names:attn_k,attn_v,flash_o"):
                # the HBM-frontier probes (BASELINE.md ladder) walk tag
                # subsets without a named policy per experiment.
                names = [n for n in
                         cfg.remat_policy.split(":", 1)[1].split(",") if n]
                policy = jax.checkpoint_policies.save_only_these_names(
                    *names)
            else:
                try:
                    policy = policies[cfg.remat_policy]
                except KeyError:
                    raise ValueError(
                        f"unknown remat_policy {cfg.remat_policy!r} "
                        f"(have {sorted(policies)})") from None
            kw = {"policy": policy} if policy is not None else {}
            block = nn.remat(Block, prevent_cse=False, **kw)
        if cfg.kv_page_size > 0 and write_locations is None:
            write_locations = positions
        # positions/tables/ids broadcast to every layer; the lora
        # stacks carry a leading layers axis the scan slices (each
        # layer sees ITS adapters' factors — in_axes=0).
        args = (positions, block_tables, write_locations, lora,
                adapter_ids)
        in_axes = (nn.broadcast, nn.broadcast, nn.broadcast, 0,
                   nn.broadcast)
        if cfg.decode:
            # The KV cache is CARRIED through the layer loop, whole,
            # and each layer is told its index: a collection scanned
            # over its layers axis comes out of the loop as a fresh
            # stack, which costs a second copy of every pool and its
            # movement every token (Attention._decode_attend). The
            # train step has no cache and keeps its program as it was.
            in_axes += (0,)
        counts = {}
        experts, expert_layers = None, 0
        for name, kind, n in cfg.layer_runs:
            run_args, run_axes = args, in_axes
            if kind == "window" and cfg.kv_page_size > 0:
                run_args = (positions, window_tables, positions) + args[3:]
            if cfg.decode:
                run_args += (jnp.arange(n, dtype=jnp.int32),)
            if kind == "mamba":
                if not cfg.decode:
                    run_args += (jnp.arange(n, dtype=jnp.int32),)
                    run_axes += (0,)
                run_args += (None, slots)
                run_axes += (nn.broadcast, nn.broadcast)
            first_expert_layer = expert_layers
            if cfg.runs_experts(kind):
                # The held experts of every expert layer, all runs', in
                # one stack a matrix, outside the scans (models/
                # experts.py); a run's layers lie there in the stack's
                # order, from ``first_expert_layer`` on.
                held, F = cfg.held_experts[1], cfg.expert_d_ff
                stack = lambda name, *shape: self.param(
                    name, nn.initializers.lecun_normal(),
                    (cfg.expert_layers, held) + shape, cfg.param_dtype)
                if experts is None:
                    experts = (stack("expert_wi", cfg.d_model, 2 * F),
                               stack("expert_wo", F, cfg.d_model))
                expert_layers += n
                if not cfg.decode:
                    run_args += (jnp.arange(n, dtype=jnp.int32),)
                    run_axes += (0,)
                run_args += (experts,)
                run_axes += (nn.broadcast,)
            ScanBlock = nn.scan(
                block,
                variable_axes={"params": 0, "aux_loss": 0},
                variable_carry="cache" if cfg.decode else False,
                split_rngs={"params": True},
                in_axes=run_axes,
                length=n,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            x, ys = ScanBlock(cfg, *((kind,) if kind else ()), *(
                (first_expert_layer,) if first_expert_layer else ()),
                name=name)(x, *run_args)
            for what, per_layer in (ys or {}).items():
                counts.setdefault(what, []).append(per_layer)
        # What this call's layers counted, for the engine's counters:
        # the routed experts' (models/experts.py COUNTS) summed, the
        # selection's (models/latent.py COUNTS) a layer: a layer's sum
        # of positions fits int32, the stack's does not.
        if "moe" in counts:
            self.sow("counts", "moe", sum(c.sum(0) for c in counts["moe"]))
        if "sparse" in counts:
            self.sow("counts", "sparse", jnp.concatenate(counts["sparse"]))
        if "window" in counts:   # Block, a layer
            self.sow("counts", "window", jnp.concatenate(counts["window"]))
        if "ssm" in counts:   # models/ssm.py COUNTS, summed
            self.sow("counts", "ssm", sum(c.sum(0) for c in counts["ssm"]))

        x = RMSNorm(cfg.dtype, cfg.norm_eps, name="ln_f")(x)
        if return_hidden:
            # Big-vocab loss chunking (parallel/lm_train.py): the caller
            # applies lm_head per sequence chunk so the [B, S, vocab]
            # f32 logits (2.1G at base/b8/S=2048) never materialise
            # whole. lm_head params still exist (created at init via the
            # normal path); the train loop consumes them directly.
            return x
        if cfg.tie_embeddings:
            head = embed.attend
        elif cfg.quant == "int8":
            head = QuantDenseGeneral((cfg.vocab_size,), axis=(-1,),
                                     dtype=cfg.dtype, name="lm_head")
        else:
            head = nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            name="lm_head")
        logits = head(x).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


# ---------------------------------------------------------------------------
# Logical axes from param paths
# ---------------------------------------------------------------------------

_AXES_BY_SUFFIX: Dict[Tuple[str, ...], Tuple[Optional[str], ...]] = {
    ("embed", "embedding"): ("vocab", "embed"),
    ("attn", "query", "kernel"): ("embed", "heads", "kv"),
    ("attn", "key", "kernel"): ("embed", "heads", "kv"),
    ("attn", "value", "kernel"): ("embed", "heads", "kv"),
    ("attn", "out", "kernel"): ("heads", "kv", "embed"),
    ("mlp", "wi", "kernel"): ("embed", "mlp"),
    ("mlp", "wo", "kernel"): ("mlp", "embed"),
    ("moe", "gate", "kernel"): ("embed", None),
    ("expert_wi",): ("layers", "expert", "embed", "expert_mlp"),
    ("expert_wo",): ("layers", "expert", "expert_mlp", "embed"),
    ("moe", "wi"): ("expert", "embed", "expert_mlp"),
    ("moe", "wo"): ("expert", "expert_mlp", "embed"),
    ("lm_head", "kernel"): ("embed", "vocab"),
}


def param_logical_axes(params) -> Any:
    """Pytree (same structure as params) of logical-axis tuples.

    Layer-stacked params (under "layers", produced by nn.scan) get a
    leading "layers" axis prepended.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    leaves = []
    for path, leaf in flat:
        names = tuple(getattr(p, "key", str(p)) for p in path)
        stacked = any(n == "layers" or n.endswith("_layers")
                      for n in names)
        axes: Optional[Tuple[Optional[str], ...]] = None
        for suffix, spec in _AXES_BY_SUFFIX.items():
            if names[-len(suffix):] == suffix:
                axes = spec
                break
        if axes is None:
            # norms / biases / anything unmatched: replicated
            axes = (None,) * (leaf.ndim - (1 if stacked else 0))
        if stacked:
            axes = ("layers",) + axes
        assert len(axes) == leaf.ndim, (names, axes, leaf.shape)
        leaves.append(axes)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_transformer(**kw) -> TransformerLM:
    """Build a TransformerLM from config keywords. Not in the classifier
    registry: LMs take int token inputs and run through lm_runner /
    LMTrainLoop, not the image-classifier TrainLoop."""
    return TransformerLM(TransformerConfig(**kw))


def truncate_layers(params, n_layers: int):
    """Layer-truncated parameter view: the first ``n_layers`` of the
    scanned layer stack, with embed / ln_f / lm_head shared verbatim.
    This is the serving engine's DRAFT model for speculative decoding
    (Leviathan et al., ICML'23): a same-tokenizer, same-vocab prefix of
    the target whose early-exit logits propose tokens the full model
    verifies. Works because the params are layer-stacked by ``nn.scan``
    (one leading "layers" axis per leaf) — no per-layer module surgery.
    The slices are views; callers device_put their own copy."""
    if "layers" not in params:
        raise ValueError("params have no scanned 'layers' collection")
    stacked = jax.tree_util.tree_leaves(params["layers"])
    depth = stacked[0].shape[0] if stacked else 0
    if not 1 <= n_layers <= depth:
        raise ValueError(
            f"draft n_layers {n_layers} not in [1, {depth}]")
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda x: x[:n_layers], params["layers"])
    return out


# Named size presets (flagship ladder).
PRESETS: Dict[str, Dict[str, int]] = {
    "tiny": dict(d_model=128, n_heads=4, head_dim=32, n_layers=2, d_ff=512,
                 vocab_size=1024, max_seq_len=256),
    "small": dict(d_model=512, n_heads=8, head_dim=64, n_layers=8, d_ff=2048,
                  vocab_size=32_000, max_seq_len=2048),
    "base": dict(d_model=1024, n_heads=16, head_dim=64, n_layers=24,
                 d_ff=4096, vocab_size=32_000, max_seq_len=4096),
    "large": dict(d_model=2048, n_heads=16, head_dim=128, n_layers=24,
                  d_ff=8192, vocab_size=32_000, max_seq_len=4096),
}


def preset_config(name: str, **overrides) -> TransformerConfig:
    base = dict(PRESETS[name])
    base.update(overrides)
    return TransformerConfig(**base)
