"""Pipeline parallelism: GPipe-style microbatched training over the
"stage" mesh axis.

Hybrid-manual shard_map (manual over "stage" only, auto over
"data"/"model"): each stage holds n_layers/pp of the layer stack — the
"layers" leaves are sharded over "stage" at rest, so HBM holds only local
layers — while dp/fsdp/tp/sp inside a stage keep working through GSPMD
exactly as in the non-pipelined path. Activations move stage-to-stage via
``ppermute`` (ICI point-to-point); autodiff reverses the permutes for the
backward pipeline. Schedule: loop of M + pp - 1 ticks (GPipe; bubble
fraction (pp-1)/(M+pp-1)).

Correctness contract (tests/test_parallel.py): pp>1 losses/grads match the
pp=1 loop for identical params and batch.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import flax.linen as nn

from ..models.transformer import Block, RMSNorm, TransformerConfig
from .lm_train import LMHyperParams, LMTrainLoop
from .mesh import AXIS_DATA, AXIS_STAGE, MeshPlan


class PipelinedLMTrainLoop(LMTrainLoop):
    """LMTrainLoop with the loss evaluated through the stage pipeline.

    Params keep the exact pytree of TransformerLM (layer-stacked under
    "layers"), so checkpoints are interchangeable with the pp=1 loop; the
    only difference is their "layers"-axis sharding and the loss path.
    """

    def __init__(self, cfg: TransformerConfig, mesh, plan: MeshPlan,
                 hp: Optional[LMHyperParams] = None,
                 n_microbatches: Optional[int] = None):
        if plan.pp <= 1:
            raise ValueError("PipelinedLMTrainLoop requires plan.pp > 1")
        if cfg.n_layers % plan.pp:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by pp={plan.pp}")
        if cfg.sp:
            raise NotImplementedError("sp inside the pipelined loop is not "
                                      "supported yet; use sp with pp=1")
        self.n_micro = n_microbatches or 2 * plan.pp
        # Bypass the pp>1 guard in the parent ctor.
        self._pp_plan = plan
        super().__init__(cfg, mesh, MeshPlan(pp=1, dp=plan.dp, tp=plan.tp,
                                             fsdp=plan.fsdp), hp)
        self.plan = plan
        # Shard the layer stack over "stage" (parent rules replicate it).
        self.rules = dict(self.rules)
        self.rules["layers"] = AXIS_STAGE
        self._local_layers = cfg.n_layers // plan.pp
        self._state_shardings = None  # rebuilt with the stage rule

    # -- stage-local module pieces (names match TransformerLM) -------------
    def _stage_blocks(self):
        return nn.scan(
            Block,
            variable_axes={"params": 0, "aux_loss": 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=self._local_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(self.cfg, name="layers")

    def _loss_fn(self, params, tokens):
        """Pipelined forward + CE. tokens: [B, S+1]."""
        cfg = self.cfg
        M = self.n_micro
        B = tokens.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        mb = B // M
        tokens_mb = tokens.reshape(M, mb, tokens.shape[1])

        embed_mod = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="embed")
        blocks_mod = self._stage_blocks()
        lnf_mod = RMSNorm(cfg.dtype, name="ln_f")
        head_mod = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="lm_head")

        def pp_body(p_embed, p_layers, p_lnf, p_head, toks):
            stage = jax.lax.axis_index(AXIS_STAGE)
            nstage = jax.lax.axis_size(AXIS_STAGE)
            last = nstage - 1
            S = toks.shape[-1] - 1
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (mb, S))

            def tick(carry, t):
                act = carry
                idx = jnp.clip(t, 0, M - 1)
                inputs = toks[idx][:, :-1]
                x0 = embed_mod.apply({"params": p_embed}, inputs)
                x = jnp.where(stage == 0, x0, act)
                if cfg.n_experts:
                    (y, _), auxv = blocks_mod.apply(
                        {"params": p_layers}, x, positions,
                        mutable=["aux_loss"])
                    aux_sum = sum(jnp.sum(v)
                                  for v in jax.tree.leaves(auxv["aux_loss"]))
                else:
                    y, _ = blocks_mod.apply({"params": p_layers}, x,
                                            positions)
                    aux_sum = jnp.float32(0.0)
                # This stage does real work for microbatch t-stage only
                # when that index is in range (bubble ticks excluded).
                in_flight = t - stage
                aux_c = jnp.where((in_flight >= 0) & (in_flight < M),
                                  aux_sum, 0.0)

                out_t = t - last
                tgt_idx = jnp.clip(out_t, 0, M - 1)
                targets = toks[tgt_idx][:, 1:]
                z = lnf_mod.apply({"params": p_lnf}, y)
                logits = head_mod.apply({"params": p_head}, z)
                ce = jnp.mean(
                    _softmax_xent(logits.astype(jnp.float32), targets))
                acc = jnp.mean(
                    (logits.argmax(-1) == targets).astype(jnp.float32))
                valid = (stage == last) & (out_t >= 0) & (out_t < M)
                contrib = jnp.where(valid, ce, 0.0)
                acc_c = jnp.where(valid, acc, 0.0)

                perm = [(i, (i + 1) % nstage) for i in range(nstage)]
                act_next = jax.lax.ppermute(y, AXIS_STAGE, perm)
                return act_next, (contrib, acc_c, aux_c)

            act0 = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)
            _, (losses, accs, auxs) = jax.lax.scan(
                tick, act0, jnp.arange(M + nstage - 1))
            loss = jax.lax.psum(jnp.sum(losses), AXIS_STAGE) / M
            acc = jax.lax.psum(jnp.sum(accs), AXIS_STAGE) / M
            if cfg.n_experts:
                # Same normalisation as the pp=1 loop: mean over layers,
                # averaged over the M microbatch forwards.
                aux = jax.lax.psum(jnp.sum(auxs), AXIS_STAGE) / (
                    cfg.n_layers * M)
                loss = loss + self.hp.moe_aux_weight * aux
            return loss, acc

        p = params
        in_specs = (P(), P(AXIS_STAGE), P(), P(), P())
        # Hybrid-manual (manual over "stage", auto over data/model) is
        # what makes dp/tp/fsdp inside a stage keep riding GSPMD. When
        # every non-stage axis is trivial there is nothing for the auto
        # half to do, so go manual over the WHOLE mesh: identical
        # numerics, and the classic full-manual lowering.
        plan = self.plan
        axis_names = ({AXIS_STAGE} if plan.dp > 1 or plan.tp > 1
                      else set(self.mesh.axis_names))
        # check_vma=False: the VMA-tracking lowering of the backward
        # (pcast/scan/ppermute combination) crashes XLA:CPU; the untracked
        # lowering is correct and is what the equivalence test checks.
        fn = jax.shard_map(pp_body, mesh=self.mesh,
                           axis_names=axis_names,
                           in_specs=in_specs, out_specs=(P(), P()),
                           check_vma=False)
        return fn(p["embed"], p["layers"], p["ln_f"], p["lm_head"], tokens_mb)


def _softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


__all__ = ["PipelinedLMTrainLoop"]
