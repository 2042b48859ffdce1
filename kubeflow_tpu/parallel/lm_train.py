"""Sharded LM training: one jit'd step over the ("stage","data","model")
mesh with dp + fsdp + tp + sp + ep expressed as shardings.

GSPMD does the heavy lifting (scaling-book recipe): parameters carry
NamedShardings from `parallel.mesh` rules, the batch is sharded over
"data", sequence-parallel constraints live inside the model, and XLA
inserts every collective — gradient reduce-scatters for fsdp, all-reduces
for tp, all-to-alls for ep. Nothing here calls a collective by hand.

One place says *where* a collective goes, still as a sharding: the
chunked loss (``_chunked_ce``) constrains the cast head to its spec
without "data" before its chunk loop and sums the head's gradient per
data shard inside it, so the head is gathered once a step and its
gradient reduced once, not once a chunk each way. That function also
carries the file's one hand-written differentiation rule: the loss and
its gradients come out of the same pass over the chunks.

bf16 compute / f32 state, donated buffers, global-norm clipping, cosine
schedule with warmup, MoE load-balance aux loss.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as obs_trace
from ..obs.metrics import default_registry
from ..models.transformer import (
    TransformerConfig,
    TransformerLM,
    param_logical_axes,
)
from .mesh import (
    AXIS_CTX,
    AXIS_DATA,
    MeshPlan,
    param_sharding_rules,
    tree_shardings,
)


class LMTrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


@dataclasses.dataclass
class LMHyperParams:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moe_aux_weight: float = 0.01
    seed: int = 0


def _opt_state_shardings(abs_opt_state, params_struct, params_shardings,
                         repl: NamedSharding):
    """Shard optimizer state: subtrees mirroring the param tree (adam mu/nu)
    inherit param shardings; scalar leaves (counts) replicate."""

    def rec(node):
        try:
            if jax.tree_util.tree_structure(node) == params_struct:
                return params_shardings
        except Exception:  # pragma: no cover - defensive
            pass
        if hasattr(node, "_fields"):  # namedtuple (optax states)
            return type(node)(*(rec(getattr(node, f)) for f in node._fields))
        if isinstance(node, (tuple, list)):
            return type(node)(rec(c) for c in node)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return repl

    return rec(abs_opt_state)


class LMTrainLoop:
    """Owns model/optimizer/step for a given mesh + plan."""

    def __init__(self, cfg: TransformerConfig, mesh: Mesh, plan: MeshPlan,
                 hp: Optional[LMHyperParams] = None):
        if plan.pp > 1:
            raise NotImplementedError(
                "pp>1 runs through parallel.pipeline.PipelinedLMTrainLoop")
        if cfg.cp != plan.cp and (cfg.cp > 1 or plan.cp > 1):
            raise ValueError(
                f"cfg.cp={cfg.cp} must match the mesh plan's cp={plan.cp}")
        if cfg.cp > 1 and cfg.sp:
            raise ValueError("sp and cp both shard the sequence dim; "
                             "enable at most one")
        self.cfg = cfg
        self.mesh = mesh
        self.plan = plan
        self.hp = hp or LMHyperParams()
        self.model = TransformerLM(cfg)
        self.rules = param_sharding_rules(plan)
        self.repl = NamedSharding(mesh, P())
        # Raw [B, S+1] token batches shard over "data" only (S+1 rarely
        # divides cp); the sliced [B, S] inputs/targets are constrained
        # onto "ctx" inside the loss, so cp shards every activation.
        self.batch_sharding = NamedSharding(mesh, P(AXIS_DATA, None))

        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.hp.learning_rate, self.hp.warmup_steps,
            max(self.hp.total_steps, self.hp.warmup_steps + 1))
        self.tx = optax.chain(
            optax.clip_by_global_norm(self.hp.grad_clip),
            optax.adamw(schedule, b1=0.9, b2=0.95,
                        weight_decay=self.hp.weight_decay),
        )
        self._state_shardings = None
        self._train_step = None
        self._eval_step = None
        # Steps dispatched by this loop: the step number the profiler's
        # trace carries (a resumed run counts from its resume).
        self._dispatched = 0
        # This process drives devices: its spans go into the profiler's
        # trace too (obs/trace.py).
        obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
        # Step-time + MFU observability on the process registry (same
        # contract as training/loop.py's classifier TrainLoop): stdout
        # lines stay the collector interface, the registry gives
        # in-process consumers — and the plane's /metrics bridge — the
        # same numbers scrape-style. MFU uses the utils.flops
        # convention (model FLOPs, remat recompute not credited)
        # against the attached chip's published peak, over every chip
        # in this loop's mesh; a device without one gets no MFU.
        obs = default_registry()
        self._obs_step = obs.histogram(
            "kfx_train_step_seconds",
            "Per-optimizer-step wall time (fused dispatches amortised).")
        self._obs_mfu = obs.gauge(
            "kfx_train_mfu",
            "Model FLOPs utilisation of the most recent training "
            "dispatch (fraction of the mesh's peak bf16 FLOP/s).")
        # Labels resolved lazily at first record: the pipelined subclass
        # swaps self.plan after this ctor runs, and the label must name
        # the REAL plan (pp included).
        self._obs_labels: Optional[Dict[str, str]] = None
        self._flops_per_token: Optional[float] = None

    def _record_steps(self, seconds: float, n_steps: int, n_tokens: int,
                      seq_len: int) -> None:
        if seconds <= 0 or n_steps <= 0 or n_tokens <= 0:
            return
        if self._obs_labels is None:
            plan, cfg = self.plan, self.cfg
            self._obs_labels = {
                "job": os.environ.get("KFX_JOB_NAME", "local"),
                "config": (f"pp{plan.pp}/dp{plan.dp}/cp{plan.cp}/"
                           f"tp{plan.tp}"
                           + ("/fsdp" if plan.fsdp else "")
                           + f"-d{cfg.d_model}L{cfg.n_layers}"),
            }
        self._obs_step.observe(seconds / n_steps, n=n_steps,
                               **self._obs_labels)
        from ..utils.flops import (
            PEAK_FLOPS, mfu, transformer_train_flops_per_token)

        # MFU is a device metric: only where the mesh's chips have a
        # published peak (never on the CPU backend).
        peak = PEAK_FLOPS.get(self.mesh.devices.flat[0].device_kind)
        if peak is None:
            return
        if self._flops_per_token is None:
            self._flops_per_token = transformer_train_flops_per_token(
                self.cfg, seq_len)
        self._obs_mfu.set(
            round(mfu(n_tokens / seconds, self._flops_per_token,
                      n_chips=self.mesh.size, peak=peak), 6),
            **self._obs_labels)

    # -- state --------------------------------------------------------------
    def _init_fn(self, rng):
        # The sample only shapes the params, but with cp>1 the in-model
        # shard_map requires the sample itself to divide the mesh: batch
        # over "data", seq over "ctx".
        s = min(self.cfg.max_seq_len, 8)
        s = ((s + self.plan.cp - 1) // self.plan.cp) * self.plan.cp
        sample = jnp.zeros((self.plan.dp, s), jnp.int32)
        variables = self.model.init(rng, sample)
        params = variables["params"]
        return LMTrainState(step=jnp.zeros((), jnp.int32), params=params,
                            opt_state=self.tx.init(params))

    def state_shardings(self) -> LMTrainState:
        if self._state_shardings is None:
            # Trace under the mesh: the model's cp/sp paths contain bare-
            # PartitionSpec sharding constraints that need an ambient mesh.
            # (An abstract key: nothing here touches a device, so the
            # mesh may be one that is only described, as in an AOT compile.)
            with jax.set_mesh(self.mesh):
                abs_state = jax.eval_shape(
                    self._init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
            axes = param_logical_axes(abs_state.params)
            params_sh = tree_shardings(self.mesh, axes, self.rules,
                                       abs_state.params)
            opt_sh = _opt_state_shardings(
                abs_state.opt_state,
                jax.tree_util.tree_structure(abs_state.params),
                params_sh, self.repl)
            self._state_shardings = LMTrainState(
                step=self.repl, params=params_sh, opt_state=opt_sh)
        return self._state_shardings

    def init_state(self) -> LMTrainState:
        """Initialise directly into the sharded layout (no host round-trip;
        each device materialises only its shard)."""
        def kfx_init_state(rng):  # the program's name in a trace
            return self._init_fn(rng)

        with jax.set_mesh(self.mesh):
            init = jax.jit(kfx_init_state,
                           out_shardings=self.state_shardings())
            return init(jax.random.PRNGKey(self.hp.seed))

    # -- loss ---------------------------------------------------------------
    def _chunked_ce(self, params, hidden, targets):
        """lm_head + CE per sequence chunk (cfg.loss_chunk tokens) in ONE
        lax.scan that also makes the gradients: the [B, S, vocab] f32
        logits never exist whole — only one [B, C, vocab] transient at a
        time — and each chunk's logits are computed once. Returns (mean
        ce, mean accuracy). Same math as the nn.Dense it replaces
        (use_bias=False, cfg.dtype matmul inputs, f32 softmax).

        What does not depend on the chunk stays out of the loop: the
        head is cast to cfg.dtype and gathered over the fsdp axis once
        (``loss_head_gather``; the constraint drops "data" from the
        kernel's spec and nothing else, so without fsdp it is a no-op
        and a tp-sharded vocabulary stays sharded), and the head's
        gradient is summed over chunks per data shard in f32 (a leading
        ``dp`` axis sharded over "data") and reduced across chips once,
        after the loop.

        The differentiation rule is written by hand because autodiff
        cannot give that: it would rerun the logits matmul in the
        backward loop, and a cfg.dtype operand hoisted out of the scan
        would have its cotangent summed in cfg.dtype. The forward rule
        computes ``softmax - onehot``, ``dh`` and ``h^T dlogits`` beside
        the loss; the backward rule only scales them by the incoming
        cotangent. Undifferentiated (``evaluate``) the same loop runs
        without the gradient half."""
        cfg = self.cfg
        C = cfg.loss_chunk
        B, S, D = hidden.shape
        if S % C:
            raise ValueError(f"seq len {S} not divisible by "
                             f"loss_chunk={C}")
        n, dp = S // C, self.plan.dp
        kernel = params["lm_head"]["kernel"]
        head = {"lm_head": {"kernel": kernel}}  # the spec the state gave it
        kernel_spec = tree_shardings(
            self.mesh, param_logical_axes(head), self.rules,
            head)["lm_head"]["kernel"].spec
        vocab_axis = kernel_spec[1]
        cons = lambda x, *spec: jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

        def chunks(kernel, hidden, targets, with_grads):
            with jax.named_scope("loss_head_gather"):
                w = cons(kernel.astype(cfg.dtype), None, vocab_axis)
            h = hidden.reshape(B, n, C, D).transpose(1, 0, 2, 3)  # [n,B,C,D]
            # Rows stay on their data shard and whole in D: left to its
            # propagation, XLA may carry ln_f's fsdp shard of D into the
            # loop and gather the head there again.
            h = cons(h, P.UNCONSTRAINED, AXIS_DATA, P.UNCONSTRAINED, None)
            t = targets.reshape(B, n, C).transpose(1, 0, 2)

            def body(carry, xs):
                h_c, t_c = xs
                h_c = h_c.astype(cfg.dtype)
                with jax.named_scope("loss_chunk"):
                    logits = jnp.einsum(
                        "bcd,dv->bcv", h_c, w).astype(jnp.float32)
                    logz = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
                    onehot = jax.nn.one_hot(t_c, logits.shape[-1],
                                            dtype=jnp.bool_)
                    ce = logz[..., 0] - jnp.sum(
                        jnp.where(onehot, logits, 0.0), axis=-1)
                    hit = (logits.argmax(-1) == t_c).astype(jnp.float32)
                    ce_s, hit_s = carry[0] + ce.sum(), carry[1] + hit.sum()
                    if not with_grads:
                        return (ce_s, hit_s), None
                    dlogits = (jnp.exp(logits - logz)
                               - onehot.astype(jnp.float32)
                               ).astype(cfg.dtype)
                    dh_c = jnp.einsum("bcv,dv->bcd", dlogits, w,
                                      preferred_element_type=jnp.float32)
                    # Per data shard: no chip adds another's rows here.
                    dw = carry[2] + jnp.einsum(
                        "pbcd,pbcv->pdv",
                        h_c.reshape(dp, B // dp, C, D),
                        dlogits.reshape(dp, B // dp, C, -1),
                        preferred_element_type=jnp.float32)
                return (ce_s, hit_s, dw), dh_c

            init = (jnp.float32(0.0), jnp.float32(0.0))
            if not with_grads:
                return jax.lax.scan(body, init, (h, t))[0], None
            dw0 = cons(jnp.zeros((dp,) + kernel.shape, jnp.float32),
                       AXIS_DATA, None, vocab_axis)
            (ce_s, hit_s, dw), dh = jax.lax.scan(body, init + (dw0,), (h, t))
            dw = cons(dw.sum(0), *kernel_spec)
            # The backward pass starts from dh: held behind the same
            # barrier, it cannot start before the reduction has freed
            # the per-shard sums (left free, the scheduler keeps them
            # through the whole backward layer scan).
            dw, dh = jax.lax.optimization_barrier((dw, dh))
            dh = dh.transpose(1, 0, 2, 3).reshape(B, S, D)
            return (ce_s, hit_s), (dw, dh)

        @jax.custom_vjp
        def sums(kernel, hidden, targets):
            return chunks(kernel, hidden, targets, with_grads=False)[0]

        def sums_fwd(kernel, hidden, targets):
            return chunks(kernel, hidden, targets, with_grads=True)

        def sums_bwd(grads, cotangents):
            dw, dh = grads
            g = cotangents[0]  # of the summed ce; hits carry no gradient
            return ((dw * g).astype(kernel.dtype),
                    (dh * g).astype(hidden.dtype), None)

        sums.defvjp(sums_fwd, sums_bwd)
        ce_sum, hit_sum = sums(kernel, hidden, targets)
        total = B * S
        return ce_sum / total, hit_sum / total

    def _loss_fn(self, params, tokens):
        """tokens: [B, S+1] int32 (inputs || shifted targets)."""
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if self.cfg.cp > 1:
            cons = lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(AXIS_DATA, AXIS_CTX)))
            inputs, targets = cons(inputs), cons(targets)
        chunked = self.cfg.loss_chunk > 0
        outputs = self.model.apply(
            {"params": params}, inputs, return_hidden=chunked,
            mutable=["aux_loss"] if self.cfg.n_experts else [])
        out, aux = outputs if isinstance(outputs, tuple) else (outputs, {})
        if chunked:
            loss, acc = self._chunked_ce(params, out, targets)
        else:
            ce = optax.softmax_cross_entropy_with_integer_labels(out,
                                                                 targets)
            loss = ce.mean()
            acc = (out.argmax(-1) == targets).mean()
        if self.cfg.n_experts:
            aux_vals = jax.tree.leaves(aux.get("aux_loss", {}))
            moe_aux = sum(jnp.sum(v) for v in aux_vals) / max(
                self.cfg.n_layers, 1)
            loss = loss + self.hp.moe_aux_weight * moe_aux
        return loss, acc

    # -- steps --------------------------------------------------------------
    def _build_train_step(self):
        # Named for the profiler's trace: the program is
        # jit_kfx_train_step there, not one more jit_step.
        def kfx_train_step(state: LMTrainState, tokens):
            (loss, acc), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(state.params, tokens)
            updates, opt_state = self.tx.update(grads, state.opt_state,
                                                state.params)
            params = optax.apply_updates(state.params, updates)
            new_state = LMTrainState(step=state.step + 1, params=params,
                                     opt_state=opt_state)
            return new_state, loss, acc

        sh = self.state_shardings()
        return jax.jit(kfx_train_step,
                       in_shardings=(sh, self.batch_sharding),
                       out_shardings=(sh, self.repl, self.repl),
                       donate_argnums=(0,))

    def _build_eval_step(self):
        def kfx_eval_step(params, tokens):
            return self._loss_fn(params, tokens)

        sh = self.state_shardings()
        return jax.jit(kfx_eval_step,
                       in_shardings=(sh.params, self.batch_sharding),
                       out_shardings=(self.repl, self.repl))

    # -- driving ------------------------------------------------------------
    def global_batch(self, tokens: np.ndarray):
        if jax.process_count() == 1:
            return jax.device_put(tokens, self.batch_sharding)
        return jax.make_array_from_process_local_data(self.batch_sharding,
                                                      tokens)

    def train_step(self, state: LMTrainState, tokens: np.ndarray
                   ) -> Tuple[LMTrainState, float, float]:
        return self.train_many(state, [tokens])

    def train_many(self, state: LMTrainState, batches
                   ) -> Tuple[LMTrainState, float, float]:
        """Run a sequence of token batches with ONE host sync at the end.

        train_step() syncs (device_get) per step, which drains the
        dispatch queue each step; here all steps are dispatched
        back-to-back and only the final loss is fetched."""
        compiled_this_call = self._train_step is None
        if compiled_this_call:
            self._train_step = self._build_train_step()
        loss = acc = None
        n_steps = n_tokens = seq_len = 0
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            for tokens in batches:
                seq_len = tokens.shape[1] - 1
                n_tokens += tokens.shape[0] * seq_len
                n_steps += 1
                # Marks the step's DISPATCH on the host (the call
                # returns before the device is done); the device time
                # is the program's, jit_kfx_train_step.
                with jax.profiler.StepTraceAnnotation(
                        "kfx_train_step", step_num=self._dispatched):
                    state, loss, acc = self._train_step(
                        state, self.global_batch(tokens))
                self._dispatched += 1
            if loss is None:
                raise ValueError("train_many needs at least one batch")
        loss, acc = float(loss), float(acc)  # device sync before timing
        if not compiled_this_call:
            # The compile-paying call would poison the step-time
            # distribution and report a near-zero MFU for a one-off
            # cost; the steady-state windows are the signal.
            self._record_steps(time.perf_counter() - t0, n_steps,
                               n_tokens, seq_len)
        return state, loss, acc

    def evaluate(self, state: LMTrainState, tokens: np.ndarray
                 ) -> Dict[str, float]:
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        with jax.set_mesh(self.mesh):
            loss, acc = self._eval_step(state.params,
                                        self.global_batch(tokens))
        return {"loss": float(loss), "accuracy": float(acc)}
