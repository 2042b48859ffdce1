"""Data-parallel training loop, GSPMD style.

TPU-first mechanics (vs the reference's in-container Horovod/DDP loops):
  * one global jit'd step over a `Mesh` with the batch sharded on the
    ``data`` axis and params replicated — XLA inserts the gradient
    all-reduce (the NCCL ring's job) over ICI/DCN;
  * donated state buffers so the optimizer update is in-place in HBM;
  * bfloat16 compute / float32 state;
  * per-process input shards assembled into global arrays with
    ``jax.make_array_from_process_local_data`` (multi-host safe).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.metrics import default_registry


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


@dataclasses.dataclass
class TrainMetrics:
    step: int
    loss: float
    accuracy: float
    seconds: float

    def line(self) -> str:
        """The stdout contract the metrics collector parses (SURVEY.md §5.5)."""
        return (f"step={self.step} loss={self.loss:.6f} "
                f"accuracy={self.accuracy:.6f} step_time={self.seconds:.4f}")


class TrainLoop:
    """Builds and runs the sharded step for a flax classifier model."""

    def __init__(self, model, learning_rate: float = 1e-3,
                 optimizer: str = "adam", weight_decay: float = 0.0,
                 mesh: Optional[Mesh] = None, seed: int = 0):
        self.model = model
        self.mesh = mesh or Mesh(np.array(jax.devices()), ("data",))
        self.seed = seed
        self.tx, self._hparams = _make_optimizer(optimizer, learning_rate,
                                                 weight_decay)
        self.repl = NamedSharding(self.mesh, P())          # replicated
        self.batch_sharding = NamedSharding(self.mesh, P("data"))
        # Stacked K-step batches: leading scan dim unsharded.
        self.chunk_sharding = NamedSharding(self.mesh, P(None, "data"))
        self._train_step = None
        self._train_many_fn = None
        self._eval_step = None
        # Device-data pipeline: compiled fns keyed by (generator identity,
        # chunk length, batch size); values pin the batch_fn so id() can
        # never be recycled while its compile is cached.
        self._device_fns: Dict[Any, Tuple[Any, Any, Any]] = {}
        # Device-placed batch_fn consts, one copy per batch_fn (see
        # train_steps_device).
        self._device_consts: Dict[int, Any] = {}
        self._device_key = jax.random.PRNGKey(seed + 1)
        # Step timing into the process registry (SURVEY.md §5.5): the
        # runner's stdout lines stay the collector contract, but the
        # registry gives in-process consumers (tests, embedded servers)
        # the same distribution without log parsing.
        obs = default_registry()
        self._obs_step = obs.histogram(
            "kfx_train_step_seconds",
            "Per-optimizer-step wall time (fused dispatches amortised).")
        self._obs_rate = obs.gauge(
            "kfx_train_examples_per_second",
            "Training throughput of the most recent dispatch.")
        # Several loops can share one process (model ladders, HPO
        # trials); the model label keeps their distributions apart.
        self._obs_model = type(model).__name__

    def _record_steps(self, seconds: float, n_steps: int,
                      batch_size: int) -> None:
        if seconds <= 0 or n_steps <= 0:
            return
        self._obs_step.observe(seconds / n_steps, n=n_steps,
                               model=self._obs_model)
        self._obs_rate.set(round(n_steps * batch_size / seconds, 2),
                           model=self._obs_model)

    # -- state -------------------------------------------------------------
    def init_state(self, sample_shape: Tuple[int, ...]) -> TrainState:
        def init() -> TrainState:
            rng = jax.random.PRNGKey(self.seed)
            dummy = jnp.zeros((1,) + tuple(sample_shape), jnp.float32)
            variables = self.model.init(rng, dummy, train=False)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              batch_stats=batch_stats,
                              opt_state=self.tx.init(params))

        # Materialize the state already replicated (out_shardings), not
        # via a host-side device_put: putting UNCOMMITTED host arrays
        # onto a cross-process sharding makes jax broadcast-and-assert
        # every leaf across hosts (multihost_utils.assert_equal) — a
        # gloo storm right after rendezvous that intermittently dies
        # with mismatched-message errors. Inside jit every process
        # computes the identical state deterministically and no
        # cross-host traffic happens at all.
        return jax.jit(init, out_shardings=self.repl)()

    def reapply_hyperparams(self, state: TrainState) -> TrainState:
        """Re-assert THIS loop's configured hyperparams over a restored
        opt_state. Checkpoints carry the hyperparams they were saved with
        (inject_hyperparams puts lr etc. in opt_state); on resume the
        CLI's values must win — the behavior lr had when it was a trace
        constant, and what an operator restarting with a new
        --learning-rate expects."""
        opt = state.opt_state
        if not hasattr(opt, "hyperparams"):
            return state
        new_hp = {k: (jnp.full_like(v, self._hparams[k])
                      if k in self._hparams else v)
                  for k, v in opt.hyperparams.items()}
        return state.replace(opt_state=opt._replace(hyperparams=new_hp))

    def legacy_checkpoint_layouts(self, state: TrainState):
        """Layout-migration triples for Checkpointer.restore_latest.

        Checkpoints written before hyperparameters moved into opt_state
        (optax.inject_hyperparams) stored the bare inner transformation's
        state where the wrapper state now sits. The inner pytree is
        unchanged — inject_hyperparams wraps, it does not restructure —
        so a legacy checkpoint restores into ``opt_state.inner_state``
        and is upgraded by grafting it back under a freshly initialised
        wrapper carrying THIS loop's configured hyperparams (which is
        also what reapply_hyperparams would assert)."""
        opt = state.opt_state
        if not hasattr(opt, "inner_state"):
            return []
        legacy_target = state.replace(opt_state=opt.inner_state)

        def upgrade(restored: TrainState) -> TrainState:
            wrapper = self.tx.init(restored.params)
            wrapper = wrapper._replace(inner_state=restored.opt_state)
            return restored.replace(opt_state=wrapper)

        return [("pre-hyperparam-injection", legacy_target, upgrade)]

    # -- steps -------------------------------------------------------------
    def _step_body(self):
        """The single SGD update (state, images, labels) -> (state, loss,
        acc) — shared by the per-step and scan-fused compiled forms."""
        model, tx = self.model, self.tx

        def loss_fn(params, batch_stats, images, labels):
            variables = {"params": params}
            if batch_stats:
                variables["batch_stats"] = batch_stats
            out = model.apply(variables, images, train=True,
                              mutable=["batch_stats"] if batch_stats else [])
            logits, new_stats = out if isinstance(out, tuple) else (out, {})
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            acc = (logits.argmax(-1) == labels).mean()
            return loss, (acc, new_stats.get("batch_stats", {}))

        def step(state: TrainState, images, labels):
            (loss, (acc, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.batch_stats,
                                       images, labels)
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            new_state = state.replace(step=state.step + 1, params=params,
                                      batch_stats=new_stats,
                                      opt_state=opt_state)
            return new_state, loss, acc

        return step

    def _build_train_step(self):
        return jax.jit(
            self._step_body(),
            in_shardings=(self.repl, self.batch_sharding, self.batch_sharding),
            out_shardings=(self.repl, self.repl, self.repl),
            donate_argnums=(0,),
        )

    def _build_train_many(self):
        """K steps per dispatch via lax.scan — identical updates to K calls
        of the single step, but one host→device round-trip. This is the
        difference between dispatch-bound and compute-bound wall-clock when
        the accelerator sits behind a high-latency link (and it removes
        K-1 dispatches on any hardware)."""
        step = self._step_body()

        def many(state: TrainState, images, labels):
            def one(state, batch):
                state, loss, acc = step(state, *batch)
                return state, (loss, acc)

            state, (losses, accs) = jax.lax.scan(one, state, (images, labels))
            return state, losses[-1], accs[-1]

        return jax.jit(
            many,
            in_shardings=(self.repl, self.chunk_sharding,
                          self.chunk_sharding),
            out_shardings=(self.repl, self.repl, self.repl),
            donate_argnums=(0,),
        )

    def _build_train_many_device(self, batch_fn, batch_size: int,
                                 n_steps: int):
        """K steps per dispatch where each step's batch is GENERATED on
        device by ``batch_fn(key, batch_size)`` — no input transfer at
        all (see data/synthetic.Dataset.device_batch_fn). Keys fold in
        the absolute step index, so restarts resume the same stream."""
        step = self._step_body()
        spec_x = self.batch_sharding
        spec_y = self.batch_sharding
        has_consts = getattr(batch_fn, "consts", None) is not None

        def many(state: TrainState, base_key, start_step, consts):
            def one(state, i):
                key = jax.random.fold_in(base_key, start_step + i)
                # `consts` are the batch_fn's device-resident tables
                # passed as jit arguments — a closure capture would bake
                # them into the program as constants (602M at ImageNet
                # geometry, breaking the remote-compile transport).
                if has_consts:
                    images, labels = batch_fn(consts, key, batch_size)
                else:
                    images, labels = batch_fn(key, batch_size)
                images = jax.lax.with_sharding_constraint(images, spec_x)
                labels = jax.lax.with_sharding_constraint(labels, spec_y)
                state, loss, acc = step(state, images, labels)
                return state, (loss, acc)

            state, (losses, accs) = jax.lax.scan(
                one, state, jnp.arange(n_steps))
            return state, losses[-1], accs[-1]

        return jax.jit(
            many,
            in_shardings=(self.repl, self.repl, self.repl, self.repl),
            out_shardings=(self.repl, self.repl, self.repl),
            donate_argnums=(0,),
        )

    def train_steps_device(self, state: TrainState, batch_fn,
                           batch_size: int, start_step: int, n_steps: int
                           ) -> Tuple[TrainState, float, float]:
        """Run n_steps with device-generated batches in one dispatch."""
        fn_key = (id(batch_fn), n_steps, batch_size)
        entry = self._device_fns.get(fn_key)
        if entry is None:
            # Place consts ONCE per batch_fn (not per chunk length — the
            # runner's chunk planner emits several k values for the same
            # fn, and each placement would pin its own replicated copy:
            # 602M apiece at ImageNet geometry). device_put commits to
            # the replicated sharding so dispatches never re-broadcast.
            ckey = id(batch_fn)
            if ckey not in self._device_consts:
                consts = getattr(batch_fn, "consts", None)
                if consts is not None:
                    consts = jax.device_put(consts, self.repl)
                self._device_consts[ckey] = consts
            entry = (batch_fn, self._device_consts[ckey],
                     self._build_train_many_device(
                         batch_fn, batch_size, n_steps))
            self._device_fns[fn_key] = entry
        _, consts, fn = entry
        t0 = time.perf_counter()
        state, loss, acc = fn(state, self._device_key,
                              jnp.int32(start_step), consts)
        loss, acc = float(loss), float(acc)  # sync before timing
        self._record_steps(time.perf_counter() - t0, n_steps, batch_size)
        return state, loss, acc

    def train_steps(self, state: TrainState, images: np.ndarray,
                    labels: np.ndarray) -> Tuple[TrainState, float, float]:
        """Run a [K, B, ...] stacked chunk in one dispatch."""
        if self._train_many_fn is None:
            self._train_many_fn = self._build_train_many()
        t0 = time.perf_counter()
        if jax.process_count() == 1:
            g_images = jax.device_put(images, self.chunk_sharding)
            g_labels = jax.device_put(labels, self.chunk_sharding)
        else:
            g_images = jax.make_array_from_process_local_data(
                self.chunk_sharding, images)
            g_labels = jax.make_array_from_process_local_data(
                self.chunk_sharding, labels)
        state, loss, acc = self._train_many_fn(state, g_images, g_labels)
        loss, acc = float(loss), float(acc)  # sync before timing
        self._record_steps(time.perf_counter() - t0, images.shape[0],
                           images.shape[1])
        return state, loss, acc

    def _build_eval_step(self):
        model = self.model

        def step(state: TrainState, images, labels):
            variables = {"params": state.params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            logits = model.apply(variables, images, train=False)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            acc = (logits.argmax(-1) == labels).mean()
            return loss, acc

        return jax.jit(
            step,
            in_shardings=(self.repl, self.batch_sharding, self.batch_sharding),
            out_shardings=(self.repl, self.repl),
        )

    # -- input assembly ----------------------------------------------------
    def global_batch(self, images: np.ndarray, labels: np.ndarray):
        """Assemble this process's shard into a global sharded array."""
        if jax.process_count() == 1:
            return (jax.device_put(images, self.batch_sharding),
                    jax.device_put(labels, self.batch_sharding))
        return (jax.make_array_from_process_local_data(self.batch_sharding, images),
                jax.make_array_from_process_local_data(self.batch_sharding, labels))

    # -- driving -----------------------------------------------------------
    def train_step(self, state: TrainState, images: np.ndarray,
                   labels: np.ndarray) -> Tuple[TrainState, float, float]:
        if self._train_step is None:
            self._train_step = self._build_train_step()
        t0 = time.perf_counter()
        g_images, g_labels = self.global_batch(images, labels)
        state, loss, acc = self._train_step(state, g_images, g_labels)
        loss, acc = float(loss), float(acc)  # sync before timing
        self._record_steps(time.perf_counter() - t0, 1, images.shape[0])
        return state, loss, acc

    def evaluate(self, state: TrainState, images: np.ndarray,
                 labels: np.ndarray, batch_size: int = 512) -> Dict[str, float]:
        """Evaluate over (process-local) arrays. In multi-process runs each
        process passes its own disjoint shard; metrics are averaged over the
        global batch by the sharded reduction inside the step."""
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        n_dev = self.mesh.size
        per = max(batch_size // n_dev, 1) * n_dev
        losses, accs, count = [], [], 0
        for i in range(0, len(images) - per + 1, per):
            li, ll = images[i:i + per], labels[i:i + per]
            g_images = jax.device_put(li, self.batch_sharding) \
                if jax.process_count() == 1 else \
                jax.make_array_from_process_local_data(self.batch_sharding, li)
            g_labels = jax.device_put(ll, self.batch_sharding) \
                if jax.process_count() == 1 else \
                jax.make_array_from_process_local_data(self.batch_sharding, ll)
            loss, acc = self._eval_step(state, g_images, g_labels)
            losses.append(float(loss))
            accs.append(float(acc))
            count += per
        return {"loss": float(np.mean(losses)) if losses else float("nan"),
                "accuracy": float(np.mean(accs)) if accs else float("nan"),
                "count": count}


def _make_optimizer(name: str, lr: float, weight_decay: float
                    ) -> Tuple[optax.GradientTransformation, Dict[str, float]]:
    """Returns (transformation, configured hyperparams).

    Hyperparameters ride in opt_state as runtime values
    (optax.inject_hyperparams), NOT as trace constants: every HPO trial
    then reuses ONE compiled step from the persistent cache instead of
    recompiling per sampled learning rate (1-3s XLA:CPU / 5-15s XLA:TPU
    compile per distinct lr in a Katib sweep; not a ledger number).
    The configured values are returned alongside so a checkpoint resume
    can re-assert them over the checkpointed ones
    (TrainLoop.reapply_hyperparams)."""
    name = name.lower()
    if name == "adam":
        hp = {"learning_rate": lr}
        return optax.inject_hyperparams(optax.adam)(**hp), hp
    if name == "adamw":
        hp = {"learning_rate": lr, "weight_decay": weight_decay or 1e-4}
        return optax.inject_hyperparams(optax.adamw)(**hp), hp
    if name == "sgd":
        hp = {"learning_rate": lr, "momentum": 0.9}
        return optax.inject_hyperparams(optax.sgd)(**hp), hp
    if name == "lamb":
        hp = {"learning_rate": lr, "weight_decay": weight_decay}
        return optax.inject_hyperparams(optax.lamb)(**hp), hp
    raise KeyError(f"unknown optimizer {name!r} (adam|adamw|sgd|lamb)")
