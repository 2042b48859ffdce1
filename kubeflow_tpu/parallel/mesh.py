"""Device mesh construction and parameter sharding rules.

Follows the scaling-book recipe: a named mesh over the slice, logical
axis names on every parameter, and a rules table mapping logical names to
mesh axes. XLA reads the shardings and inserts the collectives (psum /
all-gather / reduce-scatter) — nothing here issues a collective by hand.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


AXIS_STAGE = "stage"   # pipeline (pp)
AXIS_DATA = "data"     # batch (dp) + fsdp param shards + experts (ep)
AXIS_CTX = "ctx"       # context parallelism (cp): sequence via ring attention
AXIS_MODEL = "model"   # tensor (tp) + sequence (sp) activation shards


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A parallelism plan: how many ways along each mesh axis.

    fsdp is not a mesh axis — it reuses "data" (ZeRO-3 style: parameters
    sharded over the data-parallel group, all-gathered per layer by XLA).
    Likewise experts (ep) place the expert dimension on "data", and
    sequence parallelism (sp) reuses "model" for activation shards.
    Context parallelism (cp) has its own axis: the sequence dim of
    activations and K/V shards over "ctx", with ring attention rotating
    K/V chunks between ctx neighbours (parallel/ring_attention.py).
    """

    pp: int = 1
    dp: int = 1
    cp: int = 1
    tp: int = 1
    fsdp: bool = False  # shard params along "data" too

    @property
    def n_devices(self) -> int:
        return self.pp * self.dp * self.cp * self.tp

    def axis_sizes(self) -> Dict[str, int]:
        return {AXIS_STAGE: self.pp, AXIS_DATA: self.dp,
                AXIS_CTX: self.cp, AXIS_MODEL: self.tp}


def _factor(n: int, want_tp: Optional[int], want_pp: Optional[int],
            want_cp: Optional[int]) -> Tuple[int, int, int, int]:
    """Choose (pp, dp, cp, tp) for n devices; dp absorbs the rest."""
    pp = want_pp or 1
    if n % pp:
        raise ValueError(f"pp={pp} does not divide device count {n}")
    rest = n // pp
    cp = want_cp or 1
    if rest % cp:
        raise ValueError(f"cp={cp} does not divide {rest} (n={n}, pp={pp})")
    rest //= cp
    tp = want_tp or 1
    if rest % tp:
        raise ValueError(
            f"tp={tp} does not divide {rest} (n={n}, pp={pp}, cp={cp})")
    return pp, rest // tp, cp, tp


def make_mesh(n_devices: Optional[int] = None, *, tp: Optional[int] = None,
              pp: Optional[int] = None, cp: Optional[int] = None,
              fsdp: bool = False,
              devices: Optional[Sequence[jax.Device]] = None
              ) -> Tuple[Mesh, MeshPlan]:
    """Build the ("stage", "data", "ctx", "model") mesh over the slice.

    Device order matters for collective locality: jax.devices() on TPU is
    already ordered so that adjacent ids are ICI neighbours; tp (the most
    chatty axis: per-layer all-reduces) gets the innermost, contiguous
    stride, then cp (ring ppermute between neighbours), pp (per-microbatch
    point-to-point only) the outermost.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            msg = (f"requested a {n_devices}-device mesh but only "
                   f"{len(devs)} devices are "
                   + ("in the given `devices` sequence" if devices is not None
                      else f"visible on platform "
                           f"{devs[0].platform if devs else '?'}; for a "
                           f"virtual mesh set JAX_PLATFORMS=cpu and "
                           f"XLA_FLAGS=--xla_force_host_platform_device_"
                           f"count={n_devices} before the first jax import"))
            raise ValueError(msg)
        devs = devs[:n_devices]
    n = len(devs)
    pp_, dp_, cp_, tp_ = _factor(n, tp, pp, cp)
    arr = np.array(devs).reshape(pp_, dp_, cp_, tp_)
    return (Mesh(arr, (AXIS_STAGE, AXIS_DATA, AXIS_CTX, AXIS_MODEL)),
            MeshPlan(pp=pp_, dp=dp_, cp=cp_, tp=tp_, fsdp=fsdp))


# ---------------------------------------------------------------------------
# Logical-axis → mesh-axis rules (Megatron-style layout)
# ---------------------------------------------------------------------------

def param_sharding_rules(plan: MeshPlan) -> Dict[str, Optional[str]]:
    """Mapping of the model's logical axis names to mesh axes.

    Layout (the standard TP layout, scaling-book ch. "transformers"):
      vocab    → model   (embedding + lm head vocab-sharded)
      embed    → data if fsdp else replicated (ZeRO-3 shard of d_model dims)
      mlp      → model   (ffn hidden, column-parallel then row-parallel)
      heads    → model   (attention heads)
      kv       → None    (per-head dims replicated)
      expert   → data    (MoE expert parallelism over the dp group)
    """
    return {
        "vocab": AXIS_MODEL,
        "embed": AXIS_DATA if plan.fsdp else None,
        "mlp": AXIS_MODEL,
        "heads": AXIS_MODEL,
        "kv": None,
        "expert": AXIS_DATA,
        "expert_mlp": AXIS_MODEL,
        "layers": None,
        None: None,
    }


def logical_sharding(mesh: Mesh, logical_axes: Tuple[Optional[str], ...],
                     rules: Dict[str, Optional[str]],
                     shape: Optional[Tuple[int, ...]] = None
                     ) -> NamedSharding:
    """NamedSharding for a param annotated with logical axis names.

    A mesh axis can shard at most one dimension; on collision the first
    (leftmost) dimension keeps it (e.g. MoE experts take "data", so the
    fsdp shard of the embed dim inside expert weights is dropped). With a
    ``shape``, axes that don't divide the dimension are dropped too (e.g.
    2 experts on a 4-way data axis fall back to replication)."""
    assigned: List[Optional[str]] = []
    seen = set()
    sizes = mesh.shape
    for i, a in enumerate(logical_axes):
        m = rules.get(a)
        if m is not None and m in seen:
            m = None
        if m is not None and shape is not None and shape[i] % sizes[m]:
            m = None
        if m is not None:
            seen.add(m)
        assigned.append(m)
    return NamedSharding(mesh, P(*assigned))


def tree_shardings(mesh: Mesh, params_axes, rules,
                   abstract_params=None) -> object:
    """Map a pytree of logical-axes tuples to NamedShardings. With
    ``abstract_params`` (matching tree of ShapeDtypeStructs), divisibility
    is checked per dimension."""
    is_axes = lambda x: isinstance(x, tuple)
    if abstract_params is None:
        return jax.tree.map(
            lambda axes: logical_sharding(mesh, axes, rules), params_axes,
            is_leaf=is_axes)
    return jax.tree.map(
        lambda axes, leaf: logical_sharding(mesh, axes, rules,
                                            tuple(leaf.shape)),
        params_axes, abstract_params, is_leaf=is_axes)
