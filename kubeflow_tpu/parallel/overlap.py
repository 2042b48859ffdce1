"""Collective/compute overlap for the multi-chip training hot path.

The serialized-gradient-all-reduce tax (Megatron-LM §5 / the scaling
book's "data parallelism" chapter): with dp>1, GSPMD inserts the
gradient all-reduces at the end of the backward, and the tail
all-reduces cannot start until the *whole* backward finishes — the ICI
sits idle during compute and the MXU sits idle during the reduce. The
lever lives in the compiler, not in model code: the TPU latency-hiding
scheduler plus async collective fusion interleave the reduces with the
remaining backward + optimizer compute.

The flags are libtpu's, so they travel in ``LIBTPU_INIT_ARGS``, which
libtpu reads when the backend starts. (Never ``XLA_FLAGS``: jaxlib
parses that variable itself and aborts the process on a flag it does
not register, and it registers none of these.) ``lm_runner
--collective-overlap`` applies them; nothing does so by default,
because no chip measurement of their effect exists yet.

Visibility: ``measure_collective`` times a real all-reduce of a
gradient-sized buffer over the mesh's "data" axis — the serialized cost
that overlap hides. The LM runner records it as a ``train.collective``
span so the `kfx trace` waterfall shows the per-step collective bound
next to the measured ``train.window`` spans: if
``train.collective * steps`` is a visible fraction of the window,
overlap headroom remains.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

# Every name here is one the installed libtpu registers
# (tests/test_tpu_compile.py starts libtpu with them).
OVERLAP_TPU_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true",
    "--xla_tpu_data_parallel_opt_different_sized_ops=true",
)

LIBTPU_ENV = "LIBTPU_INIT_ARGS"


def apply_overlap_env(env: Dict[str, str]) -> bool:
    """Append the overlap flags to ``env['LIBTPU_INIT_ARGS']``; must
    happen before the process starts its TPU backend. Idempotent: flags
    already present (under any value) are left alone. Returns True when
    anything was applied."""
    current = env.get(LIBTPU_ENV, "")
    missing = [f for f in OVERLAP_TPU_FLAGS
               if f.split("=", 1)[0] not in current]
    if not missing:
        return False
    env[LIBTPU_ENV] = (current + " " + " ".join(missing)).strip()
    return True


def grad_allreduce_bytes(params, plan) -> int:
    """Bytes one step's gradient reduction moves per chip: the f32 grad
    tree for plain dp (all-reduce of the full tree), or its 1/dp shard
    for fsdp (reduce-scatter + the optimizer-sharded update)."""
    import jax
    import numpy as np

    total = sum(int(np.prod(p.shape)) * 4 for p in jax.tree.leaves(params))
    if getattr(plan, "fsdp", False) and plan.dp > 1:
        return total // plan.dp
    return total


def measure_collective(mesh, n_bytes: int,
                       axis: Optional[str] = None,
                       repeats: int = 3) -> float:
    """Measured seconds for one all-reduce of ``n_bytes`` (f32) over
    ``axis`` (default "data") on ``mesh`` — the serialized per-step
    gradient-reduction cost that collective overlap hides. Returns 0.0
    when the axis is trivial (nothing to reduce across). Compile is
    excluded (one warm dispatch before timing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import AXIS_DATA

    axis = axis or AXIS_DATA
    ways = mesh.shape.get(axis, 1)
    if ways <= 1:
        return 0.0
    # Per-shard buffer sized so the GLOBAL reduced payload is n_bytes;
    # lane-friendly [ways, n] layout sharded over the axis.
    n = max(n_bytes // 4 // ways, 1)
    x = jnp.ones((ways, n), jnp.float32)

    def allreduce(x):
        return jax.lax.psum(x, axis)

    fn = jax.jit(jax.shard_map(
        allreduce, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_vma=False))
    with jax.set_mesh(mesh):
        sharded = jax.device_put(x, NamedSharding(mesh, P(axis)))
        jax.block_until_ready(fn(sharded))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(sharded)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


__all__ = ["OVERLAP_TPU_FLAGS", "apply_overlap_env",
           "grad_allreduce_bytes", "measure_collective"]
