"""The one recipe for a virtual n-device CPU mesh.

``JAX_PLATFORMS=cpu`` plus the host-platform device-count flag, both of
which JAX reads when it is imported — so consumers put them in the
environment of a process they start (the JAXJob operator for CPU
workers, ``spmd_check``, ``__graft_entry__.dryrun_multichip``). Kept
import-light (no jax, no package siblings).
"""

from typing import Dict


def virtual_mesh_env(n_devices: int = 8) -> Dict[str, str]:
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "JAX_ENABLE_X64": "0",
    }
