"""The table of peaks, keyed by the ``device_kind`` JAX reports. The
benchmark's own copy: ``kubeflow_tpu/utils/flops.py`` stays the
program's and may drift. A device that is not in the table is an error,
never a default."""

from __future__ import annotations

import os
from typing import Dict

from .manifest import BENCH_DIR, load_json


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(table)}); a share of someone else's peak "
            "is not a measurement")
    return table[device_kind]
