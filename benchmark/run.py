#!/usr/bin/env python3
"""The kfx benchmark: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs on the machine it is started on, which must hold the TPU chips the
cell asks for; anything else is exit code 1 and no result line. The
last line of standard output is one JSON object (correct, attempted,
failed, metrics, device, with --trace 1 breakdown, and last compared:
every number ``correct`` rests on beside its limit). ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics. What a cell is lives in data files found by the names in
BENCHMARK.json (benchmark/manifest.py).
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# Workers and replicas the plane spawns import the benchmark too.
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

from benchmark import harness as H  # noqa: E402
from benchmark import manifest  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: str = "",
             root: str = ROOT) -> str:
    """The result line of one run. ``require_tpu=False`` skips the look
    for a chip (tests on the CPU only)."""
    man = manifest.manifest(root)
    bench_dir = os.path.join(root, man["paths"][0])
    wl = manifest.workload(man, workload)
    kind = manifest.traffic(wl["traffic"], bench_dir)["kind"]
    return manifest.cell_runner(kind)(
        man, wl, seed, seconds, trace, require_tpu=require_tpu,
        control=control, bench_dir=bench_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="run the cell's control (serving: int8kv; "
                         "training: bf16, stuck): correct must come "
                         "out false. Not used by the driver.")
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), control=args.control)
    except manifest.ManifestError as e:
        H.say(f"FAILED: {e}")
        return 1
    except H.RunFailure as e:
        H.say(f"FAILED: {e}")
        if e.log:
            print("---- tail of the child's log ----\n" + e.log[-6000:],
                  flush=True)
        return 1
    if "jax" in sys.modules:
        H.say("FAILED: the harness imported jax; a chip has one owner")
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
