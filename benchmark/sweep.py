#!/usr/bin/env python3
"""Find a serving cell's knee, once: one replica swept upward through
fixed rates, a window each. Not part of a run; the resulting rate is
written into the cell's file as a number.

    python3 benchmark/sweep.py --workload <cell> --rates 2,2.5,3.2 \\
        --seconds 30 --seed 1

A rate is sustained when the requests completed per second over the
second half of its window are within 3% of the rate offered and the
median TTFT of the last third of its requests is no more than twice
that of the first third (no growing backlog)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

from benchmark import harness as H  # noqa: E402
from benchmark import manifest, stats
from benchmark import serve_open_loop_cell as serve_cell  # noqa: E402


def judge(rows, rate: float, seconds: float) -> dict:
    ok = [r for r in rows if r["ok"]]
    half = seconds / 2
    third = max(1, len(rows) // 3)
    ttft = lambda rs: stats.median(stats.with_failures(
        [r["ttft_s"] for r in rs]))
    first, last = ttft(rows[:third]), ttft(rows[-third:])
    done = sum(half <= r["end_s"] <= seconds for r in ok) / half
    offered = sum(r["due_s"] >= half for r in rows) / half
    e2e = serve_cell.end_to_end(rows)
    late = serve_cell.lateness_ms(rows)
    return {"rate": rate, "sent": len(rows), "failed": len(rows) - len(ok),
            "offered_2nd_half": offered, "completed_2nd_half": done,
            "ttft_first_third_ms": 1e3 * first,
            "ttft_last_third_ms": 1e3 * last,
            "sustained": bool(done >= 0.97 * offered and last <= 2 * first),
            "late_p99_ms": round(late["p99"], 2),
            "late_max_ms": round(late["max"], 2),
            **{k: round(v, 2) for k, v in e2e.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    man = manifest.manifest()
    wl = manifest.workload(man, args.workload)
    cell = manifest.cell(wl["name"])
    mix_path = os.path.join(manifest.BENCH_DIR, "traffic",
                            f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"])
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
    run_dir = H.fresh_dir(wl["name"] + ".sweep")
    table = []
    try:
        with serve_cell.Served(cfg_path, cfg, serving, mix_path, cell, run_dir,
                               args.seed, wl["chips"], False, True) as svc:
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                win = svc.window(rate, args.seconds, args.seed + i)
                row = judge(win["rows"], rate, args.seconds)
                row["compilations"] = H.compilations(win["log"])
                c = lambda n: win["after"].get(n, 0) - win["before"].get(n, 0)
                row["tokens_per_dispatch"] = round(
                    c("kfx_lm_generated_tokens_total")
                    / max(1, c("kfx_lm_engine_chunks_total")), 2)
                row["preemptions"] = c("kfx_lm_kv_preemptions_total")
                table.append(row)
                H.say("sweep " + json.dumps(row))
    except H.RunFailure as e:
        H.say(f"FAILED: {e}")
        print(e.log[-4000:])
        return 1
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
