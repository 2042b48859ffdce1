"""One run of a decode-heavy serving cell on a configuration whose rows
carry recurrent state beside their pages: the serving cell of
``benchmark/serve_open_loop_cell.py`` (export -> InferenceService ->
Ready -> warm-up -> open-loop window through the router -> tear-down ->
reference on the chip), reused by import, with what is its own:

* The cell's file names the export writer and the check child as data
  (``export_writer``, ``check``), as it names its ``traced_replica``;
  the service is ``serve_longctx_cell.Served``, which reads the first
  and keeps the time between the window's two scrapes.
* The sample that is checked holds the request that generated most,
  the ``check_states`` that generated most among those whose slot
  still holds what they left when the window has drained,
  ``check_reused_slots`` requests that arrived after the first
  ``serving.slots`` (each took a slot another request had left: what a
  wrong reset of the state gets wrong), then others up to
  ``check_requests``.
* Beside the served tokens' gaps, ``correct`` compares the state
  itself: before the replica goes, the run reads those slots' states
  back from it (the program's ``/debug/state``), and the check child
  holds each against the reference's state after the same tokens
  (``state_gap_max``, ``state_gap_mean``). A token flips only where
  two logits all but tie; the state is the program's own numbers, and
  shows the precision it is held in.
* ``memory_peak_bytes``' floor from the gauges counts the slots' state
  beside weights and pages.

Controls (``--control``): ``bf16state``: the slots' recurrent state
held in bfloat16, the precision below the one the configuration states
(``serving.state_dtype``, which the export carries and the engine
reads): the state's comparison must fail. ``int8kv``: the K/V pool in
int8 (4 of 40 layers hold K/V). The readings are the configuration's
to give (``correct.why``).

``python -m benchmark.serve_hybrid_cell --workload <cell> --rates a,b
--seconds 51 --seed n`` sweeps the cell's knee with ``benchmark/sweep
.py``'s rule, one replica a pass.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
import urllib.request
from typing import Any, Dict, List

from . import harness as H
from . import manifest, stats
from . import serve_open_loop_cell as base
from .serve_longctx_cell import Served

CONTROLS = {"bf16state": "bfloat16", "int8kv": None}
COUNTERS = ("kfx_lm_engine_chunks_total", "kfx_lm_prefill_chunks_total",
            "kfx_lm_generated_tokens_total", "kfx_lm_kv_preemptions_total",
            "kfx_lm_ssm_row_updates_total",
            "kfx_lm_ssm_prefill_tokens_total", "kfx_lm_state_resets_total")


def check_sample(rows: List[Dict[str, Any]], reqs: List[Dict[str, Any]],
                 n: int, seed: int, slots: int, reused: int, states: int
                 ) -> List[Dict[str, Any]]:
    """A seeded sample of the finished requests: the one that generated
    most; the ``states`` that generated most among those whose slot no
    request took after them (the request's ``timing.slot``, the
    program's word; requests share a slot one after another, so the
    last to end is the last to have run there): their slot still holds
    the state they left, and the sample names it (``slot``);
    ``reused`` that arrived after the first ``slots`` (rows are in the
    order of arrival), those already picked counted in; then others
    up to ``n``."""
    done = [i for i, r in enumerate(rows) if r["ok"]]
    if not done:
        return []
    size = lambda i: (len(rows[i]["tokens"]), rows[i]["prompt_len"])
    longest = max(done, key=size)
    last: Dict[int, int] = {}
    for i in done:
        slot = (rows[i]["timing"] or {}).get("slot", -1)
        if slot >= 0 and (slot not in last
                          or rows[i]["end_s"] > rows[last[slot]]["end_s"]):
            last[slot] = i
    slot_of = {i: slot for slot, i in last.items()}
    held = sorted(slot_of, key=size, reverse=True)[:states]
    picked = [longest] + [i for i in held if i != longest]
    rest = [i for i in done if i not in picked]
    random.Random(seed).shuffle(rest)
    short = reused - sum(i >= slots for i in picked)
    picked += [i for i in rest if i >= slots][:max(0, short)]
    picked += [i for i in rest if i not in picked][:max(0, n - len(picked))]
    return [{"prompt": reqs[i]["prompt"], "served": rows[i]["tokens"],
             "arrival": i, "slot": slot_of[i] if i in held else None}
            for i in picked]


def fetch_states(svc, sample: List[Dict[str, Any]], run_dir: str) -> None:
    """What the replica's slots hold of the sample's requests that name
    one (the program's /debug/state, an .npz a slot), kept in the run's
    directory for the check child; nothing has run in those slots
    since."""
    base_url = svc.metrics_url.rsplit("/metrics", 1)[0]
    for s in sample:
        if s["slot"] is None:
            continue
        s["state_file"] = os.path.join(run_dir, f"state_{s['arrival']}.npz")
        try:
            with urllib.request.urlopen(
                    f"{base_url}/debug/state?model={svc.name}"
                    f"&slot={s['slot']}", timeout=60) as r, \
                    open(s["state_file"], "wb") as f:
                f.write(r.read())
        except OSError as e:
            raise H.RunFailure(f"no state of slot {s['slot']}: {e}",
                               H.read(svc.replica_log)[-3000:]) from e


def settings(man, wl, bench_dir: str, control: str, run_dir: str):
    """(cell, mix path, config path, config, serving) of a run; a
    control's configuration is a copy in the run's directory."""
    cell = manifest.cell(wl["name"], bench_dir)
    mix_path = os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"],
                                    os.path.dirname(bench_dir))
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
    if control:
        H.check(control in CONTROLS, f"this kind's controls are "
                f"{' and '.join(CONTROLS)}, not {control!r}")
        if control == "int8kv":
            serving["quantization"] = {"kv": "int8"}
        else:
            cfg = dict(cfg, serving=dict(cfg["serving"],
                                         state_dtype=CONTROLS[control]))
            cfg_path = os.path.join(run_dir, "control_config.json")
            with open(cfg_path, "w") as f:
                json.dump(cfg, f)
        H.say(f"CONTROL RUN: {control}")
    return cell, mix_path, cfg_path, cfg, serving


def run(man: Dict[str, Any], wl: Dict[str, Any], seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, control: str = "",
        bench_dir: str = manifest.BENCH_DIR) -> str:
    """Run the cell; returns the result line."""
    run_dir = H.fresh_dir(wl["name"])
    cell, mix_path, cfg_path, cfg, serving = settings(
        man, wl, bench_dir, control, run_dir)
    with Served(cfg_path, cfg, serving, mix_path, cell, run_dir, seed,
                wl["chips"], trace, require_tpu) as svc:
        if trace:
            with open(os.path.join(run_dir, "trace.request"), "w") as f:
                json.dump({"after_s": cell["trace_after_s"],
                           "seconds": cell["trace_seconds"]}, f)
        win = svc.window(cell["rate_rps"], seconds, seed)
        setup_s = win["t0_wall"] - H.T0
        H.say(f"window closed: it opened at setup_s={setup_s:.1f}")
        if trace:
            limit = time.monotonic() + 60
            while not os.path.exists(os.path.join(run_dir, "trace.done")):
                H.check(time.monotonic() < limit,
                        "the replica never finished its trace",
                        H.read(svc.replica_log)[-3000:])
                time.sleep(0.2)
        # The sample, while the replica still holds what its requests
        # left in their slots.
        sample = check_sample(
            win["rows"], win["reqs"], cell["check_requests"], seed,
            serving["slots"], cell["check_reused_slots"],
            cell["check_states"])
        t = time.monotonic()
        fetch_states(svc, sample, run_dir)
        H.say(f"span states s={time.monotonic() - t:.1f} "
              f"slots={[s['slot'] for s in sample if s['slot'] is not None]}")
        device, weight_bytes = svc.device, svc.weight_bytes
    rows, reqs, window_log = win["rows"], win["reqs"], win["log"]
    before, after = win["before"], win["after"]

    attempted = len(rows)
    failed = sum(not r["ok"] for r in rows)
    late = base.lateness_ms(rows)
    e2e = base.end_to_end(rows)
    compiled = H.compilations(window_log)
    latest = max((r for r in rows if r["late_s"] is not None),
                 key=lambda r: r["late_s"], default=None)
    H.say(f"requests attempted={attempted} failed={failed} "
          f"in_window={sum(r['in_window'] for r in rows)} "
          f"tokens_in_window={sum(len(r['times_in_window']) for r in rows)} "
          f"last_end_s={max((r['end_s'] or 0 for r in rows), default=0):.1f} "
          f"generator_late_p99_ms={late['p99']:.2f} "
          f"generator_late_max_ms={late['max']:.2f} "
          f"latest_due_s={latest['due_s'] if latest else -1:.1f} "
          f"compilations_in_window={compiled}")
    H.say("client " + json.dumps({k: round(v, 3) for k, v in e2e.items()})
          + f" sample={attempted} supports_p"
          f"{stats.supported_percentile(attempted):.0f}")
    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    H.say("engine " + " ".join(f"{n[len('kfx_lm_'):]}={grew(n):g}"
                               for n in COUNTERS)
          + f" kv_pages_free_now={after.get('kfx_lm_kv_pages_free', -1):g}"
          + f" scrape_seconds={win['scrape_seconds']:.1f}")
    for r in rows:
        if not r["ok"]:
            H.say(f"failed request due_s={r['due_s']:.2f} "
                  f"prompt={r['prompt_len']} asked={r['asked']} "
                  f"got={len(r['tokens'])} error={r.get('error')}")
            break

    # The reference, on the chip the replica has left.
    H.check(bool(sample), "no request finished: nothing to compare",
            window_log[-3000:])
    sample_path = os.path.join(run_dir, "check_sample.json")
    with open(sample_path, "w") as f:
        json.dump(sample, f)
    t = time.monotonic()
    out = H.run_child(
        cell["check"],
        ["--config", cfg_path, "--seed", str(seed), "--sample", sample_path,
         "--pad-to", str(cell["check_pad_to"])]
        + (["--reduce-trace", run_dir] if trace else [])
        + ([] if require_tpu else ["--host-fallback"]),
        os.path.join(run_dir, "check.log"),
        env=None if require_tpu else {"JAX_PLATFORMS": "cpu"})
    ref = H.child_result(out)
    H.say(f"span reference s={time.monotonic() - t:.1f} "
          f"positions={ref['positions']} requests={len(sample)} "
          f"arrivals={[s['arrival'] for s in sample]} "
          f"lengths={[len(s['prompt']) + len(s['served']) for s in sample]} "
          f"exact_match_share={ref['match_share']:.4f} "
          f"by_request={ref['match_by_request']} "
          f"logit_std={ref['logit_std']:.3f}")
    H.say(f"states probed={ref['state_probes']} generated="
          f"{[len(s['served']) for s in sample if s['slot'] is not None]} "
          f"gap_by_request={ref['state_gap_by_request']} "
          f"gap_by_layer={ref['state_gap_by_layer']}")
    if require_tpu:
        H.device_of(out, "reference", wl["chips"], True)
    limits = cfg["correct"]
    compared = H.print_comparison([
        {"name": "served_logit_gap_max", "value": ref["gap_max"],
         "limit": limits["served_logit_gap_max"]},
        {"name": "served_logit_gap_mean", "value": ref["gap_mean"],
         "limit": limits["served_logit_gap_mean"]},
        {"name": "state_gap_max", "value": ref["state_gap_max"],
         "limit": limits["state_gap_max"]},
        {"name": "state_gap_mean", "value": ref["state_gap_mean"],
         "limit": limits["state_gap_mean"]},
        {"name": "state_probes_short",
         "value": max(0, cell["check_states"] - ref["state_probes"]),
         "limit": 0},
        {"name": "reused_slots_short",
         "value": max(0, cell["check_reused_slots"] - sum(
             s["arrival"] >= serving["slots"] for s in sample)),
         "limit": 0},
        {"name": "compilations_in_window", "value": compiled, "limit": 0},
        {"name": "generator_late_p99_ms", "value": late["p99"],
         "limit": base.late_limit_ms(cell, e2e["ttft_p50_ms"])},
    ])

    held = (after.get("kfx_lm_kv_pages", 0) * serving["kv_page_size"]
            * after.get("kfx_lm_kv_bytes_per_token", 0)
            + after.get("kfx_lm_slots", 0)
            * after.get("kfx_lm_state_bytes_per_slot", 0))
    dev = dict(device, memory_peak_bytes=int(weight_bytes + held))
    if not trace:
        metrics = {m["name"]: {"value": e2e[m["name"]] if m["name"] in e2e
                               else setup_s, "unit": m["unit"]}
                   for m in manifest.metrics_for(man, "end_to_end",
                                                 wl["name"])}
        return H.result_line(compared, attempted, failed, metrics, dev)
    tr = ref["trace"]
    H.say(f"replica memory_stats peak_bytes_in_use="
          f"{tr.get('memory_peak_bytes')} (floor from gauges: "
          f"{dev['memory_peak_bytes']})")
    if tr.get("memory_peak_bytes"):
        dev["memory_peak_bytes"] = int(tr["memory_peak_bytes"])
    dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    ctx = {"rows": rows, "before": before, "after": after, "trace": tr,
           "cfg": cfg, "cell": cell, "serving": serving, "device": device,
           "seconds": seconds, "e2e": e2e, "t0_wall": win["t0_wall"],
           "scrape_seconds": win["scrape_seconds"]}
    metrics = manifest.read_layer_metrics(man, wl["name"], ctx, bench_dir)
    return H.result_line(compared, attempted, failed, metrics, dev,
                         tr.get("breakdown"))


def sweep(argv=None) -> int:
    """The knee of a cell of this kind: ``benchmark/sweep.py``'s pass
    and rule, with this kind's replica."""
    from . import sweep as S

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    man = manifest.manifest()
    wl = manifest.workload(man, args.workload)
    run_dir = H.fresh_dir(wl["name"] + ".sweep")
    cell, mix_path, cfg_path, cfg, serving = settings(
        man, wl, manifest.BENCH_DIR, "", run_dir)
    table = []
    try:
        with Served(cfg_path, cfg, serving, mix_path, cell, run_dir,
                    args.seed, wl["chips"], False, True) as svc:
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                win = svc.window(rate, args.seconds, args.seed + i)
                row = S.judge(win["rows"], rate, args.seconds)
                row["compilations"] = H.compilations(win["log"])
                c = lambda n: win["after"].get(n, 0) - win["before"].get(n, 0)
                row["tokens_per_dispatch"] = round(
                    c("kfx_lm_generated_tokens_total")
                    / max(1, c("kfx_lm_engine_chunks_total")), 2)
                row["preemptions"] = c("kfx_lm_kv_preemptions_total")
                row["last_end_s"] = round(max(
                    (r["end_s"] or 0 for r in win["rows"]), default=0), 1)
                table.append(row)
                H.say("sweep " + json.dumps(row))
    except H.RunFailure as e:
        H.say(f"FAILED: {e}")
        print(e.log[-4000:])
        return 1
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(sweep())
