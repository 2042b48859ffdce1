"""One run of a mixed-length serving cell on a configuration whose
window layers keep a page pool of their own beside the full layers':
the serving cell of ``benchmark/serve_open_loop_cell.py`` (export ->
InferenceService -> Ready -> warm-up -> open-loop window through the
router -> tear-down -> reference on the chip), reused by import, with
what is its own:

* The cell's file names the export writer and the check child as data
  (``export_writer``, ``check``), as it names its ``traced_replica``;
  the service is ``serve_longctx_cell.Served``.
* ``correct`` rests on two pairs of gaps (``benchmark/
  check_serve_smallthinker.py``): ``.short`` over the positions whose
  whole context lies within one window, ``.long`` over the positions
  beyond it, each with its own limit in the configuration's
  ``correct``, and on each part holding no fewer positions than the
  cell's file asks (``short_positions_min``, ``long_positions_min``).
* The sample that is checked holds the longest request; ``check_reused``
  requests that took a slot (the request's ``timing.slot``, the
  program's word) right after one whose context had outgrown the
  window, so that the window class's pages they were given had been
  given back by another row (what a stale page gets wrong); then others
  by the seed, up to ``check_requests`` requests; but for the longest,
  none of more than ``check_tokens`` tokens (the reference computes
  every expert for every token at ``highest``: 2.4 ms a token on the
  chip, and a run has to end inside the driver's time).
* The served tokens' gaps see a precision only where it flips the
  largest logit, so ``correct`` also holds THE CACHE ITSELF against the
  reference: once the window has drained, and before the replica goes,
  the run sends the cell's ``kv_probe`` (one greedy request, its prompt
  drawn from ``--seed``; the same replica, pools and compiled programs,
  outside every timed span) and, while that row decodes, reads back the
  keys and values its pages hold in one layer of each page class (the
  program's ``/debug/kv``; the row's slot from ``/debug/flight``). The
  check child compares them with the reference's keys and values of
  the same prompt: ``kv_gap_median.full`` / ``.window``.
* ``memory_peak_bytes``' floor from the gauges counts both pools
  (``kfx_lm_kv_pool_bytes``, a series a page class).

Controls (``--control``), each of which must come out not correct:
``fullwindow``: the reference's window layers see every earlier
position; ``.long`` fails. ``laterouter``: the reference's router reads
the FFN's normed input. ``int8kv``: both K/V pools in int8, the
precision below the stated one; ``kv_gap_median`` fails.

``python -m benchmark.serve_mixedctx_cell --workload <cell> --rates a,b
--seconds 51 --seed n`` sweeps the cell's knee with ``benchmark/sweep
.py``'s rule, one replica a pass.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List

import numpy as np

from . import harness as H
from . import loadgen, manifest, stats
from . import serve_open_loop_cell as base
from .serve_longctx_cell import Served

CONTROLS = {"fullwindow": ["--full-window"], "laterouter": ["--late-router"],
            "int8kv": []}
COUNTERS = ("kfx_lm_engine_chunks_total", "kfx_lm_prefill_chunks_total",
            "kfx_lm_generated_tokens_total", "kfx_lm_kv_preemptions_total",
            "kfx_lm_window_cached_positions_total",
            "kfx_lm_window_attended_positions_total",
            "kfx_lm_window_pages_freed_total",
            "kfx_lm_moe_assignments_total", "kfx_lm_moe_dispatches_total",
            "kfx_lm_moe_max_rows_total", "kfx_lm_moe_experts_hit_total",
            "kfx_lm_sample_steps_total", "kfx_lm_decode_experts_hit_total",
            "kfx_lm_decode_window_cached_positions_total",
            "kfx_lm_decode_window_attended_positions_total",
            "kfx_lm_decode_window_gathered_positions_total")


def check_sample(rows: List[Dict[str, Any]], reqs: List[Dict[str, Any]],
                 n: int, tokens: int, seed: int, window: int, reused: int
                 ) -> List[Dict[str, Any]]:
    """A seeded sample of the finished requests: the longest; ``reused``
    that started in a slot right after a request whose context had
    outgrown ``window`` (requests share a slot one after another, in
    the order they ended); then others by the seed, up to ``n``
    requests, none but the longest of more than ``tokens`` tokens."""
    done = [i for i, r in enumerate(rows) if r["ok"]]
    if not done:
        return []
    size = lambda i: rows[i]["prompt_len"] + len(rows[i]["tokens"])
    longest = max(done, key=size)
    by_slot: Dict[int, List[int]] = {}
    for i in sorted(done, key=lambda i: rows[i]["end_s"]):
        slot = (rows[i]["timing"] or {}).get("slot", -1)
        if slot >= 0:
            by_slot.setdefault(slot, []).append(i)
    after_long = {b for order in by_slot.values()
                  for a, b in zip(order, order[1:]) if size(a) > window}
    rest = [i for i in done if i != longest and size(i) <= tokens]
    random.Random(seed).shuffle(rest)
    picked = [longest] + [i for i in rest if i in after_long][:reused]
    picked += [i for i in rest if i not in picked][:max(0, n - len(picked))]
    return [{"prompt": reqs[i]["prompt"], "served": rows[i]["tokens"],
             "arrival": i, "after_long": i in after_long} for i in picked]


def kv_probe(svc, spec: Dict[str, Any], vocab: int, seed: int,
             run_dir: str) -> Dict[str, Any]:
    """Send the cell's ``kv_probe`` (a prompt of ``prompt_tokens``
    tokens drawn from ``seed``, ``new_tokens`` to generate) to the
    drained replica and, while its row decodes, keep what the row's
    pages hold in each of ``layers`` (the program's /debug/kv, an .npz
    a layer) in the run's directory for the check child. The row is the
    one /debug/flight's newest record shows decoding. Returns the
    probe with what it was ``served`` and the ``layers``' files."""
    ids = np.random.default_rng(
        np.random.SeedSequence([0x6B6678, int(seed), 4]))
    probe = {"prompt": ids.integers(0, vocab,
                                    size=spec["prompt_tokens"]).tolist(),
             "max_new_tokens": spec["new_tokens"], "temperature": 0.0,
             "layers": {}}
    base_url = svc.metrics_url.rsplit("/metrics", 1)[0]

    def get(path: str) -> bytes:
        with urllib.request.urlopen(base_url + path, timeout=60) as r:
            return r.read()

    def rows() -> Dict[str, Any]:
        """The newest iteration's record: who decodes, who prefills."""
        records = json.loads(get("/debug/flight"))["models"][
            svc.name]["records"]
        return records[-1] if records else {}

    sent: Dict[str, Any] = {}
    sender = threading.Thread(daemon=True, target=lambda: sent.update(
        loadgen.stream_one(svc.url, svc.name, probe)))
    try:
        # The probe goes alone: a window whose stragglers outlast its
        # grace (a control that serves slower) is waited for first.
        limit = time.monotonic() + 300
        while True:
            last = rows()
            if not (last.get("active") or last.get("prefilling")
                    or last.get("queue_depth")):
                break
            H.check(time.monotonic() < limit, "the replica never went "
                    "idle for the kv probe", H.read(svc.replica_log)[-3000:])
            time.sleep(0.5)
        sender.start()
        limit = time.monotonic() + 120
        while True:
            H.check(sender.is_alive() and time.monotonic() < limit,
                    "the kv probe ended before its row was read: "
                    f"{sent.get('error')}", H.read(svc.replica_log)[-3000:])
            last = rows()
            if len(last.get("active", ())) == 1 \
                    and not last.get("prefilling"):
                break
            time.sleep(0.05)
        slot = last["active"][0][0]
        for layer in spec["layers"]:
            path = os.path.join(run_dir, f"kv_probe_{layer}.npz")
            with open(path, "wb") as f:
                f.write(get(f"/debug/kv?model={svc.name}&slot={slot}"
                            f"&layer={layer}"))
            probe["layers"][str(layer)] = path
    except OSError as e:
        raise H.RunFailure(f"the kv probe's row was not read: {e}",
                           H.read(svc.replica_log)[-3000:]) from e
    sender.join(120)
    H.check(sent.get("done") and not sent.get("error"),
            f"the kv probe did not finish: {sent.get('error')}",
            H.read(svc.replica_log)[-3000:])
    return dict(probe, served=sent["tokens"])


def settings(man, wl, bench_dir: str, control: str):
    """(cell, mix path, config path, config, serving) of a run."""
    cell = manifest.cell(wl["name"], bench_dir)
    mix_path = os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"],
                                    os.path.dirname(bench_dir))
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
    if control:
        H.check(control in CONTROLS, f"this kind's controls are "
                f"{', '.join(CONTROLS)}, not {control!r}")
        if control == "int8kv":
            serving["quantization"] = {"kv": "int8"}
        H.say(f"CONTROL RUN: {control}")
    return cell, mix_path, cfg_path, cfg, serving


def run(man: Dict[str, Any], wl: Dict[str, Any], seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, control: str = "",
        bench_dir: str = manifest.BENCH_DIR) -> str:
    """Run the cell; returns the result line."""
    cell, mix_path, cfg_path, cfg, serving = settings(
        man, wl, bench_dir, control)
    run_dir = H.fresh_dir(wl["name"])
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
        t = time.monotonic()
        probe = kv_probe(svc, cell["kv_probe"], cfg["vocab_size"], seed,
                         run_dir)
        H.say(f"span kv_probe s={time.monotonic() - t:.1f} "
              f"prompt={len(probe['prompt'])} layers={list(probe['layers'])}")
        device, weight_bytes = svc.device, svc.weight_bytes
    rows, reqs, window_log = win["rows"], win["reqs"], win["log"]
    before, after = win["before"], win["after"]

    attempted = len(rows)
    failed = sum(not r["ok"] for r in rows)
    late = base.lateness_ms(rows)
    e2e = base.end_to_end(rows)
    compiled = H.compilations(window_log)
    H.say(f"requests attempted={attempted} failed={failed} "
          f"in_window={sum(r['in_window'] for r in rows)} "
          f"tokens_in_window={sum(len(r['times_in_window']) for r in rows)} "
          f"last_end_s={max((r['end_s'] or 0 for r in rows), default=0):.1f} "
          f"generator_late_p99_ms={late['p99']:.2f} "
          f"generator_late_max_ms={late['max']:.2f} "
          f"compilations_in_window={compiled}")
    H.say("client " + json.dumps({k: round(v, 3) for k, v in e2e.items()})
          + f" sample={attempted} supports_p"
          f"{stats.supported_percentile(attempted):.0f}")
    grew = lambda n: after.get(n, 0.0) - before.get(n, 0.0)
    H.say("engine " + " ".join(f"{n[len('kfx_lm_'):]}={grew(n):g}"
                               for n in COUNTERS)
          + f" kv_pages_free_now={after.get('kfx_lm_kv_pages_free', -1):g}"
          + f" scrape_seconds={win['scrape_seconds']:.1f}")
    # What of the rows' context lay inside the window: the replayed
    # schedule's lengths and the model's window decide it, no program.
    H.say("traffic window_attend_pct=%.1f" % (
        100.0 * grew("kfx_lm_window_attended_positions_total")
        / max(1.0, grew("kfx_lm_window_cached_positions_total"))))
    for r in rows:
        if not r["ok"]:
            H.say(f"failed request due_s={r['due_s']:.2f} "
                  f"prompt={r['prompt_len']} asked={r['asked']} "
                  f"got={len(r['tokens'])} error={r.get('error')}")
            break

    # The reference, on the chip the replica has left.
    sample = check_sample(rows, reqs, cell["check_requests"],
                          cell["check_tokens"], seed,
                          cfg["sliding_window_size"], cell["check_reused"])
    H.check(bool(sample), "no request finished: nothing to compare",
            window_log[-3000:])
    sample_path = os.path.join(run_dir, "check_sample.json")
    with open(sample_path, "w") as f:
        json.dump(sample, f)
    probe_path = os.path.join(run_dir, "kv_probe.json")
    with open(probe_path, "w") as f:
        json.dump(probe, f)
    t = time.monotonic()
    out = H.run_child(
        cell["check"],
        ["--config", cfg_path, "--seed", str(seed), "--sample", sample_path,
         "--kv-probe", probe_path]
        + CONTROLS.get(control, [])
        + (["--reduce-trace", run_dir] if trace else [])
        + ([] if require_tpu else ["--host-fallback"]),
        os.path.join(run_dir, "check.log"),
        env=None if require_tpu else {"JAX_PLATFORMS": "cpu"},
        timeout_s=900.0)
    ref = H.child_result(out)
    H.say(f"span reference s={time.monotonic() - t:.1f} "
          f"positions={ref['positions']} short={ref['short']['positions']} "
          f"long={ref['long']['positions']} requests={len(sample)} "
          f"arrivals={[s['arrival'] for s in sample]} "
          f"after_long={[s['arrival'] for s in sample if s['after_long']]} "
          f"lengths={[len(s['prompt']) + len(s['served']) for s in sample]} "
          f"exact_match_share={ref['match_share']:.4f} "
          f"logit_std={ref['logit_std']:.3f} kv={json.dumps(ref['kv'])}")
    if require_tpu:
        H.device_of(out, "reference", wl["chips"], True)
    limits = cfg["correct"]
    compared = H.print_comparison(
        [{"name": f"served_logit_gap_{stat}.{part}",
          "value": ref[part][f"gap_{stat}"],
          "limit": limits[f"served_logit_gap_{stat}.{part}"]}
         for part in ("short", "long") for stat in ("max", "mean")]
        + [{"name": f"kv_gap_median.{cls}", "value": kv["gap_median"],
            "limit": limits[f"kv_gap_median.{cls}"]}
           for cls, kv in sorted(ref["kv"].items())]
        + [{"name": "kv_positions_short",
            "value": max(0, cell["kv_probe"]["positions_min"]
                         - sum(kv["positions"]
                               for kv in ref["kv"].values())), "limit": 0}]
        + [{"name": f"{part}_positions_short",
            "value": max(0, cell[f"{part}_positions_min"]
                         - ref[part]["positions"]), "limit": 0}
           for part in ("short", "long")]
        + [{"name": "reused_short",
            "value": max(0, cell["check_reused"]
                         - sum(s["after_long"] for s in sample)),
            "limit": 0},
           {"name": "compilations_in_window", "value": compiled, "limit": 0},
           {"name": "generator_late_p99_ms", "value": late["p99"],
            "limit": base.late_limit_ms(cell, e2e["ttft_p50_ms"])}])

    dev = dict(device, memory_peak_bytes=int(
        weight_bytes + after.get("kfx_lm_kv_pool_bytes", 0)))
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
    cell, mix_path, cfg_path, cfg, serving = settings(
        man, wl, manifest.BENCH_DIR, "")
    run_dir = H.fresh_dir(wl["name"] + ".sweep")
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
