"""One run of a long-context serving cell on a configuration with a
latent cache and a learned sparse selection: the serving cell of
``benchmark/serve_open_loop_cell.py`` (export -> InferenceService ->
Ready -> warm-up -> open-loop window through the router -> tear-down ->
reference on the chip), reused by import, with three things its own.

* The cell's file names the export writer and the check child as data
  (``export_writer``, ``check``), as it names its ``traced_replica``.
* ``correct`` rests on two pairs of gaps
  (``benchmark/check_serve_glm_moe_dsa.py``): ``.full`` over the
  positions where the selection is everything, ``.sparse`` over the
  rest, each with its own limit in the configuration's ``correct``.
* The sample that is checked is drawn by kind: the longest request,
  every request whose whole context stays within ``index_topk``, then
  requests beyond it up to ``check_requests``. At this program's speed
  a window holds one request of the second kind, some hundred
  positions, too few to tell a lower precision from a sound run's luck
  with near-ties. So once the window has drained, and before the
  replica goes, the run sends it ``full_probes`` (the cell's file:
  ``count`` greedy requests, prompts spread evenly from ``prompt_min``
  to ``prompt_max`` tokens drawn from ``--seed``, ``new_tokens`` each,
  every context within ``index_topk``) and adds what it served to the
  sample: the same replica, weights, pool and compiled programs,
  outside every timed span. ``.full`` is judged over no fewer than
  ``full_positions_min`` positions (a compared number of its own).

Controls (``--control``): ``recent``: the reference reads the
``index_topk`` most recent positions in the place of the learned
selection; ``.sparse`` must fail. ``int8kv``: the latent pool in int8,
the program's own lower-precision path; ``.full`` must fail.

``python -m benchmark.serve_longctx_cell --workload <cell> --rates a,b
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
from typing import Any, Dict, List

import numpy as np

from . import harness as H
from . import kfx_adapter as K
from . import loadgen, manifest, stats, traffic
from . import serve_open_loop_cell as base


class Served(base.Served):
    """``serve_open_loop_cell.Served`` with the export writer the cell's
    file names, and a window that keeps the time between its two
    scrapes."""

    def _bring_up(self) -> None:
        from kubeflow_tpu.api.manifest import load_manifests

        cp, serving, name = self.cp, self.serving, self.name
        if self.require_tpu:
            H.require_chips(cp, self.chips)
        t = time.monotonic()
        out = H.run_child(
            self.cell["export_writer"],
            ["--config", self.cfg_path, "--seed", str(self.seed),
             "--out", self.export,
             "--max-seq-len", str(serving["max_seq_len"])],
            os.path.join(self.run_dir, "export.log"),
            env={"JAX_PLATFORMS": "cpu"})
        H.say(f"span export.write s={time.monotonic() - t:.1f} "
              f"{H.tagged(out, 'exported ')[-1]}")
        self.weight_bytes = H.child_result(out)["param_bytes"]

        t = time.monotonic()
        traced_argv: List[str] = []
        if self.trace:
            traced_argv = [
                sys.executable, "-m", self.cell["traced_replica"],
                f"--trace-dir={self.run_dir}", f"--model-dir={self.export}",
                f"--name={name}", "--port=$(KFX_PORT)", "--device=default",
                f"--max-batch-size={serving['slots']}",
                "--batcher-max-latency-ms=0"]
        cp.apply(load_manifests(K.inference_service(
            name, self.export, serving, traced_argv)))
        isvc = base._wait_ready(cp, name, self.replica_log, 1000)
        self.url = isvc.status["url"]
        (_, self.metrics_url), = \
            cp.manager.controllers["InferenceService"].scrape_targets()
        self.device = H.device_of(H.read(self.replica_log), "replica",
                                  self.chips, self.require_tpu)
        H.say(f"span serve.ready s={time.monotonic() - t:.1f} "
              f"device={self.device} url={self.url}")

        # The buckets first, then the warm-up traffic: a program of
        # this size compiles for a quarter of a minute, and a request
        # that waits behind eight of them gets no byte for longer than
        # the router holds a backend open (60 s).
        t = time.monotonic()
        buckets = base._wait_warm(self.metrics_url, serving["max_seq_len"],
                                  900)
        warm = [{"prompt": [1 + (i * 7 + j) % 1000 for j in range(n)],
                 "max_new_tokens": self.cell["warm_new_tokens"],
                 "temperature": 0.0}
                for i, n in enumerate(traffic.warm_prompt_lengths(self.mix))]
        try:
            loadgen.send_all(self.url, name, warm)
        except RuntimeError as e:
            raise H.RunFailure(str(e), H.read(self.replica_log)) from e
        H.say(f"span serve.warm s={time.monotonic() - t:.1f} "
              f"warm_requests={len(warm)} warm_buckets={buckets}")

    def window(self, rate: float, seconds: float, seed: int
               ) -> Dict[str, Any]:
        t = time.monotonic()
        win = super().window(rate, seconds, seed)
        win["scrape_seconds"] = time.monotonic() - t
        return win


def check_sample(rows: List[Dict[str, Any]], reqs: List[Dict[str, Any]],
                 n: int, seed: int, index_topk: int) -> List[Dict[str, Any]]:
    """A seeded sample of the finished requests: the longest, every one
    whose whole context stays within ``index_topk`` (each position of
    it is judged as ``full``), then others up to ``n`` in all."""
    done = [i for i, r in enumerate(rows) if r["ok"]]
    if not done:
        return []
    size = lambda i: rows[i]["prompt_len"] + len(rows[i]["tokens"])
    longest = max(done, key=size)
    rest = [i for i in done if i != longest]
    random.Random(seed).shuffle(rest)
    within = [i for i in rest if size(i) <= index_topk]
    beyond = [i for i in rest if size(i) > index_topk]
    picked = [longest] + within
    picked += beyond[:max(0, n - len(picked))]
    return [{"prompt": reqs[i]["prompt"], "served": rows[i]["tokens"]}
            for i in picked]


def full_probes(spec: Dict[str, Any], vocab: int, seed: int,
                index_topk: int) -> List[Dict[str, Any]]:
    """The requests of the cell's ``full_probes``: every context of
    them stays within ``index_topk``, so each position is ``full``."""
    H.check(spec["prompt_max"] + spec["new_tokens"] <= index_topk,
            f"full_probes reach past index_topk {index_topk}: {spec}")
    ids = np.random.default_rng(
        np.random.SeedSequence([0x6B6678, int(seed), 3]))
    lengths = np.linspace(spec["prompt_min"], spec["prompt_max"],
                          spec["count"]).round().astype(int)
    return [{"prompt": ids.integers(0, vocab, size=int(n)).tolist(),
             "max_new_tokens": int(spec["new_tokens"]), "temperature": 0.0}
            for n in lengths]


def run(man: Dict[str, Any], wl: Dict[str, Any], seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, control: str = "",
        bench_dir: str = manifest.BENCH_DIR) -> str:
    """Run the cell; returns the result line."""
    cell = manifest.cell(wl["name"], bench_dir)
    mix_path = os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"],
                                    os.path.dirname(bench_dir))
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
    if control:
        H.check(control in ("recent", "int8kv"),
                f"this kind's controls are recent and int8kv, not "
                f"{control!r}")
        if control == "int8kv":
            serving["quantization"] = {"kv": "int8"}
        H.say(f"CONTROL RUN: {control}")
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
        probes = full_probes(cell["full_probes"], cfg["vocab_size"], seed,
                             cfg["index_topk"])
        try:
            probed = loadgen.send_all(svc.url, svc.name, probes)
        except RuntimeError as e:
            raise H.RunFailure(str(e), H.read(svc.replica_log)[-3000:]) from e
        H.say(f"span probes s={time.monotonic() - t:.1f} "
              f"requests={len(probes)} "
              f"tokens={sum(len(r['tokens']) for r in probed)}")
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
    H.say("engine " + " ".join(
        f"{n[len('kfx_lm_'):]}={grew(n):g}" for n in (
            "kfx_lm_engine_chunks_total", "kfx_lm_prefill_chunks_total",
            "kfx_lm_generated_tokens_total", "kfx_lm_kv_preemptions_total",
            "kfx_lm_sparse_cached_positions_total",
            "kfx_lm_sparse_attended_positions_total",
            "kfx_lm_moe_assignments_total",
            "kfx_lm_moe_assignments_held_total",
            "kfx_lm_moe_dispatches_total", "kfx_lm_moe_max_rows_total"))
          + f" kv_pages_free_now={after.get('kfx_lm_kv_pages_free', -1):g}"
          + f" scrape_seconds={win['scrape_seconds']:.1f}")
    for r in rows:
        if not r["ok"]:
            H.say(f"failed request due_s={r['due_s']:.2f} "
                  f"prompt={r['prompt_len']} asked={r['asked']} "
                  f"got={len(r['tokens'])} error={r.get('error')}")
            break

    # The reference, on the chip the replica has left.
    sample = check_sample(rows, reqs, cell["check_requests"], seed,
                          cfg["index_topk"])
    H.check(bool(sample), "no request finished: nothing to compare",
            window_log[-3000:])
    sample += [{"prompt": p["prompt"], "served": r["tokens"]}
               for p, r in zip(probes, probed)]
    sample_path = os.path.join(run_dir, "check_sample.json")
    with open(sample_path, "w") as f:
        json.dump(sample, f)
    t = time.monotonic()
    out = H.run_child(
        cell["check"],
        ["--config", cfg_path, "--seed", str(seed), "--sample", sample_path]
        + (["--recent"] if control == "recent" else [])
        + (["--reduce-trace", run_dir] if trace else [])
        + ([] if require_tpu else ["--host-fallback"]),
        os.path.join(run_dir, "check.log"),
        env=None if require_tpu else {"JAX_PLATFORMS": "cpu"},
        timeout_s=1500.0)
    ref = H.child_result(out)
    H.say(f"span reference s={time.monotonic() - t:.1f} "
          f"positions={ref['positions']} full={ref['full']['positions']} "
          f"sparse={ref['sparse']['positions']} requests={len(sample)} "
          f"probes={len(probes)} "
          f"lengths={[len(s['prompt']) + len(s['served']) for s in sample[:-len(probes)]]} "
          f"exact_match_share={ref['match_share']:.4f} "
          f"logit_std={ref['logit_std']:.3f}")
    if require_tpu:
        H.device_of(out, "reference", wl["chips"], True)
    limits = cfg["correct"]
    compared = H.print_comparison(
        [{"name": f"served_logit_gap_{stat}.{part}",
          "value": ref[part][f"gap_{stat}"],
          "limit": limits[f"served_logit_gap_{stat}.{part}"]}
         for part in ("full", "sparse") for stat in ("max", "mean")]
        + [{"name": "full_positions_short",
            "value": max(0, cell["full_positions_min"]
                         - ref["full"]["positions"]), "limit": 0},
           {"name": "compilations_in_window", "value": compiled, "limit": 0},
           {"name": "generator_late_p99_ms", "value": late["p99"],
            "limit": base.late_limit_ms(cell, e2e["ttft_p50_ms"])}])

    kv_bytes = (after.get("kfx_lm_kv_pages", 0) * serving["kv_page_size"]
                * after.get("kfx_lm_kv_bytes_per_token", 0))
    dev = dict(device, memory_peak_bytes=int(weight_bytes + kv_bytes))
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
           "seconds": seconds, "e2e": e2e,
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
    cell = manifest.cell(wl["name"])
    mix_path = os.path.join(manifest.BENCH_DIR, "traffic",
                            f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"])
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
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
