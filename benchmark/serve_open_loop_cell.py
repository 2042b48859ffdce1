"""One run of a serving cell: export -> InferenceService -> Ready ->
warm-up -> open-loop window through the router -> tear-down ->
reference on the chip.

``--trace 0`` serves with the stock predictor the operator spawns;
``--trace 1`` with ``benchmark/workers/traced_replica.py`` as a custom
container, which takes a profiler trace of a few seconds of the window
and otherwise calls the program's server unchanged.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
from typing import Any, Dict, List

from . import harness as H
from . import loadgen, manifest, stats, traffic
from . import kfx_adapter as K

# The program's background warm thread compiles every prompt bucket up
# to max_seq_len // 2 (serving/engine.py); the gauge counts them.
WARM_GAUGE = "kfx_lm_warm_buckets"


def _wait_ready(cp, name: str, replica_log: str, seconds: float):
    limit = time.monotonic() + seconds
    while True:
        isvc = cp.store.try_get("InferenceService", name)
        log = H.read(replica_log)
        if isvc is not None and isvc.has_condition("Ready"):
            return isvc
        H.check("Traceback" not in log, "replica crashed while loading", log)
        H.check(time.monotonic() < limit,
                f"InferenceService not Ready after {seconds:.0f}s", log)
        time.sleep(0.25)


def _wait_warm(metrics_url: str, max_seq_len: int, seconds: float) -> int:
    """Until the replica's background compiles are over, so that none
    runs inside the window: the gauge has reached the number of buckets
    the engine warms (powers of two from 8 to max_seq_len // 2), or, if
    a later engine warms another set, has stopped rising for as long as
    a compile can take."""
    expected, b = 0, 8
    while b <= max(8, max_seq_len // 2):
        expected, b = expected + 1, b * 2
    limit = time.monotonic() + seconds
    last, since = -1.0, time.monotonic()
    while time.monotonic() < limit:
        now = loadgen.scrape(metrics_url).get(WARM_GAUGE, 0.0)
        if now != last:
            last, since = now, time.monotonic()
        if now >= expected or time.monotonic() - since > 45.0:
            break
        time.sleep(0.25)
    return int(last)


def request_rows(reqs: List[Dict[str, Any]], res: Dict[str, Any],
                 seconds: float) -> List[Dict[str, Any]]:
    """Per request: what was asked and what the client saw, with times
    relative to the window's start."""
    rows = []
    for req, r in zip(reqs, res["results"]):
        row = {"due_s": req["due_s"], "prompt_len": len(req["prompt"]),
               "asked": req["max_new_tokens"], "ok": False, "ttft_s": None,
               "tpot_s": None, "late_s": None, "tokens": [], "timing": None,
               "in_window": False, "end_s": None, "times_in_window": []}
        if r is not None:   # times are relative to the window's start
            row["late_s"] = r["t_sent"] - req["due_s"]
            row["tokens"] = r["tokens"]
            row["times_in_window"] = [t for t in r["times"] if t <= seconds]
            row["timing"] = r["timing"]
            row["error"] = r["error"]
            row["ok"] = bool(r["done"] and not r["error"]
                             and len(r["tokens"]) == req["max_new_tokens"])
            if row["ok"]:
                row["ttft_s"] = r["t_first"] - req["due_s"]
                if len(r["tokens"]) > 1:
                    row["tpot_s"] = (r["t_last"] - r["t_first"]) / (
                        len(r["tokens"]) - 1)
                row["end_s"] = r["t_end"]
                row["in_window"] = row["end_s"] <= seconds
        rows.append(row)
    return rows


def delivered_rate(rows: List[Dict[str, Any]]) -> float:
    """Output tokens a second over the window: the tokens that reached
    the client inside it, over the time from its start to the last of
    those deliveries. The clock stops at a delivery, not at the
    window's edge, because the engine hands over a chunk of every live
    row at one instant (up to 128 tokens, 2 % of a window's): against
    a fixed edge the rate reads 1.3 % more or less with the side of
    the edge that one hand-over falls on (PERF.md, PR 24)."""
    times = [t for r in rows for t in r["times_in_window"]]
    return len(times) / max(times) if times else 0.0


def end_to_end(rows: List[Dict[str, Any]]) -> Dict[str, float]:
    ttft = stats.with_failures([r["ttft_s"] for r in rows])
    tpot = stats.with_failures(
        [r["tpot_s"] for r in rows if r["asked"] > 1 or not r["ok"]])
    return {"ttft_p50_ms": 1e3 * stats.median(ttft),
            "tpot_p50_ms": 1e3 * stats.median(tpot),
            "out_tokens_per_s": delivered_rate(rows),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p95_ms": 1e3 * stats.percentile(tpot, 95)}


def lateness_ms(rows: List[Dict[str, Any]]) -> Dict[str, float]:
    """How late the sender ran, over the requests it sent: the 99th
    percentile (by rank, the value ``correct`` holds) and the largest."""
    late = sorted(1e3 * r["late_s"] for r in rows if r["late_s"] is not None)
    if not late:
        return {"p99": float("inf"), "max": float("inf")}
    return {"p99": late[int(0.99 * (len(late) - 1))], "max": late[-1]}


def late_limit_ms(cell: Dict[str, Any], ttft_p50_ms: float) -> float:
    """The limit of ``generator_late_p99_ms``: a share of the run's
    median TTFT (lateness only inflates TTFT: a request is timed from
    when it was due), but never under the cell's floor. The share
    alone shrank with every gain of the program, down to the size of
    the machine's own pauses (PERF.md section 2)."""
    return max(cell["late_floor_ms"], cell["late_share_limit"] * ttft_p50_ms)


def check_sample(rows: List[Dict[str, Any]], reqs: List[Dict[str, Any]],
                 n: int, seed: int) -> List[Dict[str, Any]]:
    """A seeded sample of the finished requests, the longest in it."""
    done = [i for i, r in enumerate(rows) if r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda i: rows[i]["prompt_len"]
                  + len(rows[i]["tokens"]))
    rest = [i for i in done if i != longest]
    random.Random(seed).shuffle(rest)
    return [{"prompt": reqs[i]["prompt"], "served": rows[i]["tokens"]}
            for i in [longest] + rest[:max(0, n - 1)]]


class Served:
    """The cell's InferenceService, up and warm for as long as the
    ``with`` block lasts: export written, service applied and Ready,
    every program this mix uses compiled, the mix's set-up traffic
    sent. Leaves no process behind."""

    name = "bench"

    def __init__(self, cfg_path: str, cfg: Dict[str, Any],
                 serving: Dict[str, Any], mix_path: str,
                 cell: Dict[str, Any], run_dir: str, seed: int, chips: int,
                 trace: bool, require_tpu: bool):
        self.cfg_path, self.cfg, self.serving = cfg_path, cfg, serving
        self.mix_path, self.mix = mix_path, manifest.table(mix_path)
        self.cell, self.run_dir, self.seed = cell, run_dir, seed
        self.chips, self.trace, self.require_tpu = chips, trace, require_tpu
        self.home = os.path.join(run_dir, "home")
        self.export = os.path.join(run_dir, "export")
        self.replica_log = os.path.join(
            self.home, "serving", f"default_{self.name}", "default-0.log")

    def __enter__(self) -> "Served":
        from kubeflow_tpu.controlplane import ControlPlane

        os.environ.update(K.replica_env(self.serving))
        os.environ["JAX_LOG_COMPILES"] = "1"
        if self.trace:
            os.environ.update(K.spec_env(self.serving))
        self.cp = ControlPlane(home=self.home)
        self.cp.__enter__()
        try:
            self._bring_up()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def _bring_up(self) -> None:
        from kubeflow_tpu.api.manifest import load_manifests

        cp, serving, name = self.cp, self.serving, self.name
        if self.require_tpu:
            H.require_chips(cp, self.chips)
        t = time.monotonic()
        out = H.run_child(
            "benchmark.workers.export_writer",
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
        isvc = _wait_ready(cp, name, self.replica_log, 1000)
        self.url = isvc.status["url"]
        (_, self.metrics_url), = \
            cp.manager.controllers["InferenceService"].scrape_targets()
        self.device = H.device_of(H.read(self.replica_log), "replica",
                                  self.chips, self.require_tpu)
        H.say(f"span serve.ready s={time.monotonic() - t:.1f} "
              f"device={self.device} url={self.url}")

        t = time.monotonic()
        warm = [{"prompt": [1 + (i * 7 + j) % 1000 for j in range(n)],
                 "max_new_tokens": self.cell["warm_new_tokens"],
                 "temperature": 0.0}
                for i, n in enumerate(traffic.warm_prompt_lengths(self.mix))]
        loadgen.send_all(self.url, name, warm)
        loadgen.send_all(self.url, name, traffic.setup_requests(
            self.mix, self.cfg["vocab_size"], self.seed))
        buckets = _wait_warm(self.metrics_url, serving["max_seq_len"], 900)
        H.say(f"span serve.warm s={time.monotonic() - t:.1f} "
              f"warm_requests={len(warm)} warm_buckets={buckets}")

    def window(self, rate: float, seconds: float, seed: int
               ) -> Dict[str, Any]:
        """One open-loop window at ``rate``, sent by a process of its
        own: the requests, what the client saw of each, the counters at
        both edges and what the replica logged meanwhile."""
        reqs = traffic.serve_requests(self.mix, self.cfg["vocab_size"],
                                      rate, seconds, seed)
        H.say(f"traffic {json.dumps(traffic.describe_lengths(reqs))} "
              f"rate_rps={rate}")
        before = loadgen.scrape(self.metrics_url)
        log_at = H.size(self.replica_log)
        out = os.path.join(self.run_dir, "window.json")
        H.run_child(
            "benchmark.loadgen",
            ["--url", self.url, "--model", self.name,
             "--traffic", self.mix_path,
             "--vocab", str(self.cfg["vocab_size"]), "--rate", str(rate),
             "--seconds", str(seconds), "--seed", str(seed),
             "--grace", str(self.cell["grace_s"]), "--out", out],
            os.path.join(self.run_dir, "loadgen.log"),
            timeout_s=seconds + self.cell["grace_s"] + 120)
        res = manifest.load_json(out)
        return {"reqs": reqs, "rows": request_rows(reqs, res, seconds),
                "t0_wall": res["t0_wall"], "before": before,
                "after": loadgen.scrape(self.metrics_url),
                "log": H.read(self.replica_log, log_at)}

    def __exit__(self, *exc) -> None:
        cp = self.cp
        try:
            if cp.store.try_get("InferenceService", self.name):
                cp.store.delete("InferenceService", self.name)
            try:
                H.wait_gone(self.home, "serving replica", 60)
            finally:
                H.kill_children(self.home)
        finally:
            cp.__exit__(None, None, None)


def run(man: Dict[str, Any], wl: Dict[str, Any], seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, control: str = "",
        bench_dir: str = manifest.BENCH_DIR) -> str:
    """Run the cell; returns the result line. Raises RunFailure where
    there is no result to print."""
    cell = manifest.cell(wl["name"], bench_dir)
    mix_path = os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")
    cfg_path = manifest.config_file(man, wl["config"],
                                    os.path.dirname(bench_dir))
    cfg = manifest.load_json(cfg_path)
    serving = dict(cfg["serving"], **cell["serving"])
    if control:
        # The program's own lower-precision path. (int8 *weights* is
        # no control here: load_lm quantizes on the device beside the
        # bfloat16 copy and does not fit at the cell's size.)
        H.check(control == "int8kv",
                f"a serving cell's control is int8kv, not {control!r}")
        serving["quantization"] = {"kv": "int8"}
        H.say(f"CONTROL RUN: quantization={serving['quantization']}")
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
        device, weight_bytes = svc.device, svc.weight_bytes
    rows, reqs, window_log = win["rows"], win["reqs"], win["log"]
    before, after = win["before"], win["after"]

    attempted = len(rows)
    failed = sum(not r["ok"] for r in rows)
    late = lateness_ms(rows)
    e2e = end_to_end(rows)
    compiled = H.compilations(window_log)
    H.say(f"requests attempted={attempted} failed={failed} "
          f"in_window={sum(r['in_window'] for r in rows)} "
          f"tokens_in_window={sum(len(r['times_in_window']) for r in rows)} "
          f"last_delivery_s="
          f"{max((t for r in rows for t in r['times_in_window']), default=0):.3f} "
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
            "kfx_lm_decode_stall_seconds_sum",
            "kfx_lm_queue_wait_seconds_sum"))
          + f" kv_pages_free_now={after.get('kfx_lm_kv_pages_free', -1):g}")
    for r in rows:
        if not r["ok"]:
            H.say(f"failed request due_s={r['due_s']:.2f} "
                  f"prompt={r['prompt_len']} asked={r['asked']} "
                  f"got={len(r['tokens'])} error={r.get('error')}")
            break

    # The reference, on the chip the replica has left.
    sample = check_sample(rows, reqs, cell["check_requests"], seed)
    H.check(bool(sample), "no request finished: nothing to compare",
            window_log[-3000:])
    sample_path = os.path.join(run_dir, "check_sample.json")
    with open(sample_path, "w") as f:
        json.dump(sample, f)
    t = time.monotonic()
    out = H.run_child(
        "benchmark.check_serve",
        ["--config", cfg_path, "--seed", str(seed), "--sample", sample_path,
         "--pad-to", str(serving["max_seq_len"])]
        + (["--reduce-trace", run_dir] if trace else [])
        + ([] if require_tpu else ["--host-fallback"]),
        os.path.join(run_dir, "check.log"),
        env=None if require_tpu else {"JAX_PLATFORMS": "cpu"})
    ref = H.child_result(out)
    H.say(f"span reference s={time.monotonic() - t:.1f} "
          f"positions={ref['positions']} requests={len(sample)} "
          f"exact_match_share={ref['match_share']:.4f}")
    if require_tpu:
        H.device_of(out, "reference", wl["chips"], True)
    limits = cfg["correct"]
    compared = H.print_comparison([
        {"name": "served_logit_gap_max", "value": ref["gap_max"],
         "limit": limits["served_logit_gap_max"]},
        {"name": "served_logit_gap_mean", "value": ref["gap_mean"],
         "limit": limits["served_logit_gap_mean"]},
        {"name": "compilations_in_window", "value": compiled, "limit": 0},
        {"name": "generator_late_p99_ms", "value": late["p99"],
         "limit": late_limit_ms(cell, e2e["ttft_p50_ms"])},
    ])

    kv_bytes = (after.get("kfx_lm_kv_pages", 0)
                * serving["kv_page_size"]
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
           "seconds": seconds, "e2e": e2e}
    metrics = manifest.read_layer_metrics(man, wl["name"], ctx, bench_dir)
    return H.result_line(compared, attempted, failed, metrics, dev,
                         tr.get("breakdown"))
