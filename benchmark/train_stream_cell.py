"""One run of a training cell: a JAXJob whose command is the
benchmark's worker goes through admission, the scheduler and the gang
like any user's job; the harness reads what the worker measured, then
runs the plain reference on the chips the worker has left."""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from . import harness as H
from . import manifest, reference_compare, stats
from . import kfx_adapter as K


def _wait_job(cp, name: str, seconds: float) -> str:
    limit = time.monotonic() + seconds
    while True:
        job = cp.store.try_get("JAXJob", name)
        log = ""
        if job is not None:
            try:
                log = cp.job_logs("JAXJob", name)
            except FileNotFoundError:
                pass  # worker not started yet
            for c in job.conditions:
                H.check(not (c.type == "Queued" and c.status == "True"
                             and c.reason == "Unschedulable"),
                        f"no accelerator: the job cannot be scheduled: "
                        f"{c.message}")
            if job.is_finished():
                H.check(job.has_condition("Succeeded"),
                        f"JAXJob failed: "
                        f"{[c.to_dict() for c in job.conditions]}",
                        log[-6000:])
                return log
        H.check(time.monotonic() < limit,
                f"JAXJob not finished after {seconds:.0f}s", log[-6000:])
        time.sleep(0.25)


def run(man: Dict[str, Any], wl: Dict[str, Any], seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, control: str = "",
        bench_dir: str = manifest.BENCH_DIR) -> str:
    cell = manifest.cell(wl["name"], bench_dir)
    cfg_path = manifest.config_file(man, wl["config"],
                                    os.path.dirname(bench_dir))
    mix_path = os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")
    cfg = manifest.load_json(cfg_path)
    run_dir = H.fresh_dir(wl["name"])
    home = os.path.join(run_dir, "home")
    os.environ["JAX_LOG_COMPILES"] = "1"
    argv = ["-m", "benchmark.workers.train_worker", "--config", cfg_path,
            "--traffic", mix_path, "--seed", str(seed),
            "--seconds", str(seconds),
            "--trace", str(cell["traced_steps"] if trace else 0),
            "--out", run_dir]
    if not require_tpu:
        argv += ["--host-fallback"]
    if control:
        H.check(control in ("bf16", "stuck"),
                f"a training cell's control is bf16 or stuck, "
                f"not {control!r}")
        argv += (["--param-dtype", "bfloat16"] if control == "bf16"
                 else ["--break-step"])
        H.say(f"CONTROL RUN: {control}")

    from kubeflow_tpu.api.manifest import load_manifests
    from kubeflow_tpu.controlplane import ControlPlane

    name = "bench"
    with ControlPlane(home=home) as cp:
        try:
            if require_tpu:
                H.require_chips(cp, wl["chips"])
            cp.apply(load_manifests(K.jaxjob(
                name, cfg["training"]["layout"], argv)))
            log = _wait_job(cp, name, 1100)
        finally:
            if cp.store.try_get("JAXJob", name):
                cp.store.delete("JAXJob", name)
            try:
                H.wait_gone(home, "train worker", 60)
            finally:
                H.kill_children(home)
    device = H.device_of(log, "worker", wl["chips"], require_tpu)
    w = manifest.load_json(os.path.join(run_dir, "worker.json"))
    for tag in ("worker_start ", "state_made ", "first_step ",
                "checked_steps "):
        H.say(f"worker {tag}{H.tagged(log, tag)[-1]}")
    setup_s = w["window_wall"] - H.T0
    steps = w["step_s"]
    tokens_per_s = len(steps) * w["tokens_per_step"] / w["elapsed_s"]
    window_log = log.split("window_open", 1)[1].split("window_closed")[0]
    compiled = H.compilations(window_log)
    H.say(f"window steps={len(steps)} elapsed_s={w['elapsed_s']:.3f} "
          f"step_median_s={stats.median(steps):.4f} "
          f"tokens_per_s={tokens_per_s:.1f} setup_s={setup_s:.1f} "
          f"compilations_in_window={compiled} "
          f"loss_first={w['check']['losses'][0]:.4f} "
          f"loss_last={w['losses'][-1]:.4f}")

    t = time.monotonic()
    out = H.run_child(
        "benchmark.check_train",
        ["--config", cfg_path, "--traffic", mix_path, "--seed", str(seed)],
        os.path.join(run_dir, "check.log"),
        env=None if require_tpu else {"JAX_PLATFORMS": "cpu"})
    ref = H.child_result(out)
    H.say(f"span reference s={time.monotonic() - t:.1f} phases: "
          + ", ".join(H.tagged(out, "reference_phase ")))
    if require_tpu:
        H.device_of(out, "reference", wl["chips"], True)
    limits = cfg["correct"]
    rows = reference_compare.training_rows(w["check"], ref, limits)
    rows.append({"name": "compilations_in_window", "value": compiled,
                 "limit": 0})
    compared = H.print_comparison(rows)

    dev = dict(device, memory_peak_bytes=int(w["memory_peak_bytes"]))
    e2e = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    if not trace:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in manifest.metrics_for(man, "end_to_end",
                                                 wl["name"])}
        return H.result_line(compared, len(steps), 0, metrics, dev)
    tr = w["trace"]
    dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    ctx = {"worker": w, "trace": tr, "cfg": cfg, "cell": cell,
           "mix": manifest.load_json(mix_path), "device": device,
           "seconds": seconds, "e2e": e2e}
    metrics = manifest.read_layer_metrics(man, wl["name"], ctx, bench_dir)
    return H.result_line(compared, len(steps), 0, metrics, dev,
                         tr.get("breakdown"))
