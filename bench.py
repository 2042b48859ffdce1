"""Benchmark entrypoint (driver contract): prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Measures the north-star config (BASELINE.md): the stock MNIST JAXJob
completing end-to-end through `kfx` resource semantics — apply → reconcile
→ gang launch → sharded training → Succeeded — on the real attached TPU.

vs_baseline: the reference publishes no numbers (BASELINE.md: upstream
Kubeflow ships pass/fail smoke tests only; BASELINE.json "published": {}).
The acceptance contract is "GPU-job wall-clock parity" for this example;
PARITY_BUDGET_S below is the documented stand-in for the reference GPU
wall-clock (one minute for the mnist training-operator example), so
vs_baseline = PARITY_BUDGET_S / measured (>1.0 = faster than parity).

Usage: python bench.py [--steps N] [--batch-size N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PARITY_BUDGET_S = 60.0

# The BENCH_CONTRACT key set (module-level so tests/test_bench_guard.py
# pins it: a key silently dropped from the compact line would read as
# "budget cut this section" forever after).
CONTRACT_KEYS = (
    "metric", "value", "unit", "vs_baseline", "final_accuracy",
    "tfjob_mnist_wall_s", "pytorchjob_mnist_wall_s",
    "mpijob_resnet_cifar10_wall_s", "katib_random_sweep_wall_s",
    "serving_p50_ms", "serving_p50_placement",
    "serving_throughput_rps", "serving_batched_p50_ms",
    "serving_batched_p99_ms",
    "lm_mfu", "lm_best_mfu", "lm_long_mfu", "lm_long_tokens_per_s",
    "lm_step_cv", "lm_best_step_cv", "lm_long_step_cv",
    "lm_best_config", "lm_long_config",
    "resnet50_mfu", "resnet50_best_mfu", "resnet50_images_per_s",
    "lm_decode_base_tokens_per_s", "lm_decode_b16_tokens_per_s",
    "lm_engine_concurrent_tokens_per_s", "lm_engine_speedup",
    "lm_engine_prefill_skipped_frac", "lm_engine_kv_bytes_per_token",
    "lm_engine_prefix_tokens_per_s",
    "lm_spec_accept_rate", "lm_spec_tokens_per_s", "lm_spec_speedup",
    "lm_spec_b4_speedup",
    "lm_quant_base_tokens_per_s", "lm_quant_ppl_f32",
    "lm_quant_w8_tokens_per_s",
    "lm_quant_w8_speedup", "lm_quant_w8_ppl_delta",
    "lm_quant_kv8_tokens_per_s", "lm_quant_kv8_ppl_delta",
    "lm_quant_kv8_admit_ratio", "lm_quant_w8kv8_tokens_per_s",
    "lm_quant_w8kv8_ppl_delta", "lm_quant_weight_bytes_ratio",
    "lm_quant_draft8_tokens_per_s", "lm_quant_draft8_accept_rate",
    "lm_quant_draft8_speedup",
    "lm_mixed_itl_p99_off_ms", "lm_mixed_itl_p99_on_ms",
    "lm_mixed_itl_improvement", "lm_mixed_prefill_skipped_frac",
    "lm_mixed_prefill_skipped_frac_blind", "lm_mixed_affinity_hits",
    "lm_adapters_n", "lm_adapters_tokens_per_s",
    "lm_adapters_base_tokens_per_s", "lm_adapters_hbm_mb",
    "lm_adapters_hbm_ratio", "lm_adapters_sep_engines_hbm_ratio",
    "lm_multimodel_n", "lm_multimodel_tokens_per_s",
    "lm_multimodel_hbm_mb", "lm_multimodel_base_hbm_mb",
    "lm_multimodel_hbm_ratio", "lm_multimodel_sep_engines_hbm_ratio",
    "lm_multimodel_byte_identical", "lm_multimodel_swap_cold_s",
    "lm_multimodel_respawn_cold_s",
    "lm_qos_interactive_itl_p99_ms", "lm_qos_interactive_itl_p99_flood_ms",
    "lm_qos_flood_ratio", "lm_qos_batch_served",
    "lm_qos_deadline_shed", "lm_qos_deadline_timeouts",
    "lm_disagg_handoffs", "lm_disagg_tokens_per_s",
    "lm_disagg_interleaved_tokens_per_s", "lm_disagg_itl_p99_ms",
    "lm_disagg_interleaved_itl_p99_ms",
    "lm_disagg_migrate_ms_c64", "lm_disagg_recompute_ms_c64",
    "lm_disagg_migrate_ms_c128", "lm_disagg_recompute_ms_c128",
    "lm_disagg_migrate_ms_c224", "lm_disagg_recompute_ms_c224",
    "lm_disagg_migrate_speedup",
    "serving_scale_p50_ms", "serving_scale_p99_ms",
    "serving_scale_success_rate", "serving_scale_max_replicas",
    "serving_scale_cold_start_ms", "serving_scale_rolled_back",
    "serving_scale_preempted_training",
    "obs_scrape_ms", "obs_rule_eval_ms", "obs_tsdb_window_samples",
    "obs_engine_tokens_per_s", "obs_engine_tokens_delta_frac",
    "obs_flightrec_tokens_delta_frac",
    "obs_slo_eval_ms", "obs_slo_tokens_delta_frac",
    "cpu_count", "host_speed_score", "load_avg_max",
    "contaminated_sections", "sections_skipped_for_budget",
    "bench_wall_s")


def _ancestors(pid: int, limit: int = 25) -> list:
    """ppid chain of ``pid`` up to init (best-effort; races are fine —
    a vanished process is no longer contention)."""
    out = []
    for _ in range(limit):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split(")")[-1].split()[1])
        except (OSError, ValueError, IndexError):
            break
        if ppid <= 0:
            break
        out.append(ppid)
        pid = ppid
        if ppid == 1:
            break
    return out


def _find_strays(root: int = 0) -> list:
    """Framework worker processes that are NOT this bench's own: a
    leaked 100k-step test worker contended the entire round-2
    measurement window, and a concurrent builder session inflated the
    round-3 mnist number 13s→44s mid-run. Strays are reported, not
    killed: they are evidence, and killing them would hide the
    contention that tainted the numbers.

    Any process whose ANCESTRY contains ``root`` (default: this process)
    is ours — gang workers, mpi-launcher ranks (grandchildren), etc. —
    and is measurement, not contamination. Tests pass a foreign ``root``
    to make a planted descendant count as a stray."""
    me = root or os.getpid()
    strays = []
    try:
        for pid_s in os.listdir("/proc"):
            if not pid_s.isdigit() or int(pid_s) == me:
                continue
            pid = int(pid_s)
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(
                        "utf-8", "replace").strip()
            except OSError:
                continue
            if "kubeflow_tpu.runners" in cmd or "kfx-bench" in cmd:
                if me in _ancestors(pid):
                    continue  # our own descendant at any depth
                strays.append({"pid": pid, "cmd": cmd[:120]})
    except OSError:
        pass
    return strays


class _BoxGuard:
    """Contamination guard: a background thread samples strays + load
    every few seconds and attributes each sample to the CURRENT bench
    section, so a process appearing (and even exiting) mid-section
    leaves a trace — the start-only snapshot was blind to exactly the
    round-3 13s→44s mid-run contamination. Sections with strays are
    flagged; per-section max load and the run-wide max are recorded."""

    PERIOD_S = 5.0

    def __init__(self, root: int = 0):
        import threading

        self._root = root
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        self._label = "start"
        self._t0 = None
        self.sections = {}
        self.flagged = []
        self.max_load = 0.0
        self.stray_evidence = []

    def start(self):
        import threading

        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-box-guard")
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.PERIOD_S):
            self.sample()

    def section(self, label: str) -> None:
        """Enter a new section: close out the previous one with a final
        sample, then attribute subsequent samples to ``label``."""
        self.sample()
        with self._lock:
            self._label = label
            if self._t0 is None:
                self._t0 = time.monotonic()
            # Progress on stderr (stdout carries only the JSON line):
            # when a run blows its budget, this shows which section ate it.
            print(f"[bench] t+{time.monotonic() - self._t0:7.1f}s "
                  f"section={label}", file=sys.stderr, flush=True)
        self.sample()

    def sample(self, label: str = "") -> None:
        strays = _find_strays(self._root)
        load = round(os.getloadavg()[0], 2)
        with self._lock:
            label = label or self._label
            rec = self.sections.setdefault(
                label, {"strays": 0, "load_avg": 0.0, "samples": 0})
            rec["samples"] += 1
            rec["strays"] = max(rec["strays"], len(strays))
            rec["load_avg"] = max(rec["load_avg"], load)
            self.max_load = max(self.max_load, load)
            if strays and label not in self.flagged:
                self.flagged.append(label)
                self.stray_evidence.extend(strays[:3])

    def finish(self) -> dict:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self.sample("end")
        with self._lock:
            out = {"load_avg_max": self.max_load,
                   "box_sections": self.sections,
                   "contaminated_sections": list(self.flagged)}
            if self.stray_evidence:
                out["stray_workers"] = self.stray_evidence[:6]
            return out


def _host_speed_score(matmuls: int = 60, n: int = 384) -> float:
    """Single-core host speed: a fixed chain of f64 matmuls (~2s on a
    typical idle core) in a BLAS-single-threaded subprocess; score =
    matmuls/second. The CPU-bound contract rows (tfjob/pytorchjob/mpijob/
    katib walls) are only comparable across rounds at similar scores —
    r4's four "regressions" were all host shape (1 exposed core), and
    without this number a real regression would be indistinguishable
    from a slow box (BASELINE.md comparability rule)."""
    import subprocess

    code = (
        "import time, numpy as np\n"
        f"a = np.random.default_rng(0).standard_normal(({n}, {n}))\n"
        "t0 = time.perf_counter()\n"
        f"for _ in range({matmuls}): a = np.tanh(a @ a / {n})\n"
        "print(time.perf_counter() - t0)\n")
    env = dict(os.environ, OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1",
               MKL_NUM_THREADS="1", VECLIB_MAXIMUM_THREADS="1",
               NUMEXPR_NUM_THREADS="1")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    if r.returncode != 0 or not r.stdout.strip():
        raise RuntimeError(
            f"calibration child rc={r.returncode}: "
            f"{(r.stderr or '').strip()[:120]}")
    return round(matmuls / float(r.stdout.strip()), 1)


def _box_check() -> dict:
    """Start-of-run snapshot (kept as stable top-level fields; the
    per-section story lives in _BoxGuard's report)."""
    strays = _find_strays()
    out = {"stray_workers_at_start": len(strays),
           "load_avg_at_start": round(os.getloadavg()[0], 2),
           # Host shape, for cross-round comparability of the CPU-bound
           # rows: the round-4 box exposes ONE core (full suite 1008s in
           # r3 -> 2896s in r4 on identical tests), so wall-clock deltas
           # must be read against this field, not assumed to be code.
           "cpu_count": len(os.sched_getaffinity(0))}
    try:
        out["host_speed_score"] = _host_speed_score()
    except Exception as e:  # calibration must never sink the bench
        out["host_speed_error"] = str(e)[:120]
    if strays:
        out["stray_workers_at_start_evidence"] = strays[:5]
    return out

MANIFEST = """
apiVersion: kubeflow.org/v1
kind: JAXJob
metadata:
  name: bench-mnist
  namespace: default
spec:
  runPolicy:
    backoffLimit: 0
  jaxReplicaSpecs:
    Worker:
      replicas: 1
      restartPolicy: Never
      template:
        spec:
          containers:
          - name: jax
            command: ["{python}", "-m", "kubeflow_tpu.runners.jax_runner"]
            args:
            - "--model=mlp"
            - "--dataset=mnist"
            - "--steps={steps}"
            - "--batch-size={batch_size}"
            - "--log-every=100"
            - "--scan-steps=50"
            - "--no-checkpoint"
"""


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=600)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--timeout", type=float, default=1200.0)
    args = p.parse_args()

    import tempfile

    from kubeflow_tpu.controlplane import ControlPlane

    import shutil

    run_t0 = time.time()  # budget clock starts before ANY jax work
    box = _box_check()
    # Persistent XLA compile cache for the in-process sections (lm/
    # decode/resnet): compile time is not the measured quantity — every
    # section times steps after a warmup dispatch. Environment only:
    # this process must not touch JAX until the MNIST JAXJob's worker,
    # which needs the chip, has come and gone.
    from kubeflow_tpu.runners.jax_runner import enable_compile_cache

    enable_compile_cache()
    guard = _BoxGuard().start()
    guard.section("mnist_jaxjob")
    home = tempfile.mkdtemp(prefix="kfx-bench-")
    # worker_platform="" -> the worker inherits the machine's default JAX
    # platform (the attached TPU); single worker, whole chip.
    t0 = time.time()
    try:
        with ControlPlane(home=home, worker_platform="") as cp:
            cp.apply_text(MANIFEST.format(python=sys.executable,
                                          steps=args.steps,
                                          batch_size=args.batch_size))
            job = cp.wait_for_job("JAXJob", "bench-mnist",
                                  timeout=args.timeout)
            wall = time.time() - t0
            log = cp.job_logs("JAXJob", "bench-mnist")
    finally:
        shutil.rmtree(home, ignore_errors=True)
    if not job.has_condition("Succeeded"):
        print(json.dumps({"metric": "mnist_jaxjob_wall_clock_s",
                          "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                          "error": "job failed", "log_tail": log[-2000:]}))
        return 1

    acc = None
    for line in log.splitlines():
        if line.startswith("accuracy="):
            acc = float(line.split("=", 1)[1])

    # Optional sections run oldest-contract-first under a wall budget so
    # a driver-side timeout can only cost the newest metrics, never the
    # whole JSON line (KFX_BENCH_BUDGET_S to tune; sections check before
    # starting, not mid-flight).
    # 2100: r4 measured 1177s for the pre-r5 sections; the r5 additions
    # (serving load leg, resnet ladder + 224^2 probe, flagship decode)
    # add ~600s of estimates. The have_time gate still trims the newest
    # sections first if the box runs slow.
    budget = float(os.environ.get("KFX_BENCH_BUDGET_S", "2100"))
    bench_t0 = run_t0  # whole-run clock: setup + mnist phase count too

    skipped = []

    def have_time(est_s: float, label: str = "") -> bool:
        ok = (time.time() - bench_t0) + est_s < budget
        if not ok and label:
            skipped.append(label)
        return ok

    # Every number below is a device number: without a TPU this is not a
    # benchmark, and it says so instead of timing the CPU backend. (The
    # MNIST worker is gone by now, so this process may take the chip.)
    import jax

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"metric": "mnist_jaxjob_wall_clock_s",
                          "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                          "error": f"no TPU: jax found "
                                   f"{jax.devices()[0].platform!r}"}))
        return 1

    guard.section("serving")
    serving = _bench_serving_p50()
    lm: dict = {}
    if have_time(150, "obs_overhead"):
        # Telemetry plane (obs/tsdb.py + obs/rules.py): one scrape
        # cycle's cost (render + parse + ingest) with the store at a
        # 10k-sample window, default-rule-pack evaluation cost over
        # that window, and the engine-throughput tax of a live scrape
        # loop (acceptance: tokens/s delta <= 2%).
        guard.section("obs_overhead")
        lm.update(_bench_obs_overhead())
    if have_time(240, "lm"):
        # save_dense selective remat: keep the fat matmul outputs,
        # recompute only elementwise + the S^2 block — measured 4.8%
        # faster than full remat at this shape (ABAB, idle box); the
        # linear-in-S saves fit HBM at S=512 but not at S=2048.
        guard.section("lm")
        lm.update(_bench_lm(remat_policy="save_dense"))
    if have_time(300, "lm_long"):
        # Long-context ladder: S=2048 rides the pallas flash-attention
        # kernel (attn_impl="auto" switches at S>=1024 since round 5;
        # measured 1.24x over the XLA dense path at this shape on the
        # v5e). Rung 1 is the round-5 incumbent: save_flash_full keeps
        # the kernel's (o, lse) residuals + q/k/v/out/wo so the remat
        # backward runs only the flash backward kernels (measured 864.6
        # -> 796.9 ms/step, +8.5% MFU over full remat). Rung 2 probes
        # the batch axis: b16 with the minimal flash save set +
        # chunked CE (loss_chunk keeps the [B,S,vocab] f32 logits from
        # ever materialising whole — the transient that used to cap
        # batch) — bigger batch amortises the per-step fixed work; an
        # HBM overflow just loses the rung, not the section.
        guard.section("lm_long")
        lm.update(_bench_lm_ladder("lm_long_", [
            ("b8/save_flash_full",
             dict(batch=8, seq_len=2048, n_steps=6,
                  remat_policy="save_flash_full")),
            ("b16/save_flash_min/chunked",
             dict(batch=16, seq_len=2048, n_steps=6,
                  remat_policy="save_flash_min",
                  overrides={"loss_chunk": 256})),
        ], have_time))
    if have_time(300, "lm_best"):
        # Best-MFU ladder (round-4 discipline, recorded in BASELINE.md):
        # arithmetic intensity rises with d_model, so the chip's
        # ceiling is probed at d=2048 with layers cut to fit HBM —
        # d2048/L8 (668M params, b16, S=512, save_dense) measured 0.53
        # MFU vs the base preset's 0.41-0.42. Pre-loss_chunk, one notch
        # up in ANY direction (L12, b20, b24, S=1024, no-remat) failed
        # AOT buffer assignment on the 15.75G chip; chunked CE frees
        # the 1G f32 logits transient, so rung 2 re-probes no-remat
        # (remat recompute is the one overhead MFU's accounting
        # penalises — eliminating it is pure utilisation) and rung 3
        # re-probes b20. Failed rungs are recorded, not fatal.
        guard.section("lm_best")
        lm.update(_bench_lm_ladder("lm_best_", [
            ("b16/save_dense",
             dict(preset="large", overrides={"n_layers": 8}, batch=16,
                  seq_len=512, n_steps=8, remat_policy="save_dense")),
            ("b16/noremat/chunked",
             dict(preset="large",
                  overrides={"n_layers": 8, "loss_chunk": 512},
                  batch=16, seq_len=512, n_steps=8, remat=False)),
            ("b20/noremat/chunked",
             dict(preset="large",
                  overrides={"n_layers": 8, "loss_chunk": 512},
                  batch=20, seq_len=512, n_steps=8, remat=False)),
        ], have_time))
    if have_time(420, "baseline_configs"):
        guard.section("baseline_configs")
        lm.update(_bench_baseline_configs(
            deadline=bench_t0 + budget))
    # resnet50 is BASELINE contract #3a (the ResNet-50 number, measured
    # where the chip is) — contract metrics outrank the decode extra.
    if have_time(480, "resnet50"):  # incl. ladder + 224^2 probe compiles
        guard.section("resnet50")
        lm.update(_bench_resnet50())
    if have_time(300, "lm_decode"):
        guard.section("lm_decode")
        lm.update(_bench_lm_decode())
    if have_time(300, "lm_decode_b16"):
        # Batched decode: 4x the batch shares the same per-step
        # dispatch. Estimate matches the base decode section: a new
        # shape pays the same one-time compile.
        guard.section("lm_decode_b16")
        lm.update(_bench_lm_decode(batch=16, prefix="lm_decode_b16_"))
    if have_time(400, "lm_decode_base"):
        # Flagship decode (r4 verdict: generation throughput was only
        # known at toy scale): the 468M base preset, batch 8, a 512-token
        # prompt — the KV cache ([B, 576, H*D] bf16 x2 x24 layers
        # ~= 0.5G) rides comfortably in HBM beside the f32 params.
        guard.section("lm_decode_base")
        lm.update(_bench_lm_decode(preset="base", batch=8, prompt_len=512,
                                   max_new=64, max_seq_len=640,
                                   prefix="lm_decode_base_"))
    if have_time(200, "serving_scale"):
        # Serving autoscaler (serving/autoscaler.py): sustained RPS ramp
        # against one InferenceService — scale 0->max on concurrency
        # (cold start measured), a mid-ramp canary with injected faults
        # auto-rolled-back on SLO breach, low-priority training
        # preempted for chips and resumed on scale-in.
        guard.section("serving_scale")
        lm.update(_bench_serving_scale())
    if have_time(300, "lm_engine"):
        # Continuous batching (serving/engine.py): aggregate decode
        # throughput with 8 CONCURRENT single-prompt clients vs the
        # same 8 requests serialized run-to-completion — the serving
        # regime where the one-shot path collapses to ~1/B of the
        # batched number and the slotted engine gets it back.
        guard.section("lm_engine")
        lm.update(_bench_lm_engine())
    if have_time(240, "lm_spec"):
        # Speculative decoding (serving/engine.py draft path): draft
        # on vs off on a weight-streaming-bound d>=384 config at batch
        # 1 and 4 — the small-batch regime where every decoded token
        # used to stream the full weights and the multi-token verify
        # window streams them once per k+1 candidates.
        guard.section("lm_spec")
        lm.update(_bench_lm_spec())
    if have_time(420, "lm_quant"):
        # Quantized serving (serving/engine.py + models/transformer.py
        # quant paths): greedy tokens/s for int8 weights / int8 paged
        # KV / both vs the f32 oracle on the weight-bound d=512
        # config, each variant's perplexity delta scored UNDER THE F32
        # MODEL (speed never silently buys accuracy loss), the
        # byte-budget admission multiplier int8 KV earns, and a
        # quantized-DRAFT speculative leg (accept rate is the only
        # thing a wrong draft can cost).
        guard.section("lm_quant")
        lm.update(_bench_lm_quant())
    if have_time(300, "lm_mixed_trace"):
        # Chunked prefill + prefix-affinity routing (serving/engine.py
        # + serving/router.py): inter-token p99 of short-chat clients
        # while long prompts admit, chunking on vs off (the
        # head-of-line-blocking kill), and the FLEET-level
        # prefill-skipped fraction of a shared-system-prompt mix
        # routed across 2 replicas with affinity vs blind round-robin
        # (the per-replica prefix cache becoming a fleet cache).
        guard.section("lm_mixed_trace")
        lm.update(_bench_lm_mixed_trace())
    if have_time(180, "lm_adapters"):
        # Multi-tenant LoRA adapters (serving/adapters.py): 8 adapters
        # served concurrently over ONE engine (batched-gather — every
        # slot wears a different adapter inside one fused dispatch) vs
        # the 8-separate-merged-engines alternative. The headline is
        # the measured-HBM ratio: one base + stacks vs ~8 bases.
        guard.section("lm_adapters")
        lm.update(_bench_lm_adapters())
    if have_time(240, "lm_multimodel"):
        # Multi-model weight pool (serving/weights.py): 8 whole
        # checkpoints time-sharing ONE engine's chips via refcounted
        # HBM weight slots vs 8 dedicated engines. Headlines: the
        # measured-HBM ratio (bar: <= ~1.5x one engine vs 8x
        # separate), scale-from-zero as a weight SWAP vs an engine
        # respawn (cold-start seconds, same histogram the operator
        # fills), and per-model greedy byte-identity to dedicated
        # engines.
        guard.section("lm_multimodel")
        lm.update(_bench_lm_multimodel())
    if have_time(240, "lm_qos"):
        # Request plane under class pressure (serving/engine.py QoS +
        # deadline admission): interactive p99 ITL with a concurrent
        # batch flood vs without (bar: <= 1.5x — FairQueue admits
        # interactive first, batch is the preemption victim), plus the
        # deadline burst — infeasible requests shed BEFORE prefill,
        # zero post-prefill deadline timeouts.
        guard.section("lm_qos")
        lm.update(_bench_lm_qos())
    if have_time(300, "lm_disagg"):
        # KV transfer plane (serving/kvtransfer.py): asymmetric
        # prefill->decode disaggregation vs one interleaved engine
        # (tokens/s + decode-side p99 ITL), and live-migration cost vs
        # the seeded-re-dispatch recompute at 3 context lengths — the
        # crossover where moving pages beats re-prefilling them.
        guard.section("lm_disagg")
        lm.update(_bench_lm_disagg())
    lm.update(guard.finish())
    if skipped:
        # A missing metric key must read as "budget cut this section",
        # never as silent coverage loss (decode compiles cost ~250s each
        # through the remote-compile helper on the 1-core host, so the
        # tail sections are the ones the 1800s budget trims first).
        lm["sections_skipped_for_budget"] = skipped
    lm["bench_wall_s"] = round(time.time() - bench_t0, 1)
    out = {
        "metric": "mnist_jaxjob_wall_clock_s",
        "value": round(wall, 2),
        "unit": "s",
        # vs_baseline honesty: the reference publishes no numbers
        # (BASELINE.json "published": {}), so the denominator is the
        # builder-chosen 60s parity budget. The credible absolute perf
        # signals are lm_mfu / lm_long_mfu / resnet50_images_per_s.
        "vs_baseline": round(PARITY_BUDGET_S / wall, 3),
        "vs_baseline_definition": (
            f"builder-chosen parity budget {PARITY_BUDGET_S:.0f}s / "
            f"measured; reference publishes no numbers — see lm_mfu for "
            f"the absolute perf signal"),
        "steps": args.steps,
        "batch_size": args.batch_size,
        "final_accuracy": acc,
    }
    out.update(box)
    out.update(serving)
    out.update(lm)
    print(json.dumps(out))
    # Truncation-proof artifact: the driver records a BOUNDED stdout tail,
    # and r4's single giant line lost its FRONT fields (the north star
    # itself) to that bound. The last line printed is therefore a compact
    # subset holding only the contract keys — whatever the tail keeps, it
    # keeps this.
    compact = {k: out[k] for k in CONTRACT_KEYS if k in out}
    print("BENCH_CONTRACT " + json.dumps(compact))
    return 0


def _bench_lm(preset: str = "base", batch: int = 16, seq_len: int = 512,
              n_steps: int = 12, prefix: str = "lm_",
              remat_policy: str = "nothing", remat: bool = True,
              overrides: dict = None, variance_steps: int = 4) -> dict:
    """Flagship LM measurement on the real TPU: step time, tokens/s, MFU.

    The base preset (d=1024, 24 layers, d_ff=4096 — MXU-shaped dims,
    bf16 compute, scan-over-layers, remat) is trained for n_steps with
    back-to-back dispatch and a single host sync, then MFU is computed
    against the chip's published bf16 peak (utils.flops convention: model
    FLOPs, remat recompute not credited). A short per-step SYNCED leg
    afterwards measures step-time variance (cv = std/mean) — the fused
    dispatch can't see per-step jitter, and the multichip acceptance
    criteria require MFU gains to not regress variance."""
    try:
        import numpy as np

        from kubeflow_tpu.models.transformer import preset_config
        from kubeflow_tpu.parallel.lm_train import LMHyperParams, LMTrainLoop
        from kubeflow_tpu.parallel.mesh import make_mesh
        from kubeflow_tpu.utils.flops import (
            mfu, peak_flops_per_chip, transformer_train_flops_per_token)

        from kubeflow_tpu.data.lm import LMDataset

        cfg = preset_config(preset, max_seq_len=seq_len, remat=remat,
                            remat_policy=remat_policy, **(overrides or {}))
        mesh, plan = make_mesh(1)
        loop = LMTrainLoop(cfg, mesh, plan,
                           LMHyperParams(total_steps=1000, warmup_steps=10))
        state = loop.init_state()
        # Distinct Markov-chain batches per step: loss_after is then a
        # (short) learning signal toward the dataset's entropy floor,
        # not memorization of one repeated batch.
        ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=seq_len)
        it = ds.batches(batch)
        import jax
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(state.params))
        # Warmup (compile + first step), synced.
        state, _, _ = loop.train_many(state, [next(it)])
        steps = [next(it) for _ in range(n_steps)]
        t0 = time.perf_counter()
        state, loss, _ = loop.train_many(state, steps)
        dt = (time.perf_counter() - t0) / n_steps
        fpt = transformer_train_flops_per_token(cfg, seq_len)
        tok_s = batch * seq_len / dt
        # Variance leg: per-step sync (the fused leg reports throughput,
        # this one jitter; the sync overhead is why it is not the MFU
        # source).
        times = []
        for _ in range(max(variance_steps, 0)):
            tv = time.perf_counter()
            state, _, _ = loop.train_many(state, [next(it)])
            times.append(time.perf_counter() - tv)
        cv = (float(np.std(times) / np.mean(times))
              if len(times) >= 2 and np.mean(times) > 0 else 0.0)
        out = {
            "model": preset,
            "params_m": round(n_params / 1e6, 1),
            "batch": batch,
            "seq_len": seq_len,
            "step_time_ms": round(dt * 1000, 2),
            "step_cv": round(cv, 4),
            "tokens_per_s": round(tok_s, 0),
            "flops_per_token": round(fpt, 0),
            "mfu": round(mfu(tok_s, fpt), 4),
            "peak_flops": peak_flops_per_chip(),
            "loss_after": round(float(loss), 3),
            "loss_floor": round(ds.entropy_floor(), 3),
        }
        return {prefix + k: v for k, v in out.items()}
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}


def _bench_lm_ladder(prefix: str, candidates, have_time) -> dict:
    """Run a short ladder of configs for one lm_* section and keep the
    best-MFU rung's numbers under ``prefix`` (+ ``<prefix>config``
    naming the winner, and per-rung MFUs for the trajectory). The first
    rung is the incumbent and always runs; later rungs run only while
    ``have_time(est, label)`` says so, and a rung that fails to compile
    or fit HBM is recorded, not fatal — this is how the remat-policy /
    batch / loss-chunk tuning is MEASURED per hardware instead of
    hardcoded (BASELINE.md ladder discipline)."""
    best: dict = {}
    best_mfu = -1.0
    rungs: dict = {}
    for i, (tag, kw) in enumerate(candidates):
        if i > 0 and not have_time(150, f"{prefix}ladder:{tag}"):
            break
        r = _bench_lm(prefix=prefix, **kw)
        m = r.get(prefix + "mfu")
        if m is None:
            rungs[tag] = r.get(prefix + "error", "no mfu")[:80]
            continue
        rungs[tag] = m
        if m > best_mfu:
            best_mfu, best = m, r
    if not best:
        # Every rung failed: surface the first rung's error.
        tag, kw = candidates[0]
        return {prefix + "error": str(rungs.get(tag, "ladder empty"))[:200],
                prefix + "ladder": rungs}
    winner = max(rungs, key=lambda t: rungs[t]
                 if isinstance(rungs[t], (int, float)) else -1.0)
    best[prefix + "config"] = winner
    best[prefix + "ladder"] = rungs
    return best


def _bench_baseline_configs(deadline: float) -> dict:
    """BASELINE.md configs #1-#4: apply -> Succeeded wall-clock for the
    stock tf-operator/pytorch-operator/mpi-operator examples and the
    Katib random sweep, through full resource semantics (the same
    `kfx run` path a user takes). Config #5 (serving p50) and the
    north-star (#mnist JAXJob) are measured separately. Every wait is
    bounded by ``deadline`` so one wedged config can never eat the whole
    bench budget (the JSON line must always print)."""
    import shutil
    import tempfile

    from kubeflow_tpu.controlplane import ControlPlane

    here = os.path.dirname(os.path.abspath(__file__))
    configs = {
        "tfjob_mnist_wall_s": "tfjob-mnist.yaml",
        "pytorchjob_mnist_wall_s": "pytorchjob-mnist.yaml",
        "mpijob_resnet_cifar10_wall_s": "mpijob-resnet-cifar10.yaml",
        "katib_random_sweep_wall_s": "experiment-random-mnist.yaml",
    }
    out: dict = {}
    for key, fname in configs.items():
        budget_left = deadline - time.time()
        if budget_left < 30:
            out[key.replace("_wall_s", "_error")] = "skipped: bench budget"
            continue
        path = os.path.join(here, "examples", fname)
        home = tempfile.mkdtemp(prefix=f"kfx-bench-{key}-")
        try:
            t0 = time.time()
            # worker_platform=None: workers inherit the plane's platform
            # (operators/training.platform_for).
            with ControlPlane(home=home, worker_platform=None) as cp:
                applied = cp.apply_file(path)
                for obj, _ in applied:
                    if obj.KIND == "Experiment":
                        final = cp.wait_for_condition(
                            obj.KIND, obj.name, "Succeeded",
                            namespace=obj.namespace, timeout=budget_left)
                    else:
                        final = cp.wait_for_job(obj.KIND, obj.name,
                                                timeout=budget_left)
                        if not final.has_condition("Succeeded"):
                            raise RuntimeError(f"{obj.KIND} failed")
            out[key] = round(time.time() - t0, 2)
            if key == "katib_random_sweep_wall_s":
                best = final.status.get("currentOptimalTrial", {})
                metrics = best.get("observation", {}).get("metrics", [])
                if metrics:
                    out["katib_best_objective"] = metrics[0].get("latest")
        except Exception as e:
            out[key.replace("_wall_s", "_error")] = str(e)[:160]
        finally:
            shutil.rmtree(home, ignore_errors=True)
    return out


def _bench_lm_decode(preset: str = "small", batch: int = 4,
                     prompt_len: int = 64, max_new: int = 64,
                     max_seq_len: int = 512,
                     prefix: str = "lm_decode_") -> dict:
    """Generation throughput: jitted KV-cache prefill + scan decode
    (models/generate.py) on the real TPU — decoded tokens per second
    across the batch, measured after the one-time compile."""
    try:
        import numpy as np

        from kubeflow_tpu.models.generate import LMGenerator
        from kubeflow_tpu.models.transformer import (
            TransformerLM, preset_config)

        import jax

        cfg = preset_config(preset, max_seq_len=max_seq_len)
        rng = np.random.default_rng(0)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jax.numpy.zeros((1, 8), jax.numpy.int32))["params"]
        gen = LMGenerator(cfg, params)
        prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                   for _ in range(batch)]
        gen.generate(prompts, max_new_tokens=max_new)  # compile + warm
        t0 = time.perf_counter()
        reps = 3
        for i in range(reps):
            gen.generate(prompts, max_new_tokens=max_new,
                         temperature=0.7, seed=i)
        dt = (time.perf_counter() - t0) / reps
        return {
            prefix + "model": preset,
            prefix + "batch": batch,
            prefix + "prompt_len": prompt_len,
            prefix + "new_tokens": max_new,
            prefix + "tokens_per_s": round(batch * max_new / dt, 1),
            prefix + "ms_per_token": round(dt / max_new * 1000, 2),
        }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}


def _bench_obs_overhead() -> dict:
    """Telemetry-plane overhead micro-section (ISSUE 14 acceptance):

    (a) ``obs_scrape_ms`` — one full scrape cycle (render a
        plane-shaped registry, parse its exposition text, ingest into
        the store) with every series already holding a 10k-sample ring
        buffer (the worst-case window the retention caps allow);
    (b) ``obs_rule_eval_ms`` — evaluating the DEFAULT rule pack
        against that 10k-deep store;
    (c) ``obs_engine_tokens_delta_frac`` — the decode-engine
        throughput tax of a live 0.25s scrape-loop (registry render +
        parse + ingest + rule eval on a background thread, the
        contention a real replica sees); the acceptance bar is <= 2%;
    (d) ``obs_flightrec_tokens_delta_frac`` — the flight recorder's
        own tax: the same engine with the recorder detached vs
        attached (ISSUE 16 acceptance: <= 2% tokens/s).
    """
    prefix = "obs_"
    eng = None
    scraper = None
    try:
        import numpy as np

        import jax

        from kubeflow_tpu.models.transformer import (
            TransformerConfig, TransformerLM)
        from kubeflow_tpu.obs.metrics import MetricsRegistry
        from kubeflow_tpu.obs.rules import RuleEngine, default_rules
        from kubeflow_tpu.obs.tsdb import TSDB, CentralScraper
        from kubeflow_tpu.serving.engine import DecodeEngine
        from kubeflow_tpu.utils.prom import parse_prom_text

        window_samples = 10_000
        # A plane-shaped registry: ~50 families incl. every family the
        # default rule pack queries, labelled like the real plane's.
        reg = MetricsRegistry()
        for i in range(40):
            reg.counter(f"kfx_synth_{i}_total").inc(1 + i, shard="0")
        req = reg.counter("kfx_router_requests_total")
        restarts = reg.counter("kfx_replica_restarts_total")
        rec_h = reg.histogram("kfx_reconcile_duration_seconds")
        qw_h = reg.histogram("kfx_lm_queue_wait_seconds")
        tsdb = TSDB(retention_s=1e12, max_samples=window_samples,
                    max_series=16384)
        families = parse_prom_text(reg.render())
        # Fill every ring buffer to its 10k cap with advancing
        # timestamps (0.06s spacing: the pack's 60-300s windows then
        # cover 1k-5k points each) — the state one long-lived plane
        # reaches and stays at.
        base_ts = 1_000_000.0
        for i in range(window_samples):
            tsdb.ingest(families, ts=base_ts + i * 0.06)
        now = base_ts + window_samples * 0.06
        # (a) the real cycle, registry values advancing per scrape.
        reps = 15
        t0 = time.perf_counter()
        for i in range(reps):
            req.inc(3, namespace="default", isvc="fleet",
                    revision="default", code="2xx")
            restarts.inc(0, namespace="default", isvc="fleet",
                         revision="default", reason="crashed")
            rec_h.observe(0.004, kind="InferenceService")
            qw_h.observe(0.02, model="fleet")
            tsdb.ingest(parse_prom_text(reg.render()),
                        ts=now + (i + 1) * 0.06)
        scrape_ms = (time.perf_counter() - t0) * 1000.0 / reps
        # (b) the default pack over the 10k-deep store.
        rules = RuleEngine(tsdb, default_rules())
        now += (reps + 1) * 0.06
        t0 = time.perf_counter()
        for i in range(reps):
            rules.evaluate(now=now + i * 0.06)
        rule_ms = (time.perf_counter() - t0) * 1000.0 / reps
        # (b2) a 16-SLO pack (burn rates + budgets, ISSUE 18) over the
        # same 10k-deep store — the error-budget cost a plane pays per
        # scrape cycle once SLOs are declared fleet-wide.
        from kubeflow_tpu.api.base import from_manifest
        from kubeflow_tpu.obs.slo import SLOEngine

        slo_eng = SLOEngine(tsdb)
        for i in range(16):
            slo_eng.ensure(from_manifest({
                "apiVersion": "obs.kubeflow.org/v1alpha1",
                "kind": "SLO",
                "metadata": {"name": f"bench-{i}",
                             "namespace": "default"},
                "spec": {"objective": "error-rate", "target": 0.99,
                         "windowSeconds": 300,
                         "selector": {"isvc": "fleet"}}}))
        t0 = time.perf_counter()
        for i in range(reps):
            slo_eng.evaluate(now=now + i * 0.06)
        slo_ms = (time.perf_counter() - t0) * 1000.0 / reps
        # (c) engine tokens/s, unscraped vs under a live scrape loop.
        cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=2,
                                head_dim=32, n_layers=2, d_ff=128,
                                max_seq_len=192,
                                dtype=jax.numpy.float32)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jax.numpy.zeros((1, 8), jax.numpy.int32))["params"]
        rng = np.random.default_rng(0)
        clients, max_new = 4, 48
        eng = DecodeEngine(cfg, params, n_slots=clients, chunk_tokens=8,
                           name="obsbench", kv_page_size=16)
        eng.warm([64])

        def leg():
            prompts = [list(rng.integers(0, cfg.vocab_size, 48))
                       for _ in range(clients)]
            t0 = time.perf_counter()
            eng.generate(prompts, max_new_tokens=max_new)
            return clients * max_new / (time.perf_counter() - t0)

        leg()  # warm the full path
        # (d) flight-recorder tax: same engine, recorder detached vs
        # attached (hooks check `flight is not None`; requests bind it
        # at _make_request, so flipping between legs is clean). The
        # acceptance bar is <= 2% tokens/s.
        recorder = eng.flight
        # Alternate detached/attached legs and keep each condition's
        # best: one leg is only ~40ms of decode, so consecutive-pair
        # sampling measured scheduler noise (10%+ swings), not the
        # recorder's ~1us/iteration append.
        flight_off = flight_on = 0.0
        for _ in range(8):
            eng.flight = None
            flight_off = max(flight_off, leg())
            eng.flight = recorder
            flight_on = max(flight_on, leg())
        flight_delta = max(0.0, (flight_off - flight_on) / flight_off) \
            if flight_off > 0 else 0.0
        # (e) tenant-ledger tax (ISSUE 18 acceptance <= 2%): the same
        # engine with the usage ledger detached vs attached — the
        # billing hooks are one dict update at admission and one at
        # finish, so this bounds the metering vertical's hot-path cost.
        ledger = eng.usage
        meter_off = meter_on = 0.0
        for _ in range(8):
            eng.usage = None
            meter_off = max(meter_off, leg())
            eng.usage = ledger
            meter_on = max(meter_on, leg())
        meter_delta = max(0.0, (meter_off - meter_on) / meter_off) \
            if meter_off > 0 else 0.0
        base = max(flight_off, flight_on, meter_off, meter_on)
        live_tsdb = TSDB()
        scraper = CentralScraper(
            live_tsdb, reg, interval_s=0.25,
            rules=RuleEngine(live_tsdb, default_rules())).start()
        time.sleep(0.3)  # the loop is provably running mid-leg
        scraped = max(leg(), leg())
        scraper.stop()
        delta = max(0.0, (base - scraped) / base) if base > 0 else 0.0
        return {
            prefix + "scrape_ms": round(scrape_ms, 3),
            prefix + "rule_eval_ms": round(rule_ms, 3),
            prefix + "tsdb_window_samples": window_samples,
            prefix + "engine_tokens_per_s": round(base, 1),
            prefix + "engine_tokens_per_s_scraped": round(scraped, 1),
            prefix + "engine_tokens_delta_frac": round(delta, 4),
            prefix + "flightrec_tokens_per_s": round(flight_on, 1),
            prefix + "flightrec_tokens_delta_frac":
                round(flight_delta, 4),
            prefix + "slo_eval_ms": round(slo_ms, 3),
            prefix + "slo_tokens_per_s": round(meter_on, 1),
            prefix + "slo_tokens_delta_frac": round(meter_delta, 4),
        }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        if scraper is not None:
            scraper.stop()
        if eng is not None:
            eng.close()


def _bench_lm_engine(preset: str = "small", clients: int = 8,
                     prompt_len: int = 64, max_new: int = 64,
                     max_seq_len: int = 512, chunk: int = 8,
                     prefix: str = "lm_engine_") -> dict:
    """Continuous-batching serving throughput: ``clients`` concurrent
    single-prompt requests through the slotted DecodeEngine vs the same
    requests serialized through the one-shot LMGenerator (today's
    run-to-completion serving behavior). Both paths pre-warmed; greedy,
    so the outputs are byte-identical and the comparison is pure
    scheduling."""
    eng = None
    try:
        import numpy as np

        import jax

        from kubeflow_tpu.models.generate import LMGenerator
        from kubeflow_tpu.models.transformer import (
            TransformerLM, preset_config)
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg = preset_config(preset, max_seq_len=max_seq_len)
        rng = np.random.default_rng(0)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jax.numpy.zeros((1, 8), jax.numpy.int32))["params"]
        gen = LMGenerator(cfg, params)
        # 16-token pages: the shared system prompt (3/4 of prompt_len)
        # must cover whole pages for the prefix cache to share them —
        # at 64-token prompts a 32-token page would leave only one
        # shareable page (see docs/serving.md, page-size trade-off).
        eng = DecodeEngine(cfg, params, n_slots=clients,
                           chunk_tokens=chunk,
                           request_timeout_s=600.0,
                           kv_page_size=16)
        from kubeflow_tpu.models.generate import pow2_bucket

        sys_len = (3 * prompt_len) // 4
        prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                   for _ in range(clients)]
        gen.generate([prompts[0]], max_new_tokens=max_new)  # warm
        # Engine warm: the full-prompt bucket AND the post-match tail
        # bucket (a prefix hit prefills only the tokens past the
        # matched FULL pages; its compile must not land inside a timed
        # leg). The warm prompt is NOT reused in the legs, so the
        # concurrent leg measures pure scheduling, never an accidental
        # prefix hit.
        tail_len = prompt_len - (sys_len // eng.page_size) * eng.page_size
        eng.warm([pow2_bucket(prompt_len, max_seq_len),
                  pow2_bucket(max(tail_len, 1), max_seq_len)])
        eng.generate([list(rng.integers(0, cfg.vocab_size, prompt_len))],
                     max_new_tokens=max_new)  # warm
        t0 = time.perf_counter()
        for p in prompts:
            gen.generate([p], max_new_tokens=max_new)
        serial_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=max_new)
        engine_dt = time.perf_counter() - t0
        total = clients * max_new
        # Shared-prefix client mix (the million-user chat shape): every
        # client carries the same system prompt (3/4 of the prompt) +
        # a unique tail. The prefix cache prefills the shared pages
        # once; the skipped fraction is measured over THIS leg only
        # (deltas — the unique-prompt legs above would dilute it).
        system = list(rng.integers(0, cfg.vocab_size, sys_len))
        mix = [system + list(rng.integers(0, cfg.vocab_size,
                                          prompt_len - sys_len))
               for _ in range(clients)]
        eng.generate([mix[0]], max_new_tokens=1)  # seed the cache
        stats0 = eng.prefix_stats()
        t0 = time.perf_counter()
        eng.generate(mix, max_new_tokens=max_new)
        mix_dt = time.perf_counter() - t0
        admitted = eng.prefix_stats()["prompt_tokens"] \
            - stats0["prompt_tokens"]
        reused = eng.prefix_stats()["tokens_reused"] \
            - stats0["tokens_reused"]
        return {
            prefix + "model": preset,
            prefix + "clients": clients,
            prefix + "new_tokens": max_new,
            prefix + "chunk_tokens": chunk,
            prefix + "kv_page_size": eng.page_size,
            prefix + "kv_pages": eng.n_pages,
            prefix + "kv_bytes_per_token": eng.kv_bytes_per_token,
            prefix + "serial_tokens_per_s": round(total / serial_dt, 1),
            prefix + "concurrent_tokens_per_s":
                round(total / engine_dt, 1),
            prefix + "speedup": round(serial_dt / engine_dt, 2),
            prefix + "prefix_tokens_per_s": round(total / mix_dt, 1),
            prefix + "prefill_skipped_frac":
                round(reused / admitted, 3) if admitted else 0.0,
        }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        if eng is not None:
            eng.close()


def _bench_lm_adapters(n_adapters: int = 8, max_new: int = 32,
                       prompt_len: int = 16, rank: int = 8,
                       prefix: str = "lm_adapters_") -> dict:
    """Multi-tenant adapter leg: one DecodeEngine serving
    ``n_adapters`` LoRA adapters concurrently (every request wears its
    own adapter — batched-gather inside the shared fused dispatch) vs
    a base-only engine of the same shape. Reports aggregate tokens/s
    with all tenants mixed in one batch, and the MEASURED device-byte
    ratio: the adapter engine's total HBM over the base engine's
    (weights + KV pool + logits + stacks — engine.hbm_bytes() sums
    real array bytes), next to the ~N x a fleet of N separate merged
    engines would pay. The HBM ratio is the economics of the feature:
    N tenants at base + stacks instead of N bases."""
    engines = []
    import tempfile

    try:
        import numpy as np

        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.transformer import (
            TransformerConfig, TransformerLM)
        from kubeflow_tpu.serving.adapters import random_lora_flat
        from kubeflow_tpu.serving.engine import DecodeEngine
        from kubeflow_tpu.serving.export import export_adapter

        cfg = TransformerConfig(vocab_size=512, d_model=256, n_heads=4,
                                head_dim=64, n_layers=4, d_ff=1024,
                                max_seq_len=256, dtype=jnp.float32)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]
        rng = np.random.default_rng(7)
        with tempfile.TemporaryDirectory() as td:
            sources = {}
            for i in range(n_adapters):
                name = f"tenant-{i}"
                sources[name] = export_adapter(
                    os.path.join(td, name), name, cfg,
                    random_lora_flat(cfg, rank, seed=100 + i),
                    rank, 2.0 * rank)
            base = DecodeEngine(cfg, params, n_slots=n_adapters,
                                chunk_tokens=8, name="adapters-off",
                                kv_page_size=16,
                                request_timeout_s=600.0)
            engines.append(base)
            eng = DecodeEngine(cfg, params, n_slots=n_adapters,
                               chunk_tokens=8, name="adapters-on",
                               kv_page_size=16,
                               request_timeout_s=600.0,
                               adapters=sources,
                               adapter_slots=n_adapters,
                               adapter_rank=rank)
            engines.append(eng)
            from kubeflow_tpu.models.generate import pow2_bucket

            bucket = pow2_bucket(prompt_len, cfg.max_seq_len)
            base.warm([bucket])
            eng.warm([bucket])
            prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                       for _ in range(n_adapters)]
            # Warm compiles + page the adapters in OUTSIDE the timed
            # window (a production pool serves hot adapters; the cold
            # load is a one-time artifact read the loads counter
            # already measures).
            base.generate([prompts[0]], max_new_tokens=4)
            for i in range(n_adapters):
                eng.generate([prompts[i]], max_new_tokens=4,
                             adapter=f"tenant-{i}")
            t0 = time.perf_counter()
            reqs = [base.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            for r in reqs:
                r.result(600)
            base_dt = time.perf_counter() - t0
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new_tokens=max_new,
                               adapter=f"tenant-{i}")
                    for i, p in enumerate(prompts)]
            for r in reqs:
                r.result(600)
            dt = time.perf_counter() - t0
            total = n_adapters * max_new
            hbm = eng.hbm_bytes()["total"]
            hbm_base = base.hbm_bytes()["total"]
            return {
                prefix + "n": n_adapters,
                prefix + "rank": rank,
                prefix + "d_model": cfg.d_model,
                prefix + "tokens_per_s": round(total / dt, 1),
                prefix + "base_tokens_per_s":
                    round(total / base_dt, 1),
                prefix + "hbm_mb": round(hbm / 1e6, 2),
                prefix + "base_hbm_mb": round(hbm_base / 1e6, 2),
                # ONE engine serving N adapters vs ONE base engine:
                # the acceptance bar is <= 1.5x.
                prefix + "hbm_ratio": round(hbm / hbm_base, 3),
                # What N separate merged deployments would pay,
                # relative to the same denominator: the ESTIMATE is N
                # by construction (each merged engine is one base
                # engine's buffers) — reported honestly as such, not
                # dressed up as a measurement.
                prefix + "sep_engines_hbm_ratio": float(n_adapters),
                prefix + "loads": eng.adapter_stats()["loads"],
            }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        for e_ in engines:
            e_.close()


def _bench_lm_multimodel(n_models: int = 8, max_new: int = 32,
                         prompt_len: int = 16,
                         prefix: str = "lm_multimodel_") -> dict:
    """Multi-model weight-pool leg: ``n_models`` whole checkpoints
    time-sharing ONE DecodeEngine via refcounted HBM weight slots
    (serving/weights.py) vs one dedicated engine per model.

    Three headlines. (1) HBM economics: the pooled engine's measured
    device bytes over ONE dedicated engine's — N models at one KV
    pool + N weight slots instead of N full engines (the sep-engines
    alternative is N by construction). (2) Scale-from-zero as a
    weight swap: evict a model, then time its next request's
    swap-in against what a process respawn pays (measured here as
    dedicated-engine construct + warm + first token — an
    UNDERestimate of a real respawn, which also pays interpreter
    startup, so the comparison is conservative). (3) Correctness:
    per-model greedy outputs from the shared pool byte-identical to
    each model's dedicated engine."""
    engines = []
    import tempfile

    try:
        import numpy as np

        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.generate import pow2_bucket
        from kubeflow_tpu.models.transformer import (
            TransformerConfig, TransformerLM)
        from kubeflow_tpu.serving.engine import DecodeEngine
        from kubeflow_tpu.serving.lm_server import export_lm, load_lm

        cfg = TransformerConfig(vocab_size=512, d_model=256, n_heads=4,
                                head_dim=64, n_layers=4, d_ff=1024,
                                max_seq_len=256, dtype=jnp.float32)
        rng = np.random.default_rng(11)
        with tempfile.TemporaryDirectory() as td:
            sources = {}
            for i in range(n_models):
                params_i = TransformerLM(cfg).init(
                    jax.random.PRNGKey(100 + i),
                    jnp.zeros((1, 8), jnp.int32))["params"]
                sources[f"m{i}"] = export_lm(
                    os.path.join(td, f"m{i}"), cfg, params_i)
                del params_i
            # The resident default loads from its own export so the
            # pooled tree is bit-for-bit what a dedicated engine
            # loads.
            cfg0, params0 = load_lm(sources["m0"])
            # KV pool sized so the marginal cost of 7 extra
            # checkpoints lands against a realistic
            # activation/KV-dominated engine, as in production.
            kv_kw = dict(chunk_tokens=8, kv_page_size=16,
                         kv_pages=2048, request_timeout_s=600.0)
            pool = DecodeEngine(cfg0, params0, n_slots=n_models,
                                name="multimodel", models=sources,
                                model_default="m0",
                                weight_slots=n_models, **kv_kw)
            engines.append(pool)
            bucket = pow2_bucket(prompt_len, cfg.max_seq_len)
            pool.warm([bucket])
            prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                       for _ in range(n_models)]
            # Page every model in OUTSIDE the timed window (the swap
            # histogram measures the cold loads; the timed window
            # measures hot multi-model decode).
            for i in range(n_models):
                pool.generate([prompts[i]], max_new_tokens=4,
                              model=f"m{i}")
            t0 = time.perf_counter()
            reqs = [pool.submit(p, max_new_tokens=max_new,
                                model=f"m{i}")
                    for i, p in enumerate(prompts)]
            pooled_out = [r.result(600) for r in reqs]
            dt = time.perf_counter() - t0
            hbm = pool.hbm_bytes()["total"]
            # Swap-in cold start: drop one idle model's slot, then
            # time a 1-token request against the same request warm —
            # the delta is the artifact-load + device-put swap the
            # activator's cold path pays instead of a respawn.
            assert pool.evict_model(f"m{n_models - 1}")
            t0 = time.perf_counter()
            pool.generate([prompts[-1]], max_new_tokens=1,
                          model=f"m{n_models - 1}")
            cold_1tok = time.perf_counter() - t0
            t0 = time.perf_counter()
            pool.generate([prompts[-1]], max_new_tokens=1,
                          model=f"m{n_models - 1}")
            warm_1tok = time.perf_counter() - t0
            swap_s = max(cold_1tok - warm_1tok, 0.0)
            # Dedicated comparators, one at a time (peak memory is 2
            # engines): byte-identity per model, the HBM denominator
            # from m0 (same KV config as the pool), and the respawn
            # cold start from the last model.
            identical = True
            hbm_base = 0.0
            respawn_s = 0.0
            for i in range(n_models):
                cfg_i, params_i = load_lm(sources[f"m{i}"])
                t0 = time.perf_counter()
                ded = DecodeEngine(cfg_i, params_i, n_slots=1,
                                   name=f"ded-m{i}",
                                   **(kv_kw if i == 0 else
                                      dict(kv_kw, kv_pages=256)))
                ded.warm([bucket])
                out = ded.generate([prompts[i]],
                                   max_new_tokens=max_new)[0]
                if i == n_models - 1:
                    # Construct + compile-warm + first tokens: what
                    # scale-from-zero pays when no warm replica
                    # exists to swap into.
                    respawn_s = time.perf_counter() - t0
                if i == 0:
                    hbm_base = ded.hbm_bytes()["total"]
                identical = identical and \
                    list(out) == list(pooled_out[i])
                ded.close()
            total = n_models * max_new
            return {
                prefix + "n": n_models,
                prefix + "tokens_per_s": round(total / dt, 1),
                prefix + "hbm_mb": round(hbm / 1e6, 2),
                prefix + "base_hbm_mb": round(hbm_base / 1e6, 2),
                # ONE engine hosting N checkpoints vs ONE dedicated
                # engine: the acceptance bar is <= ~1.5x.
                prefix + "hbm_ratio": round(hbm / hbm_base, 3),
                # N separate deployments pay ~N of the denominator by
                # construction — reported as the estimate it is.
                prefix + "sep_engines_hbm_ratio": float(n_models),
                prefix + "byte_identical": bool(identical),
                prefix + "swap_cold_s": round(swap_s, 3),
                prefix + "respawn_cold_s": round(respawn_s, 3),
                prefix + "loads": pool.weight_stats()["loads"],
                prefix + "evictions":
                    pool.weight_stats()["evictions"],
            }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        for e_ in engines:
            e_.close()


def _bench_lm_mixed_trace(prefix: str = "lm_mixed_") -> dict:
    """Mixed long-prompt/short-chat trace, two legs.

    Inter-token leg (one engine, the lm_spec weight-bound d=512/L4
    config): two short-chat clients decode continuously while two
    320-token prompts admit mid-stream; inter-token arrival gaps of
    the short clients are sampled host-side and the p99 compared with
    chunked prefill OFF (monolithic: each long admission stalls decode
    for its whole prefill) vs ON (32-token chunks: the stall is
    bounded per iteration) — the head-of-line-blocking story in one
    number.

    Fleet leg (2 in-process LM servers behind the Router): 16 requests
    over 4 distinct system prompts (48 shared + 16 unique tokens) in
    shuffled order, with client-computed X-Kfx-Prefix headers; the
    FLEET prefill-skipped fraction = sum(reused)/sum(admitted) across
    both replicas' engines, measured with prefix affinity vs blind
    round-robin (affinity_capacity=0) — affinity routes every repeat
    to the replica already holding the pages, so the per-replica
    cache composes into a fleet-level one."""
    try:
        out = {}
        out.update(_mixed_itl_leg(prefix))
        out.update(_mixed_fleet_leg(prefix))
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}


def _mixed_itl_leg(prefix: str, short_new: int = 96,
                   long_len: int = 320, chunk: int = 32) -> dict:
    import threading

    import numpy as np

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import pow2_bucket
    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg = TransformerConfig(vocab_size=512, d_model=512, n_heads=4,
                            head_dim=128, n_layers=4, d_ff=2048,
                            max_seq_len=512, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(7)
    shorts = [list(rng.integers(0, cfg.vocab_size, 16))
              for _ in range(2)]
    longs = [list(rng.integers(0, cfg.vocab_size, long_len))
             for _ in range(2)]

    def run_leg(chunk_tokens: int) -> float:
        # chunk_tokens=1 (one decode dispatch per token): the sampled
        # gaps ARE inter-token latencies, not K-token-batch arrivals.
        eng = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=1,
                           name="mix", kv_page_size=16,
                           request_timeout_s=600.0,
                           prefill_chunk_tokens=chunk_tokens)
        try:
            eng.warm([pow2_bucket(16, 512),
                      pow2_bucket(long_len, 512)])
            eng.generate([shorts[0]], max_new_tokens=4)  # warm path
            reqs = [eng.submit(p, max_new_tokens=short_new)
                    for p in shorts]

            def feed_longs():
                for p in longs:
                    time.sleep(0.4)
                    eng.submit(p, max_new_tokens=8)

            feeder = threading.Thread(target=feed_longs, daemon=True)
            feeder.start()
            gaps = []
            last_len = [0] * len(reqs)
            last_t = [None] * len(reqs)
            deadline = time.perf_counter() + 300
            while (not all(r.done() for r in reqs)
                   and time.perf_counter() < deadline):
                now = time.perf_counter()
                for i, r in enumerate(reqs):
                    n = len(r.tokens)
                    if n > last_len[i]:
                        if last_t[i] is not None:
                            gaps.append(now - last_t[i])
                        last_t[i] = now
                        last_len[i] = n
                time.sleep(0.0005)
            feeder.join(30)
            for r in reqs:
                r.result(60)
            return float(np.percentile(gaps, 99)) if gaps else 0.0
        finally:
            eng.close()

    p99_off = run_leg(0)
    p99_on = run_leg(chunk)
    return {
        prefix + "short_clients": 2,
        prefix + "long_prompt_tokens": long_len,
        prefix + "chunk_tokens": chunk,
        prefix + "itl_p99_off_ms": round(p99_off * 1000, 1),
        prefix + "itl_p99_on_ms": round(p99_on * 1000, 1),
        prefix + "itl_improvement":
            round(p99_off / p99_on, 2) if p99_on > 0 else 0.0,
    }


def _bench_lm_qos(prefix: str = "lm_qos_") -> dict:
    """Mixed-class request plane (serving/engine.py QoS classes +
    deadline-aware admission), one engine, three phases.

    Quiet: two interactive clients decode alone; inter-token gaps
    stamped at the engine's on_token streaming sink -> the no-flood
    p99 ITL. Flood: the same two interactive clients while feeders
    keep a batch-class backlog saturating the remaining slots —
    FairQueue admits interactive first and batch slots are the
    preemption victims, so the acceptance bar is flood p99 <= 1.5x
    quiet (phase p99s are medians over three interleaved reps). Deadline: with the
    slots pinned by batch work and the queue-wait EWMA warm, a burst
    of 5ms-deadline requests must shed BEFORE prefill
    (DeadlineInfeasible at submit or while queued) — shed > 0 and
    ZERO post-prefill deadline timeouts is the contract."""
    import threading

    import numpy as np

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import pow2_bucket
    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from kubeflow_tpu.serving.engine import (DeadlineInfeasible,
                                             DecodeEngine)

    cfg = TransformerConfig(vocab_size=512, d_model=512, n_heads=4,
                            head_dim=128, n_layers=4, d_ff=2048,
                            max_seq_len=512, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    inter = [list(rng.integers(0, cfg.vocab_size, 16))
             for _ in range(2)]
    # The flood is LONG-RUNNING batch requests (that is what the batch
    # class is for): on a serial device every admission prefill runs
    # at decode-step cost no matter how it is chunked, so the way to
    # protect interactive p99 is to bound the RATE of head-of-line
    # events below 1% of gap samples — long batch decodes mean ~2
    # admissions per measurement window, and p99 (an order statistic
    # over ~510 gaps) sits on ordinary decode cadence, not on the
    # admission stalls. UNIQUE prompt per submission: repeated prompts
    # would hit the prefix cache and turn every admission into a COW
    # boundary-page clone whose compiled-copy cost lands in the
    # interactive gap; a real batch flood is distinct requests.
    batch_prompts = [list(rng.integers(0, cfg.vocab_size, 32))
                     for _ in range(64)]
    eng = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=1,
                       name="qos", kv_page_size=16,
                       request_timeout_s=600.0)
    try:
        eng.warm([pow2_bucket(16, 512), pow2_bucket(32, 512)])
        eng.generate([inter[0]], max_new_tokens=4)  # warm path

        def itl_p99(flood: bool) -> float:
            stop = threading.Event()
            served = [0]

            handles = []

            def feeder(fid: int):
                # Staggered decode lengths per feeder: three feeders
                # finishing (and re-admitting) in the same iteration
                # would stack admission work into one gap sample.
                while not stop.is_set():
                    try:
                        r = eng.submit(
                            batch_prompts[served[0] % len(batch_prompts)],
                            max_new_tokens=256 + 16 * fid, qos="batch")
                        handles.append(r)
                        served[0] += 1
                        while not r.done() and not stop.is_set():
                            time.sleep(0.01)
                    except Exception:
                        time.sleep(0.05)

            feeders = []
            if flood:
                feeders = [threading.Thread(target=feeder, args=(fid,),
                                            daemon=True)
                           for fid in range(3)]
                for f in feeders:
                    f.start()
                time.sleep(0.5)  # backlog established
            # ITL is stamped at the engine's on_token streaming sink —
            # the same loop-thread callback the SSE path serializes
            # from, so each gap is the wire cadence an end client
            # would see. (A host-side polling sampler measured its OWN
            # GIL-scheduling jitter under the flood's extra threads,
            # not the engine's.) 2 x 256 tokens -> ~510 gap samples:
            # p99 sits at the ~6th-largest gap, not the max.
            stamps = [[] for _ in inter]

            def sink(i):
                def cb(tok):
                    if tok is not None:
                        stamps[i].append(time.perf_counter())
                return cb

            reqs = [eng.submit(p, max_new_tokens=256,
                               qos="interactive", on_token=sink(i))
                    for i, p in enumerate(inter)]
            for r in reqs:
                r.result(240)
            stop.set()
            for f in feeders:
                f.join(30)
            # Drain: in-flight batch decodes outlive the feeders (up
            # to ~256 tokens) and would pollute the NEXT quiet phase.
            for r in handles:
                try:
                    r.result(240)
                except Exception:
                    pass
            gaps = [b - a for ts in stamps
                    for a, b in zip(ts, ts[1:])]
            p99 = float(np.percentile(gaps, 99)) if gaps else 0.0
            return p99, served[0]

        # Interleaved quiet/flood phase pairs, MEDIAN p99 per phase:
        # both sides of the ratio carry +/-30% single-rep jitter on a
        # shared-CPU host (one scheduler hiccup lands in the p99 of a
        # ~510-gap sample), and the bar is a RATIO — medians over
        # three interleaved reps keep one bad scheduling window on
        # either side from deciding it.
        quiets, floods = [], []
        flood_served = 0
        for _rep in range(3):
            q, _ = itl_p99(flood=False)
            f, s = itl_p99(flood=True)
            quiets.append(q)
            floods.append(f)
            flood_served += s
        p99_quiet = float(np.median(quiets))
        p99_flood = float(np.median(floods))

        # Deadline phase: pin every slot with long batch decodes so
        # the queue is non-empty, then burst infeasible 5ms-deadline
        # requests at the full queue.
        pinned = [eng.submit(p, max_new_tokens=96, qos="batch")
                  for p in batch_prompts[:4]]
        shed = timeouts = 0
        probes = []
        for _ in range(8):
            try:
                probes.append(eng.submit(inter[0], max_new_tokens=8,
                                         deadline_s=0.005))
            except DeadlineInfeasible:
                shed += 1
        for r in probes:
            try:
                r.result(30)
            except DeadlineInfeasible:
                shed += 1  # expired while queued — still pre-prefill
            except TimeoutError:
                timeouts += 1  # burned a prefill, then died: the bug
        for r in pinned:
            r.result(120)
        return {
            prefix + "interactive_itl_p99_ms":
                round(p99_quiet * 1000, 1),
            prefix + "interactive_itl_p99_flood_ms":
                round(p99_flood * 1000, 1),
            # Acceptance bar: <= 1.5 (interactive stays flat under a
            # batch flood).
            prefix + "flood_ratio":
                round(p99_flood / p99_quiet, 2) if p99_quiet > 0
                else 0.0,
            # Batch requests ADMITTED during the flood (class tiering
            # degrades batch, never starves it) + the pinned deadline
            # phase's four.
            prefix + "batch_served": flood_served + len(pinned),
            prefix + "deadline_shed": shed,
            prefix + "deadline_timeouts": timeouts,
        }
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        eng.close()


def _bench_lm_disagg(clients: int = 6, prompt_len: int = 64,
                     max_new: int = 24,
                     prefix: str = "lm_disagg_") -> dict:
    """KV transfer plane (serving/kvtransfer.py), two legs.

    Disaggregated vs interleaved: ``clients`` single-prompt requests
    through an asymmetric prefill-engine -> decode-engine pair (the
    prefill tier ships each finished prompt's pages over the page-
    stream codec and the decode tier resumes from them) vs the same
    requests through one mixed engine — aggregate tokens/s plus p99
    inter-token latency stamped at the on_token sink on the DECODE
    side of each topology.

    Migration vs recompute at 3 context lengths: an in-flight decode
    is migrated donor->receiver (export + verified transfer + import)
    and the wall time is compared against the receiver recomputing
    the same-length context from the prompt (the seeded re-dispatch
    fallback) — the crossover is the economics of moving KV instead
    of re-prefilling it. Acceptance: migration beats recompute at the
    longest benched length."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import pow2_bucket
    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from kubeflow_tpu.serving.engine import DecodeEngine, RequestMigrated

    cfg = TransformerConfig(vocab_size=512, d_model=512, n_heads=4,
                            head_dim=128, n_layers=4, d_ff=2048,
                            max_seq_len=512, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(23)
    engines = []

    def make(role, send=None, slots=clients, chunk=8):
        e = DecodeEngine(cfg, params, n_slots=slots, chunk_tokens=chunk,
                         request_timeout_s=600.0, kv_page_size=16,
                         name=f"disagg-{role}-{len(engines)}",
                         role=role, kv_peer_send=send)
        engines.append(e)
        return e

    def sink(ts):
        def cb(tok):
            if tok is not None:
                ts.append(time.perf_counter())
        return cb

    def p99_ms(stamp_lists):
        gaps = [b - a for ts in stamp_lists for a, b in zip(ts, ts[1:])]
        return round(float(np.percentile(gaps, 99)) * 1000, 1) \
            if gaps else 0.0

    try:
        out = {}
        prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                   for _ in range(clients)]
        bucket = pow2_bucket(prompt_len, cfg.max_seq_len)

        # -- leg 1: asymmetric prefill->decode pair vs one mixed engine
        decode_eng = make("decode")
        adopted = []

        def send(payload):
            ts = []
            req = decode_eng.kv_import(payload, on_token=sink(ts))
            adopted.append((req, ts))
            return "decode-local"

        prefill_eng = make("prefill", send=send)
        for e in (prefill_eng, decode_eng):
            e.warm([bucket])
            e._gather_fn()  # transfer compiles out of the timed legs
            e._scatter_fn()
        prefill_eng.generate([list(rng.integers(0, cfg.vocab_size,
                                                prompt_len))],
                             max_new_tokens=2)  # warm decode path
        t0 = time.perf_counter()
        reqs = prefill_eng.submit_batch(prompts, max_new_tokens=max_new)
        moved = 0
        for r in reqs:
            try:
                r.result(600)
            except RequestMigrated:
                moved += 1
        for r, _ in adopted:
            r.result(600)
        asym_dt = time.perf_counter() - t0
        asym_tokens = sum(len(r.tokens) for r, _ in adopted) \
            + sum(len(r.tokens) for r in reqs if r.error is None)
        out[prefix + "handoffs"] = moved
        out[prefix + "tokens_per_s"] = round(asym_tokens / asym_dt, 1)
        out[prefix + "itl_p99_ms"] = p99_ms([ts for _, ts in adopted])

        mixed_eng = make("mixed")
        mixed_eng.warm([bucket])
        mixed_eng.generate([list(rng.integers(0, cfg.vocab_size,
                                              prompt_len))],
                           max_new_tokens=2)  # warm
        stamps = [[] for _ in prompts]
        t0 = time.perf_counter()
        mreqs = [mixed_eng.submit(p, max_new_tokens=max_new,
                                  on_token=sink(ts))
                 for p, ts in zip(prompts, stamps)]
        for r in mreqs:
            r.result(600)
        mixed_dt = time.perf_counter() - t0
        out[prefix + "interleaved_tokens_per_s"] = \
            round(sum(len(r.tokens) for r in mreqs) / mixed_dt, 1)
        out[prefix + "interleaved_itl_p99_ms"] = p99_ms(stamps)

        # -- leg 2: migration vs recompute at 3 context lengths.
        # Short chunks: migrate_out quiesces at iteration boundaries,
        # so the in-flight chunk dispatch is a fixed floor under the
        # measured cost — chunk=4 keeps that floor about the transfer's
        # own size instead of 2x it.
        recv = make("mixed", slots=2, chunk=4)
        moved_to = []
        donor = make("mixed", slots=2, chunk=4, send=lambda p: (
            moved_to.append(recv.kv_import(p)), "recv-local")[1])
        for e in (donor, recv):
            e._gather_fn()
            e._scatter_fn()
        speedup = 0.0
        for ctx in (64, 128, 224):
            b = pow2_bucket(ctx, cfg.max_seq_len)
            donor.warm([b])
            recv.warm([b])
            # Recompute cost: the receiver prefills a fresh ctx-token
            # prompt from scratch (time to first token — what the
            # seeded re-dispatch fallback pays before streaming).
            p1 = list(rng.integers(0, cfg.vocab_size, ctx))
            t0 = time.perf_counter()
            recv.submit(p1, max_new_tokens=1).result(600)
            recompute_ms = (time.perf_counter() - t0) * 1000
            # Migration cost: a throttled in-flight decode of the same
            # context length moves donor->receiver; migrate_out blocks
            # through export + verified transfer + import + detach.
            # max_new must leave the donor several chunk boundaries of
            # runway past the export snapshot — the fail-safe ordering
            # lets it keep decoding during the transfer, and a request
            # that retires before the peer ACK counts as moved=0.
            p2 = list(rng.integers(0, cfg.vocab_size, ctx))
            r = donor.submit(p2, max_new_tokens=64,
                             on_token=lambda t: time.sleep(0.005))
            dl = time.monotonic() + 60
            while len(r.tokens) < 2 and not r.done() \
                    and time.monotonic() < dl:
                time.sleep(0.005)
            t0 = time.perf_counter()
            stats = donor.migrate_out(reason="rebalance")
            migrate_ms = (time.perf_counter() - t0) * 1000
            for m in moved_to:
                m.result(600)
            moved_to.clear()
            try:
                r.result(600)
            except RequestMigrated:
                pass
            if not stats["moved"]:
                continue  # donor finished first: no number this rung
            out[prefix + f"migrate_ms_c{ctx}"] = round(migrate_ms, 1)
            out[prefix + f"recompute_ms_c{ctx}"] = round(recompute_ms, 1)
            speedup = recompute_ms / migrate_ms if migrate_ms else 0.0
        # Speedup at the LONGEST length that actually migrated —
        # the acceptance bar is > 1 there (moving pages beats
        # re-prefilling them where context is big).
        out[prefix + "migrate_speedup"] = round(speedup, 2)
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        for e in engines:
            e.close()


def _mixed_fleet_leg(prefix: str, n_prompts: int = 4,
                     repeats: int = 4) -> dict:
    import json as _json
    import shutil
    import tempfile
    import urllib.request

    import numpy as np

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from kubeflow_tpu.obs.metrics import MetricsRegistry
    from kubeflow_tpu.serving.lm_server import LMPredictor, export_lm
    from kubeflow_tpu.serving.prefix import PREFIX_HEADER, affinity_key
    from kubeflow_tpu.serving.router import Router

    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=2,
                            head_dim=32, n_layers=2, d_ff=128,
                            max_seq_len=128, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tmp = tempfile.mkdtemp(prefix="kfx-bench-mix-")
    export_lm(tmp, cfg, params)
    rng = np.random.default_rng(11)
    systems = [[int(t) for t in rng.integers(0, cfg.vocab_size, 48)]
               for _ in range(n_prompts)]
    order = [(s, r) for r in range(repeats)
             for s in range(n_prompts)]
    rng.shuffle(order)
    saved = {k: os.environ.get(k)
             for k in ("KFX_LM_ENGINE", "KFX_LM_SPEC",
                       "KFX_LM_KV_PAGE_SIZE", "KFX_LM_PREFILL_CHUNK")}
    os.environ.update({"KFX_LM_ENGINE": "1", "KFX_LM_SPEC": "0",
                       "KFX_LM_KV_PAGE_SIZE": "16",
                       "KFX_LM_PREFILL_CHUNK": "32"})

    def run_leg(affinity: bool):
        from kubeflow_tpu.serving.server import ModelServer

        servers, router = [], None
        try:
            for _ in range(2):
                p = LMPredictor(tmp, name="mix", warm_buckets=[8])
                p.load()
                srv = ModelServer(port=0)
                srv.register(p)
                srv.start()
                servers.append(srv)
            reg = MetricsRegistry()
            router = Router(metrics=reg, name="mix", namespace="bench",
                            affinity_capacity=512 if affinity else 0
                            ).start()
            router.default.set_endpoints(
                [f"127.0.0.1:{s.port}" for s in servers])
            url = (f"http://127.0.0.1:{router.port}"
                   "/v1/models/mix:generate")
            for s_idx, r_idx in order:
                prompt = systems[s_idx] + [
                    int(t) for t in rng.integers(0, cfg.vocab_size, 16)]
                hdrs = {"Content-Type": "application/json"}
                if affinity:
                    hdrs[PREFIX_HEADER] = affinity_key(prompt)
                req = urllib.request.Request(
                    url, data=_json.dumps(
                        {"prompt_tokens": [prompt],
                         "max_new_tokens": 4}).encode(), headers=hdrs)
                with urllib.request.urlopen(req, timeout=60) as resp:
                    _json.load(resp)
            reused = admitted = 0.0
            for srv in servers:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics"
                        "?format=json", timeout=10) as resp:
                    row = _json.load(resp)["engine"]["mix"]
                reused += row.get("prefix_tokens_reused", 0.0)
                admitted += row.get("prompt_tokens_admitted", 0.0)
            hits = reg.counter(
                "kfx_router_prefix_affinity_hits_total").value(
                    namespace="bench", isvc="mix")
            return (reused / admitted if admitted else 0.0), hits
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()

    try:
        frac_aff, hits = run_leg(affinity=True)
        frac_blind, _ = run_leg(affinity=False)
        return {
            prefix + "fleet_replicas": 2,
            prefix + "fleet_requests": len(order),
            prefix + "prefill_skipped_frac": round(frac_aff, 3),
            prefix + "prefill_skipped_frac_blind":
                round(frac_blind, 3),
            prefix + "affinity_hits": int(hits),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tmp, ignore_errors=True)


def _spec_benchable_params(params, alpha: float = 0.35):
    """Random-init params reshaped into the structure speculative
    decoding targets: the lm_head is tied to the embedding (GPT-2/
    LLaMA-style weight tying — a peaked, self-consistent next-token
    distribution instead of argmax gaps below float noise) and every
    layer's residual projections (attn out / mlp wo) are scaled by
    ``alpha`` so deep layers REFINE the stream rather than overwrite
    it — the layerwise structure trained checkpoints have and raw
    random init adversarially lacks (measured: truncated-draft argmax
    agreement <= 0.29 on raw init vs ~0.6-0.95 here depending on
    alpha). The accept rate the engine achieves on these params is
    MEASURED and reported, never assumed; the bench's claim is about
    engine mechanics (tokens/s at the reported accept rate), not about
    any particular checkpoint's draft agreement."""
    import jax

    def scale(path, x):
        names = tuple(getattr(p, "key", str(p)) for p in path)
        if "layers" in names and names[-2:] in (("out", "kernel"),
                                                ("wo", "kernel")):
            return x * alpha
        return x

    params = jax.tree_util.tree_map_with_path(scale, params)
    params = dict(params)
    params["lm_head"] = {"kernel": params["embed"]["embedding"].T}
    return params


def _bench_lm_spec(max_new: int = 64, prompt_len: int = 16,
                   draft_layers: int = 1, propose_tokens: int = 4,
                   prefix: str = "lm_spec_") -> dict:
    """Speculative-decode leg: one weight-streaming-bound config
    (d=512, head_dim=128, 4 layers, f32 — per-step cost dominated by
    reading ~17M params), greedy decode through the DecodeEngine with
    the draft OFF vs ON at batch 1 and batch 4. Greedy, so the two
    engines' outputs are byte-identical and the speedup is pure
    mechanics: k+1 candidate tokens per target weight-stream times the
    measured accept rate, minus the draft's own streams."""
    engines = []
    try:
        import numpy as np

        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.transformer import (
            TransformerConfig, TransformerLM)
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg = TransformerConfig(vocab_size=512, d_model=512, n_heads=4,
                                head_dim=128, n_layers=4, d_ff=2048,
                                max_seq_len=256, dtype=jnp.float32)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]
        params = _spec_benchable_params(params)
        rng = np.random.default_rng(3)
        base = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=8,
                            name="spec-off", kv_page_size=16,
                            request_timeout_s=600.0)
        engines.append(base)
        spec = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=8,
                            name="spec-on", kv_page_size=16,
                            request_timeout_s=600.0,
                            draft_layers=draft_layers,
                            propose_tokens=propose_tokens)
        engines.append(spec)
        from kubeflow_tpu.models.generate import pow2_bucket

        bucket = pow2_bucket(prompt_len, cfg.max_seq_len)
        base.warm([bucket])
        spec.warm([bucket])
        out = {
            prefix + "d_model": cfg.d_model,
            prefix + "n_layers": cfg.n_layers,
            prefix + "draft_layers": draft_layers,
            prefix + "propose_tokens": propose_tokens,
            prefix + "new_tokens": max_new,
        }
        for batch, tag in ((1, ""), (4, "b4_")):
            prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                       for _ in range(batch)]
            base.generate([prompts[0]], max_new_tokens=8)   # warm
            spec.generate([prompts[0]], max_new_tokens=8)   # warm
            t0 = time.perf_counter()
            ref = base.generate(prompts, max_new_tokens=max_new)
            base_dt = time.perf_counter() - t0
            st0 = spec.spec_stats()
            t0 = time.perf_counter()
            got = spec.generate(prompts, max_new_tokens=max_new)
            spec_dt = time.perf_counter() - t0
            st1 = spec.spec_stats()
            if got != ref:  # greedy parity is the leg's precondition
                return {prefix + "error": "speculative output diverged "
                        "from the non-speculative engine (greedy)"}
            proposed = st1["proposed"] - st0["proposed"]
            accepted = st1["accepted"] - st0["accepted"]
            total = batch * max_new
            out.update({
                prefix + tag + "base_tokens_per_s":
                    round(total / base_dt, 1),
                prefix + tag + "tokens_per_s":
                    round(total / spec_dt, 1),
                prefix + tag + "speedup": round(base_dt / spec_dt, 2),
            })
            out[prefix + tag + "accept_rate"] = \
                round(accepted / proposed, 3) if proposed else 0.0
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        for eng in engines:
            eng.close()


def _bench_lm_quant(max_new: int = 64, prompt_len: int = 16,
                    batch: int = 4, prefix: str = "lm_quant_") -> dict:
    """Quantized-serving leg on the lm_spec weight-bound config (d=512,
    head_dim=128, 4 layers, f32 — per-step cost dominated by reading
    ~17M params): greedy decode through the DecodeEngine for f32, int8
    weights (per-channel, dequant-fused matmul), int8 paged KV and
    both; plus a speculative leg with ONLY the draft quantized. Every
    variant's generations are scored by the F32 MODEL (teacher-forced
    NLL over the completion region -> perplexity), so the reported
    delta is the quality the quantized engine actually costs — never
    assumed. CPU-host caveat (docs/serving.md): XLA:CPU has no int8
    GEMM kernels and materializes the dequant convert, so int8 weights
    measure AT OR BELOW 1x wall-clock here; the HBM story
    (weight_bytes_ratio, kv8_admit_ratio) is exact on any backend and
    is what the TPU wall-clock win is made of."""
    engines = []
    try:
        import dataclasses

        import numpy as np

        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.generate import pow2_bucket
        from kubeflow_tpu.models.transformer import (
            TransformerConfig, TransformerLM, quantize_params_int8)
        from kubeflow_tpu.serving.engine import DecodeEngine

        cfg = TransformerConfig(vocab_size=512, d_model=512, n_heads=4,
                                head_dim=128, n_layers=4, d_ff=2048,
                                max_seq_len=256, dtype=jnp.float32)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]
        params = _spec_benchable_params(params)
        qparams = quantize_params_int8(params)
        qcfg = dataclasses.replace(cfg, quant="int8")
        rng = np.random.default_rng(7)
        prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
                   for _ in range(batch)]
        bucket = pow2_bucket(prompt_len, cfg.max_seq_len)
        oracle = TransformerLM(cfg)

        def ppl(outs) -> float:
            """Perplexity of prompt+completion sequences under the f32
            model, next-token NLL over the COMPLETION region only (the
            prompt region is identical across variants and would only
            dilute the delta)."""
            seqs = jnp.asarray([p + o for p, o in zip(prompts, outs)],
                               jnp.int32)
            logits = oracle.apply({"params": params}, seqs)
            lp = jax.nn.log_softmax(
                logits[:, prompt_len - 1:-1].astype(jnp.float32), -1)
            tok = seqs[:, prompt_len:, None]
            nll = -jnp.mean(jnp.take_along_axis(lp, tok, axis=-1))
            return float(jnp.exp(nll))

        def run(name, c, p, **kw):
            eng = DecodeEngine(c, p, n_slots=batch, chunk_tokens=8,
                               name=name, kv_page_size=16,
                               request_timeout_s=600.0, **kw)
            engines.append(eng)
            eng.warm([bucket])
            eng.generate([prompts[0]], max_new_tokens=8)  # warm
            t0 = time.perf_counter()
            outs = eng.generate(prompts, max_new_tokens=max_new)
            dt = time.perf_counter() - t0
            return eng, outs, batch * max_new / dt

        base, outs_f32, tps_f32 = run("q-f32", cfg, params)
        _, outs_w8, tps_w8 = run("q-w8", qcfg, qparams)
        kv8, outs_kv8, tps_kv8 = run("q-kv8", cfg, params,
                                     kv_quant="int8")
        _, outs_both, tps_both = run("q-w8kv8", qcfg, qparams,
                                     kv_quant="int8")
        ppl_f32 = ppl(outs_f32)
        # Weight bytes: int8 kernels + f32 scales vs the f32 tree —
        # the exact per-token weight-stream reduction on any backend.
        fbytes = sum(x.size * x.dtype.itemsize for x in
                     jax.tree_util.tree_leaves(params))
        qbytes = sum(np.asarray(x).size * np.asarray(x).dtype.itemsize
                     for x in jax.tree_util.tree_leaves(qparams))
        out = {
            prefix + "d_model": cfg.d_model,
            prefix + "new_tokens": max_new,
            prefix + "batch": batch,
            prefix + "ppl_f32": round(ppl_f32, 3),
            prefix + "base_tokens_per_s": round(tps_f32, 1),
            prefix + "w8_tokens_per_s": round(tps_w8, 1),
            prefix + "w8_speedup": round(tps_w8 / tps_f32, 2),
            prefix + "w8_ppl_delta": round(ppl(outs_w8) - ppl_f32, 3),
            prefix + "kv8_tokens_per_s": round(tps_kv8, 1),
            prefix + "kv8_ppl_delta": round(ppl(outs_kv8) - ppl_f32, 3),
            prefix + "kv8_admit_ratio": round(
                base.kv_bytes_per_token / kv8.kv_bytes_per_token, 2),
            prefix + "w8kv8_tokens_per_s": round(tps_both, 1),
            prefix + "w8kv8_ppl_delta": round(
                ppl(outs_both) - ppl_f32, 3),
            prefix + "weight_bytes_ratio": round(fbytes / qbytes, 2),
        }
        # Quantized-DRAFT speculative leg: target f32, draft int8 —
        # output distribution is the target's (greedy: byte-identical
        # to the non-spec f32 engine), the draft only moves accept
        # rate and therefore speed.
        spec = DecodeEngine(cfg, params, n_slots=batch, chunk_tokens=8,
                            name="q-d8", kv_page_size=16,
                            request_timeout_s=600.0, draft_layers=1,
                            propose_tokens=4, draft_quant="int8")
        engines.append(spec)
        spec.warm([bucket])
        spec.generate([prompts[0]], max_new_tokens=8)  # warm
        st0 = spec.spec_stats()
        t0 = time.perf_counter()
        outs_d8 = spec.generate(prompts, max_new_tokens=max_new)
        spec_dt = time.perf_counter() - t0
        st1 = spec.spec_stats()
        if outs_d8 != outs_f32:
            out[prefix + "draft8_error"] = (
                "quantized-draft output diverged from the f32 engine "
                "(greedy) — the verify path must make this impossible")
            return out
        proposed = st1["proposed"] - st0["proposed"]
        accepted = st1["accepted"] - st0["accepted"]
        tps_d8 = batch * max_new / spec_dt
        out.update({
            prefix + "draft8_tokens_per_s": round(tps_d8, 1),
            prefix + "draft8_accept_rate":
                round(accepted / proposed, 3) if proposed else 0.0,
            prefix + "draft8_speedup": round(tps_d8 / tps_f32, 2),
        })
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {prefix + "error": str(e)[:200]}
    finally:
        for eng in engines:
            eng.close()


def _resnet50_point(ds, batch: int, steps: int, *, cost_analysis: bool,
                    gflops_per_image: float = 0.0):
    """One (dataset shape, batch) training-throughput point: images/s
    after a warmup dispatch, plus measured-program MFU. With
    ``cost_analysis`` the step's own HLO flop count is taken (one extra
    single-step compile); otherwise ``gflops_per_image`` from a
    same-shape point is reused (flops/image depend on the input shape,
    not the batch)."""
    from kubeflow_tpu.models import get_model
    from kubeflow_tpu.training import TrainLoop

    loop = TrainLoop(get_model("resnet50", num_classes=ds.num_classes))
    state = loop.init_state(ds.shape)
    batch_fn = ds.device_batch_fn()
    state, _, _ = loop.train_steps_device(state, batch_fn, batch, 0, steps)
    t0 = time.perf_counter()
    state, loss, acc = loop.train_steps_device(state, batch_fn, batch,
                                               steps, steps)
    dt = time.perf_counter() - t0
    point = {
        "images_per_s": round(steps * batch / dt, 0),
        "step_time_ms": round(dt / steps * 1000, 2),
        "train_acc": round(float(acc), 3),
        "gflops_per_image": gflops_per_image,
        "mfu": 0.0,
    }
    if cost_analysis:
        # Cost analysis CANNOT run on the measured scan program (XLA
        # counts a while-loop body once regardless of trip count —
        # measured ~60x under), so a single-step compile provides the
        # flop count; the scan program stays the measured one (driving
        # the scan through a separately AOT-compiled executable loses
        # the donated-dispatch path, measured 38→127 ms/step).
        try:
            import jax.numpy as jnp

            x = jnp.zeros((batch,) + tuple(ds.shape), jnp.float32)
            y = jnp.zeros((batch,), jnp.int32)
            ca = loop._build_train_step().lower(state, x, y).compile(
                ).cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            step_flops = float(ca.get("flops", 0.0))
            if step_flops > 0:
                point["gflops_per_image"] = round(step_flops / batch / 1e9,
                                                  2)
        except Exception:
            pass  # cost analysis is backend-dependent; the row stands
    if point["gflops_per_image"]:
        from kubeflow_tpu.utils.flops import peak_flops_per_chip

        point["mfu"] = round(
            point["gflops_per_image"] * 1e9 * point["images_per_s"]
            / peak_flops_per_chip(), 4)
    return point


def _bench_resnet50(steps: int = 60, batch: int = 256,
                    ladder=(384, 512), probe_224: bool = True) -> dict:
    """ResNet-50 single-chip training throughput on the real TPU
    (BASELINE config #3 names ResNet-50; the MPIJob example runs
    resnet18 on CPU ranks for budget — see BASELINE.md note — so the
    resnet50 number is measured here where the chip actually is).
    Device-generated batches, scan-fused dispatch: compute-bound.

    Beyond the contract point (B=256 on the 32x32 CIFAR stem), a batch
    ladder (B=384/512, same shape — r4 verdict: one point can't separate
    the chip's conv ceiling from the batch) and a 224^2 ImageNet-geometry
    probe (B=64) that isolates the small-stem effect; the best measured
    MFU across points is reported as resnet50_best_mfu."""
    try:
        from kubeflow_tpu.data import get_dataset

        ds = get_dataset("cifar10")
        base = _resnet50_point(ds, batch, steps, cost_analysis=True)
        out = {
            "resnet50_batch": batch,
            "resnet50_step_time_ms": base["step_time_ms"],
            "resnet50_images_per_s": base["images_per_s"],
            "resnet50_train_acc": base["train_acc"],
        }
        if base["gflops_per_image"]:
            out["resnet50_gflops_per_image"] = base["gflops_per_image"]
            out["resnet50_mfu"] = base["mfu"]
        best = (base["mfu"], batch, "cifar-32x32")
        for b in ladder:
            try:
                p = _resnet50_point(
                    ds, b, max(steps // 2, 10), cost_analysis=False,
                    gflops_per_image=base["gflops_per_image"])
                out[f"resnet50_b{b}_images_per_s"] = p["images_per_s"]
                if p["mfu"]:
                    out[f"resnet50_b{b}_mfu"] = p["mfu"]
                best = max(best, (p["mfu"], b, "cifar-32x32"))
            except Exception as e:
                out[f"resnet50_b{b}_error"] = str(e)[:120]
        if probe_224:
            try:
                ds224 = get_dataset("imagenet-sim")
                p = _resnet50_point(ds224, 64, 12, cost_analysis=True)
                out["resnet50_224_batch"] = 64
                out["resnet50_224_images_per_s"] = p["images_per_s"]
                out["resnet50_224_gflops_per_image"] = p["gflops_per_image"]
                if p["mfu"]:
                    out["resnet50_224_mfu"] = p["mfu"]
                best = max(best, (p["mfu"], 64, "imagenet-224x224"))
            except Exception as e:
                out["resnet50_224_error"] = str(e)[:120]
        if best[0]:
            out["resnet50_best_mfu"] = best[0]
            out["resnet50_best_config"] = f"B={best[1]} {best[2]}"
        else:
            # Cost analysis unavailable on this backend: report missing
            # data, never a fabricated 0.0 MFU (a 0.0 in BENCH_CONTRACT
            # would read as a catastrophic regression).
            out["resnet50_mfu_unavailable"] = "no HLO flop count"
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {"resnet50_error": str(e)[:200]}


_BROKEN_CANARY = """
import json, os
from http.server import BaseHTTPRequestHandler, HTTPServer

class H(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass
    def _send(self, code, obj):
        b = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(b)))
        self.end_headers()
        self.wfile.write(b)
    def do_GET(self):
        self._send(200, {"ready": True})
    def do_POST(self):
        self._send(500, {"error": "injected canary fault"})

HTTPServer(("127.0.0.1", int(os.environ["KFX_PORT"])), H).serve_forever()
"""


def _bench_serving_scale(max_replicas: int = 4, slice_chips: int = 6,
                         phase_s: float = 8.0) -> dict:
    """Serving autoscaler ramp (ISSUE 6 acceptance): one sklearn
    InferenceService under a rising concurrent-client ramp —

    * scale-from-zero cold start (ms, and the autoscale.cold_start span
      lands on the trace waterfall),
    * replicas 1 -> maxReplicas under load and back after it,
    * a mid-ramp canary revision that 500s every predict is rolled back
      automatically on the error-rate SLO (annotation + event),
    * the slice is pinned to ``slice_chips`` with a low-priority
      4-chip training job occupying it, so the serving burst must
      preempt training for chips and hand them back on scale-in.
    """
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import json as _json

    out: dict = {"serving_scale_max_replicas_config": max_replicas}
    prev_chips = os.environ.get("KFX_SLICE_CHIPS")
    os.environ["KFX_SLICE_CHIPS"] = str(slice_chips)
    home = tempfile.mkdtemp(prefix="kfx-bench-scale-")
    try:
        import numpy as np
        from sklearn.linear_model import LogisticRegression

        from kubeflow_tpu.controlplane import ControlPlane
        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.serving.sklearn_server import export_sklearn

        ds = get_dataset("mnist")
        images, labels = next(ds.batches(256))
        est = LogisticRegression(max_iter=20)
        est.fit(images.reshape(len(images), -1), labels)
        exp = os.path.join(home, "export")
        export_sklearn(exp, est, input_shape=ds.shape,
                       num_classes=ds.num_classes)
        broken = os.path.join(home, "broken_canary.py")
        with open(broken, "w") as f:
            f.write(_BROKEN_CANARY)
        manifest = f"""
apiVersion: kubeflow.org/v1
kind: JAXJob
metadata:
  name: bg-train
spec:
  runPolicy:
    schedulingPolicy:
      priority: 0
  jaxReplicaSpecs:
    Worker:
      replicas: 4
      restartPolicy: Never
      template:
        spec:
          containers:
          - name: sleep
            command: ["{sys.executable}", "-c", "import time; time.sleep(600)"]
---
apiVersion: serving.kubeflow.org/v1beta1
kind: InferenceService
metadata:
  name: ramp
spec:
  predictor:
    minReplicas: 0
    maxReplicas: {max_replicas}
    targetConcurrency: 2
    stableWindowSeconds: 4
    panicWindowSeconds: 2
    scaleToZeroIdleSeconds: 6
    sklearn:
      storageUri: file://{exp}
"""
        payload = _json.dumps({"instances": np.zeros(
            (1, 28, 28, 1), np.float32).tolist()}).encode()
        lats: list = []
        fails = [0]
        lock = threading.Lock()

        def one(url):
            t = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()
                with lock:
                    lats.append((time.perf_counter() - t) * 1000)
                return True
            except Exception:
                with lock:
                    fails[0] += 1
                return False

        with ControlPlane(home=home) as cp:
            cp.apply_text(manifest)
            url = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not url:
                url = cp.store.get("InferenceService",
                                   "ramp").status.get("url")
                time.sleep(0.1)
            if url is None:
                raise RuntimeError("InferenceService ramp never "
                                   "published status.url")
            predict = f"{url}/v1/models/ramp:predict"
            # Cold start: request until the activator has scaled 0->1.
            t0 = time.monotonic()
            deadline = t0 + 90
            while time.monotonic() < deadline:
                if one(predict):
                    break
                time.sleep(0.2)
            out["serving_scale_cold_start_ms"] = round(
                (time.monotonic() - t0) * 1000, 1)
            # The ramp: rising client counts; replicas sampled over time.
            replicas_series: list = []
            max_seen = [0]
            stop = threading.Event()

            def sampler():
                while not stop.is_set():
                    st = cp.store.get("InferenceService", "ramp").status
                    n = (st.get("replicas") or {}).get("default", 0)
                    replicas_series.append(n)
                    max_seen[0] = max(max_seen[0], n)
                    time.sleep(0.5)

            smp = threading.Thread(target=sampler, daemon=True)
            smp.start()

            def client(until):
                while time.monotonic() < until:
                    one(predict)

            for i, clients in enumerate((2, 6, 12)):
                until = time.monotonic() + phase_s
                threads = [threading.Thread(target=client, args=(until,),
                                            daemon=True)
                           for _ in range(clients)]
                for t in threads:
                    t.start()
                if i == 1:
                    # Mid-ramp canary with injected faults + rollout.
                    # Retry on Conflict: the operator's concurrent
                    # status/annotation writes bump resourceVersion
                    # between our get and update.
                    from kubeflow_tpu.core.store import Conflict
                    for _ in range(10):
                        fresh = cp.store.get("InferenceService", "ramp")
                        fresh.spec["canary"] = {
                            "minReplicas": 1,
                            "containers": [{"name": "bad", "command": [
                                sys.executable, broken]}]}
                        fresh.spec["rollout"] = {
                            "stepPercent": 30, "intervalSeconds": 2.0,
                            "sloErrorRate": 0.2, "minRequests": 8}
                        try:
                            cp.store.update(fresh)
                            break
                        except Conflict:
                            time.sleep(0.05)
                for t in threads:
                    t.join()
            # Rollback should have landed during/after the ramp.
            rolled = False
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and not rolled:
                cur = cp.store.get("InferenceService", "ramp")
                rolled = "kubeflow.org/rollout-rolled-back" in \
                    cur.metadata.annotations
                time.sleep(0.3)
            out["serving_scale_rolled_back"] = rolled
            # Preemption evidence: the low-priority gang was suspended
            # while the burst held chips.
            job = cp.store.get("JAXJob", "bg-train")
            preempted = bool(job.metadata.annotations.get(
                "kubeflow.org/preempted-by")) or \
                job.has_condition("Suspended")
            out["serving_scale_preempted_training"] = preempted
            stop.set()
            smp.join(timeout=2)
            # Scale-in: load gone -> replicas drain, chips return, the
            # training job resumes.
            deadline = time.monotonic() + 45
            resumed = drained = False
            while time.monotonic() < deadline:
                cur = cp.store.get("InferenceService", "ramp")
                job = cp.store.get("JAXJob", "bg-train")
                drained = (cur.status.get("replicas") or {}).get(
                    "default", 0) <= 1
                resumed = not job.run_policy().suspend
                if drained and (resumed or not preempted):
                    break
                time.sleep(0.5)
            out["serving_scale_scaled_in"] = drained
            out["serving_scale_training_resumed"] = resumed
        if lats:
            lats.sort()
            total = len(lats) + fails[0]
            out.update({
                "serving_scale_p50_ms": round(lats[len(lats) // 2], 2),
                "serving_scale_p99_ms": round(
                    lats[int(len(lats) * 0.99)], 2),
                "serving_scale_requests": total,
                "serving_scale_success_rate": round(len(lats) / total, 4),
                "serving_scale_max_replicas": max_seen[0],
                "serving_scale_replicas_over_time": replicas_series[::4],
            })
        return out
    except Exception as e:  # secondary metric must not sink the bench
        out["serving_scale_error"] = str(e)[:200]
        return out
    finally:
        if prev_chips is None:
            os.environ.pop("KFX_SLICE_CHIPS", None)
        else:
            os.environ["KFX_SLICE_CHIPS"] = prev_chips
        shutil.rmtree(home, ignore_errors=True)


def _bench_serving_p50(n_requests: int = 200, load_clients: int = 32,
                       load_requests: int = 960,
                       batcher_max_batch: int = 32) -> dict:
    """BASELINE config #5, measured both ways:

    * single-stream p50/p99 — one client, one instance per request (the
      latency floor a lone caller sees);
    * throughput under concurrent load — ``load_clients`` clients keep
      requests in flight against the SAME predictor behind the
      micro-batcher (maxBatchSize=32), so concurrent singles aggregate
      into one device dispatch: batched MXU dispatch amortizing the
      per-dispatch completion across the batch.
    """
    try:
        import numpy as np

        from kubeflow_tpu.data import get_dataset
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.serving.export import export_params
        from kubeflow_tpu.serving.server import JaxPredictor, ModelServer
        from kubeflow_tpu.training import TrainLoop

        import json as _json
        import tempfile

        ds = get_dataset("cifar10")
        model = get_model("resnet18", num_classes=ds.num_classes)
        loop = TrainLoop(model)
        state = loop.init_state(ds.shape)
        exp = tempfile.mkdtemp(prefix="kfx-bench-isvc-")
        export_params(exp, "resnet18", ds.shape, ds.num_classes, state)
        predictor = JaxPredictor(exp, name="resnet",
                                 max_batch_size=batcher_max_batch)
        predictor.load()
        server = ModelServer(port=0)
        server.register(predictor)
        server.start()
        x = np.zeros((1,) + ds.shape, np.float32).tolist()
        payload = _json.dumps({"instances": x}).encode()
        # Persistent HTTP/1.1 connection: measure the request, not TCP
        # handshakes.
        import http.client
        import socket

        def connect(port):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            conn.connect()
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            return conn

        path = "/v1/models/resnet:predict"

        def one(conn):
            t = time.perf_counter()
            conn.request("POST", path, body=payload,
                         headers={"Content-Type": "application/json"})
            conn.getresponse().read()
            return (time.perf_counter() - t) * 1000

        conn = connect(server.port)
        lat = [one(conn) for _ in range(n_requests)]
        conn.close()
        # Server-reported latency distribution (obs registry histogram):
        # recorded next to the client-observed number so a drift between
        # the two (queueing outside the handler) is visible in BENCH.
        server_p50 = (server._latency_summary()
                      .get("resnet", {}).get("p50"))
        server.stop()
        lat.sort()
        out = {
            "serving_p50_ms": round(lat[len(lat) // 2], 2),
            "serving_p50_ms_server": server_p50,
            "serving_p99_ms": round(lat[int(len(lat) * 0.99)], 2),
            # The headline p50 is a batch-1 predict: name the platform
            # its bucket was compiled for, so a CPU number is never
            # mistaken for an accelerator number.
            "serving_p50_placement": predictor.placement.get(1, "unknown"),
            "serving_model": "resnet18-cifar10",
            "serving_placement": {str(k): v
                                  for k, v in predictor.placement.items()},
        }
        out.update(_bench_serving_load(
            predictor, connect, one, clients=load_clients,
            total_requests=load_requests, max_batch=batcher_max_batch))
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {"serving_error": str(e)[:200]}


def _bench_serving_load(predictor, connect, one, *, clients: int,
                        total_requests: int, max_batch: int) -> dict:
    """Concurrent-load leg: same predictor (buckets already compiled and
    warm), fresh server with the micro-batcher in front."""
    import threading

    from kubeflow_tpu.serving.server import ModelServer

    try:
        server = ModelServer(port=0)
        # workers=2: a second batcher thread dispatches the next batch
        # while the first is in flight.
        server.register(predictor, batcher={"maxBatchSize": max_batch,
                                            "maxLatencyMs": 5.0,
                                            "workers": 2})
        server.start()
        per_client = total_requests // clients
        lats: list = []
        errs: list = []
        lock = threading.Lock()
        # Ready-count + event instead of a Barrier: one client failing
        # its connect must not abort the whole leg (a broken barrier
        # would lose the contract keys for the round) — the healthy
        # clients still rendezvous and measure.
        ready = threading.Semaphore(0)
        go = threading.Event()

        def client():
            try:
                conn = connect(server.port)
            except Exception as e:  # pragma: no cover - load-leg fault
                with lock:
                    errs.append(str(e)[:120])
                ready.release()
                return
            ready.release()
            go.wait()
            try:
                mine = [one(conn) for _ in range(per_client)]
                conn.close()
                with lock:
                    lats.extend(mine)
            except Exception as e:  # pragma: no cover - load-leg fault
                with lock:
                    errs.append(str(e)[:120])

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for _ in range(clients):
            ready.acquire()
        t0 = time.perf_counter()
        go.set()
        deadline = t0 + 300
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        wall = time.perf_counter() - t0
        server.stop()
        stragglers = sum(1 for t in threads if t.is_alive())
        with lock:  # freeze: a straggler must not mutate during sort
            done = list(lats)
        if not done:
            return {"serving_load_error": (errs or ["no latencies"])[0]}
        done.sort()
        lats = done
        out = {
            "serving_throughput_rps": round(len(lats) / wall, 1),
            "serving_batched_p50_ms": round(lats[len(lats) // 2], 2),
            "serving_batched_p99_ms": round(lats[int(len(lats) * 0.99)], 2),
            "serving_load_clients": clients,
            "serving_load_requests": len(lats),
            "serving_batcher_max_batch": max_batch,
            # Device the top bucket (where aggregated batches land) runs
            # on — the amortization claim is only made if this says
            # accelerator. "unknown" when the bucket is absent from the
            # placement map (non-bucketed predictor): silently claiming
            # "accelerator" would fabricate the headline evidence.
            "serving_batched_placement": predictor.placement.get(
                max_batch, "unknown"),
        }
        if stragglers:
            # The wall then includes the join timeout: flag it so the
            # rps number is read as a lower bound, not a measurement.
            out["serving_load_stragglers"] = stragglers
        if errs:
            out["serving_load_client_errors"] = errs[:3]
        return out
    except Exception as e:  # secondary metric must not sink the bench
        return {"serving_load_error": str(e)[:200]}


if __name__ == "__main__":
    sys.exit(main())
