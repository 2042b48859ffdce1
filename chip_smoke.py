#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that kfx still starts on the chip.

    python chip_smoke.py            one TPU chip: train -> export -> serve
    python chip_smoke.py --chips 4  four chips: sharded vs replicated training

One chip (what the driver runs). A Pipeline of the shape of
``examples/lm-train-serve-pipeline.yaml`` is applied to a ControlPlane:
a JAXJob trains the ``base`` preset (d_model 1024, 24 layers, vocab
32 000, ~470 M parameters, random weights from ``SEED``) on ``lm-small``
at its own sequence length 2048 through ``runners/lm_runner.py`` for a
few optimizer steps and exports it; an InferenceService then serves the
export through ``serving/server.py`` -> ``LMPredictor`` ->
``DecodeEngine`` with its defaults (paged KV, chunked prefill,
speculative draft) and answers ``:generate`` requests over HTTP through
the router. Checked: the attention path the worker names is the Pallas
flash kernel, losses are finite and fall, token ids are in range and of
the requested count, greedy output is equal across identical requests
and between a streamed and a buffered answer, a prompt longer than one
prefill chunk was chunked, the fused speculative step ran.

Four chips (``--chips 4``, and no other phase). The same JAXJob under
``parallelism: {tensor: 2, data: 2, fsdp: true}`` and then under
``{data: 4}`` (replicated), one worker process driving all four chips,
same seed and global batch: the mesh must span four distinct TPU
devices, the sharded plan's per-device parameter bytes must be well
under the whole, and the two plans' losses must agree.

A chip has one owner. This process never initialises a JAX backend (it
asserts so before printing ``ok``); the device in the last line is what
the worker and the replica each report about themselves, and the two
must agree. Phases are sequential and each child is gone, by pid, before
the next needs the chip. On anything but a TPU the script says so and
exits non-zero; any failed check, phase error or timeout does the same
after printing the phase and the tail of the child's log.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import random
import shutil
import signal
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from kubeflow_tpu.api.manifest import load_manifests  # noqa: E402
from kubeflow_tpu.controlplane import ControlPlane  # noqa: E402
from kubeflow_tpu.runners.jax_runner import compile_cache_dir  # noqa: E402

SEED = 0
PRESET = "base"
DATASET = "lm-small"       # vocab 32 000, sequence length 2048
BATCH = 8                  # base at S=2048 peaks at 14.3 of 15.75 GiB (AOT)
TRAIN_STEPS = 12
MESH_STEPS = 6
PARITY_STEPS = 4           # tests/test_parallel.py's plan-parity window
PARITY_TOL = 5e-2          # ... and its tolerance
NEW_TOKENS = 16
LONG_PROMPT = 600          # > 2 prefill chunks of the engine's default 256
BUDGET_S = 1100.0          # of the contract's 1200

TRAIN_ARGV = [
    sys.executable, "-m", "kubeflow_tpu.runners.lm_runner",
    f"--preset={PRESET}", f"--dataset={DATASET}", f"--batch-size={BATCH}",
    f"--seed={SEED}", "--learning-rate=1e-3", "--warmup-steps=2",
    "--remat", "--remat-policy=save_flash_full", "--log-every=1",
    "--no-checkpoint"]

PIPELINE = """
apiVersion: kubeflow.org/v1
kind: Pipeline
metadata: {{name: smoke, namespace: default}}
spec:
  steps:
  - name: train
    template:
      spec:
        containers:
        - name: main
          command: {argv}
  - name: serve
    dependsOn: [train]
    resource:
      apiVersion: serving.kubeflow.org/v1beta1
      kind: InferenceService
      spec:
        predictor:
          minReplicas: 1
          maxReplicas: 1
          jax:
            storageUri: file://${{params.workspace}}/lm-export
"""

MESH_JOB = """
apiVersion: kubeflow.org/v1
kind: JAXJob
metadata: {{name: {name}, namespace: default}}
spec:
  runPolicy: {{backoffLimit: 0}}
  parallelism: {parallelism}
  jaxReplicaSpecs:
    Worker:
      replicas: 1
      restartPolicy: Never
      template:
        spec:
          containers:
          - name: jax
            command: {argv}
"""

_T0 = time.monotonic()


class SmokeFailure(Exception):
    def __init__(self, message: str, log: str = ""):
        super().__init__(message)
        self.log = log


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def deadline(seconds: float) -> float:
    """A phase's time limit, cut to what is left of the whole budget."""
    return min(time.monotonic() + seconds, _T0 + BUDGET_S)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.monotonic()
    say(f"phase {name}: start")
    try:
        yield
    except SmokeFailure as e:
        e.phase = getattr(e, "phase", name)
        raise
    except Exception as e:  # an error is a failed phase, with its name
        failure = SmokeFailure(f"{type(e).__name__}: {e}")
        failure.phase = name
        raise failure from e
    say(f"phase {name}: ok wall_s={time.monotonic() - t0:.1f}")


def check(ok: bool, what: str, log: str = "") -> None:
    if not ok:
        raise SmokeFailure(what, log)


def kv(line: str) -> dict:
    return dict(tok.split("=", 1) for tok in line.split() if "=" in tok)


def tagged(log: str, tag: str) -> list:
    return [line[len(tag):].strip() for line in log.splitlines()
            if line.startswith(tag)]


def read(path: str) -> str:
    if not os.path.exists(path):
        return ""
    with open(path, "rb") as f:
        return f.read().decode(errors="replace")


def children(home: str, module: str = "") -> list:
    """Live processes of this run: their command line or working
    directory names the plane home (a worker runs in its gang directory,
    a replica serves an export under the home), optionally narrowed to
    one entry point. Read from /proc, not from handles the program gave
    us: an orphan is found either way."""
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{entry}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
            cwd = os.readlink(f"/proc/{entry}/cwd")
        except OSError:
            continue  # gone between listdir and open
        if state != "Z" and module in cmd and (home in cmd or home in cwd):
            found.append(int(entry))
    return found


def wait_gone(home: str, module: str, what: str,
              seconds: float = 30.0) -> None:
    limit = deadline(seconds)
    while children(home, module):
        check(time.monotonic() < limit,
              f"{what} still alive: pids {children(home, module)}")
        time.sleep(0.1)
    say(f"{what}: no process left")


def device_of(log: str, who: str) -> dict:
    """The device triple a child printed about itself; it must be a TPU."""
    lines = tagged(log, "device ")
    check(bool(lines), f"{who} printed no device line", log)
    dev = json.loads(lines[-1])
    check(dev["platform"] == "tpu",
          f"device is not a TPU: {who} reports {dev}", log)
    return dev


def cache_entries() -> int:
    path = compile_cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# -- training ---------------------------------------------------------------

def wait_for_job(cp: ControlPlane, name: str, seconds: float) -> str:
    """Wait for the JAXJob to succeed; fail as soon as its worker says
    it is not on a TPU, or the scheduler says it cannot be placed."""
    limit = deadline(seconds)
    device_seen = False
    while True:
        job = cp.store.try_get("JAXJob", name)
        log = ""
        if job is not None:
            try:
                log = cp.job_logs("JAXJob", name)
            except FileNotFoundError:
                pass  # worker not started yet
            for c in job.conditions:
                check(not (c.type == "Queued" and c.status == "True"
                           and c.reason == "Unschedulable"),
                      f"JAXJob {name} cannot be scheduled: {c.message}")
            if not device_seen and tagged(log, "device "):
                say(f"worker {name}: pid="
                    f"{children(cp.home, 'runners.lm_runner')} "
                    f"device {device_of(log, 'worker')}")
                device_seen = True
            if job.is_finished():
                check(job.has_condition("Succeeded"),
                      f"JAXJob {name} failed: "
                      f"{[c.to_dict() for c in job.conditions]}", log)
                return log
        check(time.monotonic() < limit,
              f"JAXJob {name} not finished after {seconds:.0f}s", log)
        time.sleep(0.5)


def training_report(log: str, name: str, steps: int) -> dict:
    """Parse and check one lm_runner log: device, attention path,
    per-step losses (compile separated from steps)."""
    dev = device_of(log, f"worker {name}")
    att = kv(tagged(log, "attention ")[-1])
    check(att.get("path") == "flash" and att.get("seq_len") == "2048",
          f"attention path is {att}, expected the flash kernel at S=2048",
          log)
    first = tagged(log, "first_step ")
    check(len(first) == 1, "no first_step line", log)
    first = kv(first[0])
    rows = [kv(line) for line in log.splitlines() if line.startswith("step=")]
    losses = [float(first["loss"])] + [float(r["loss"]) for r in rows]
    check(len(losses) == steps,
          f"{len(losses)} step losses logged, expected {steps}", log)
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}",
          log)
    times = sorted(float(r["step_time"]) for r in rows)
    say(f"worker {name}: {kv(tagged(log, 'runner_start ')[-1]).get('plan')} "
        f"attention={att['path']} compile_s={first['compile_seconds']} "
        f"step_time_median_s={times[len(times) // 2]:.3f} "
        f"tokens_per_step={BATCH * int(att['seq_len'])}")
    say(f"worker {name}: losses " + " ".join(f"{x:.4f}" for x in losses))
    return {"device": dev, "losses": losses,
            "param_bytes": json.loads(tagged(log, "param_bytes ")[-1])}


# -- serving ----------------------------------------------------------------

def post_generate(url: str, body: dict):
    return urllib.request.urlopen(urllib.request.Request(
        f"{url}/v1/models/smoke-serve:generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}), timeout=120.0)


def generate(url: str, body: dict) -> dict:
    with post_generate(url, body) as r:
        return json.load(r)


def generate_stream(url: str, body: dict) -> list:
    """One SSE answer: the token of every ``data:`` event, in order."""
    tokens, done = [], False
    with post_generate(url, dict(body, stream=True)) as r:
        check("text/event-stream" in r.headers.get("Content-Type", ""),
              f"stream answered {r.headers.get('Content-Type')}")
        for raw in r:
            line = raw.decode().strip()
            check(not line.startswith("event: error"),
                  f"stream error frame: {line}")
            if not line.startswith("data:"):
                continue
            event = json.loads(line[len("data:"):])
            if event.get("done"):
                done = True
            elif "token" in event:
                check(event["index"] == len(tokens),
                      f"stream index {event['index']} != {len(tokens)}")
                tokens.append(event["token"])
    check(done, "stream ended without its done event")
    return tokens


def counters(metrics_url: str) -> dict:
    """kfx_lm_* counter totals from a replica's /metrics."""
    with urllib.request.urlopen(metrics_url, timeout=30) as r:
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if line.startswith("kfx_lm_") and " " in line:
            name = line.split("{", 1)[0].split(" ", 1)[0]
            out[name] = out.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
    return out


def serve_phase(cp: ControlPlane, replica_log: str, vocab: int) -> dict:
    with phase("serve.ready"):
        limit = deadline(600)
        while True:
            isvc = cp.store.try_get("InferenceService", "smoke-serve")
            log = read(replica_log)
            if isvc is not None and isvc.has_condition("Ready"):
                break
            check("Traceback" not in log, "replica crashed while loading",
                  log)
            check(time.monotonic() < limit,
                  "InferenceService not Ready after 600s", log)
            time.sleep(0.5)
        log = read(replica_log)
        dev = device_of(log, "replica")
        ready = kv(tagged(log, "server_ready ")[-1])
        pids = children(cp.home, "serving.server")
        check(len(pids) == 1, f"expected one replica process, found {pids}")
        say(f"replica: pid={pids[0]} device {dev} "
            f"framework={ready['framework']} "
            f"load_and_warm_s={ready['load_seconds']}")
        url = isvc.status["url"]

    with phase("serve.generate"):
        rng = random.Random(SEED)
        short = [rng.randrange(vocab) for _ in range(12)]
        long_ = [rng.randrange(vocab) for _ in range(LONG_PROMPT)]
        greedy = {"max_new_tokens": NEW_TOKENS, "temperature": 0.0}
        answers = []

        def ask(label: str, body: dict, stream: bool = False) -> list:
            t0 = time.monotonic()
            if stream:
                toks = generate_stream(url, body)
            else:
                out = generate(url, body)["generated_tokens"]
                check(len(out) == 1, f"{label}: {len(out)} answers")
                toks = out[0]
            check(len(toks) == body["max_new_tokens"],
                  f"{label}: {len(toks)} tokens, asked for "
                  f"{body['max_new_tokens']}", read(replica_log))
            check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
                  f"{label}: token id out of range: {toks}")
            say(f"request {label}: prompt={len(body['prompt_tokens'][0])} "
                f"tokens_returned={len(toks)} "
                f"wall_s={time.monotonic() - t0:.2f} first={toks[:4]}")
            answers.append(toks)
            return toks

        a = ask("greedy", dict(greedy, prompt_tokens=[short]))
        b = ask("greedy-again", dict(greedy, prompt_tokens=[short]))
        check(a == b, f"greedy output differs across identical requests: "
                      f"{a} vs {b}")
        s = ask("greedy-streamed", dict(greedy, prompt_tokens=[short]),
                stream=True)
        check(s == a, f"streamed tokens differ from buffered: {s} vs {a}")
        ask("long-prompt", dict(greedy, prompt_tokens=[long_]))
        ask("sampled", {"prompt_tokens": [short], "temperature": 0.8,
                        "top_k": 40, "seed": SEED + 1,
                        "max_new_tokens": NEW_TOKENS})

        serving = cp.manager.controllers["InferenceService"]
        (_, metrics_url), = serving.scrape_targets()
        c = counters(metrics_url)
        say("engine: " + " ".join(
            f"{k[len('kfx_lm_'):]}={int(c.get(k, 0))}" for k in (
                "kfx_lm_engine_chunks_total", "kfx_lm_prefill_chunks_total",
                "kfx_lm_spec_proposed_total", "kfx_lm_spec_accepted_total",
                "kfx_lm_generated_tokens_total")))
        check(c.get("kfx_lm_prefill_chunks_total", 0) >= 2,
              f"the {LONG_PROMPT}-token prompt was not prefilled in chunks",
              read(replica_log))
        check(c.get("kfx_lm_spec_proposed_total", 0) > 0,
              "the fused speculative step never ran", read(replica_log))
        check(c.get("kfx_lm_generated_tokens_total", 0)
              >= len(answers) * NEW_TOKENS, "engine token count too low")
    return dev


def one_chip(cp: ControlPlane) -> dict:
    with phase("train"):
        argv = TRAIN_ARGV + [f"--steps={TRAIN_STEPS}",
                             "--export-dir=${params.workspace}/lm-export"]
        cp.apply(load_manifests(PIPELINE.format(argv=json.dumps(argv))))
        limit = deadline(60)
        while cp.store.try_get("JAXJob", "smoke-train") is None:
            check(time.monotonic() < limit, "pipeline created no train job")
            time.sleep(0.1)
        log = wait_for_job(cp, "smoke-train", 600)
        rep = training_report(log, "smoke-train", TRAIN_STEPS)
        losses = rep["losses"]
        check(losses[-1] < losses[0],
              f"loss did not fall: first {losses[0]} last {losses[-1]}", log)
        check(len(tagged(log, "exported_lm ")) == 1, "no export", log)
        wait_gone(cp.home, "runners.lm_runner", "train worker")

    export = os.path.join(cp.home, "pipeline-workspaces", "default_smoke",
                          "lm-export")
    with open(os.path.join(export, "lm_config.json")) as f:
        vocab = json.load(f)["config"]["vocab_size"]
    say(f"export: {export} "
        f"params_mb={os.path.getsize(os.path.join(export, 'params.msgpack')) >> 20}")
    replica_log = os.path.join(cp.home, "serving", "default_smoke-serve",
                               "default-0.log")
    try:
        served = serve_phase(cp, replica_log, vocab)
    finally:
        with phase("serve.delete"):
            # Deleting the pipeline deletes the service it owns; the
            # replica must be gone — reaped — before anything else may
            # want the chip, and before this script ends.
            cp.store.delete("Pipeline", "smoke")
            wait_gone(cp.home, "serving.server", "serving replica")
    check(served == rep["device"],
          f"worker and replica disagree on the device: {rep['device']} "
          f"vs {served}")
    check(served["count"] == 1, f"expected one chip, found {served}")
    return served


# -- four chips ---------------------------------------------------------------

def four_chips(cp: ControlPlane) -> dict:
    check(cp.sched.capacity >= 4 or os.environ.get("JAX_PLATFORMS") == "cpu",
          f"--chips 4 needs four TPU chips; this host exposes "
          f"{cp.sched.capacity}")
    argv = json.dumps(TRAIN_ARGV + [f"--steps={MESH_STEPS}"])
    reports = {}
    for name, par in (("smoke-tp2-dp2-fsdp",
                       {"tensor": 2, "data": 2, "fsdp": True}),
                      ("smoke-dp4", {"data": 4})):
        with phase(name):
            cp.apply(load_manifests(MESH_JOB.format(
                name=name, parallelism=json.dumps(par), argv=argv)))
            log = wait_for_job(cp, name, 500)
            rep = reports[name] = training_report(log, name, MESH_STEPS)
            check(rep["device"]["count"] == 4,
                  f"{name}: worker sees {rep['device']}, expected 4 chips")
            per_dev = rep["param_bytes"]["per_device"]
            check(len(per_dev) == 4,
                  f"{name}: parameters live on devices {sorted(per_dev)}, "
                  f"expected four distinct ones", log)
            total = rep["param_bytes"]["total"]
            share = max(per_dev.values()) / total
            say(f"worker {name}: param_bytes total={total} "
                f"per_device={per_dev} max_share={share:.3f}")
            if par.get("fsdp"):
                check(0.2 <= share <= 0.55,
                      f"{name}: a device holds {share:.2f} of the "
                      f"parameters — not sharded tensor x fsdp", log)
            else:
                check(share > 0.99, f"{name}: expected a full replica per "
                                    f"device, found {share:.2f}", log)
            cp.store.delete("JAXJob", name)
            wait_gone(cp.home, "runners.lm_runner", f"worker {name}")
    a, b = (reports[n]["losses"][:PARITY_STEPS] for n in reports)
    gaps = [abs(x - y) for x, y in zip(a, b)]
    say(f"plan parity: max |loss gap| over {PARITY_STEPS} steps = "
        f"{max(gaps):.5f} (tolerance {PARITY_TOL})")
    check(max(gaps) < PARITY_TOL,
          f"sharded and replicated losses disagree: {a} vs {b}")
    return reports["smoke-tp2-dp2-fsdp"]["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the multi-chip training phase and its "
                         "comparison, and no other phase")
    args = ap.parse_args()

    home = tempfile.mkdtemp(prefix="kfx-smoke-")
    say(f"chip_smoke chips={args.chips} root={ROOT} home={home} "
        f"compile_cache={compile_cache_dir()} entries={cache_entries()}")
    device = None
    try:
        with ControlPlane(home=home) as cp:
            say(f"plane: capacity={cp.sched.capacity} chip(s)")
            device = four_chips(cp) if args.chips == 4 else one_chip(cp)
        wait_gone(home, "", "children of this run")
    except SmokeFailure as e:
        say(f"FAILED in phase {getattr(e, 'phase', 'setup')}: {e}")
        if e.log:
            print("---- tail of the child's log ----\n" + e.log[-4000:],
                  flush=True)
        return 1
    finally:
        # Whatever happened, nothing this run started survives it.
        for pid in children(home):
            os.kill(pid, signal.SIGKILL)
        shutil.rmtree(home, ignore_errors=True)
    say(f"compile_cache entries={cache_entries()}")
    if "jax" in sys.modules:
        say("FAILED: this process imported jax; a chip has one owner")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
