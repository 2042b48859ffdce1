"""A throw-away tiny benchmark beside the real one: a copy of
``benchmark/`` in a temporary root, plus a configuration, two traffic
mixes, two cells and a per-layer metric (with a reader of its own)
added as NEW files and NEW manifest entries only. What the tests drive
on the CPU, and the proof that a later PR can add without editing."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_CONFIG = {
    "source": "throw-away", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 128,
    "serving": {"dtype": "float32", "param_dtype": "float32", "slots": 4,
                "max_seq_len": 128, "kv_page_size": 16, "decode_chunk": 4,
                "prefill_chunk": 32, "speculative": {"enabled": False},
                # Off: with it on, this float32 engine now and then
                # serves a token 0.03-0.06 below the reference's best
                # (PERF.md section 7); the tests are not about that.
                "prefix_cache": False},
    "training": {"dtype": "float32", "param_dtype": "float32",
                 "layout": {"data": 4, "fsdp": True}, "remat": True,
                 "remat_policy": "nothing", "loss_chunk": 32,
                 "learning_rate": 0.01, "warmup_steps": 4,
                 "total_steps": 100, "beta1": 0.9, "beta2": 0.95,
                 "eps": 1e-08, "weight_decay": 0.1, "grad_clip": 1.0,
                 "reference_rows_per_chip": 1, "checked_steps": 3},
    "correct": {"served_logit_gap_max": 1e-3, "served_logit_gap_mean": 1e-4,
                # sound float32 runs read 4e-7 / 3e-7 / 7e-7 here and
                # bfloat16 parameters 5e-5 / 3e-3 / 2e-3
                "loss_gap_max": 1e-5, "first_grad_norm_gap": 1e-4,
                "param_change_norm_gap": 1e-4},
}
TINY_CHAT = {
    "kind": "serve_open_loop", "arrivals": {"process": "exponential_quantiles"},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 80},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 10},
    "temperature": 0.0, "shared_prefix": None, "schedule_seed": None,
}
TINY_TRAIN = {"kind": "train_stream", "global_batch_sequences": 4,
              "sequence_tokens": 64, "branching": 4}
TINY_READER = '''"""A reader kind added by a later PR: requests finished."""


def read(ctx, args):
    rows = ctx.get("rows")
    return None if rows is None else float(sum(r["ok"] for r in rows))
'''


def make_root(tmp: str) -> str:
    """Copy the benchmark into ``tmp`` and add the tiny files; returns
    the new root."""
    root = os.path.join(tmp, "root")
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"))
    before = {os.path.relpath(os.path.join(d, f), bench): os.path.getsize(
        os.path.join(d, f)) for d, _, fs in os.walk(bench) for f in fs}

    def put(rel: str, obj) -> None:
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    put("configs/tiny.json", TINY_CONFIG)
    put("traffic/tiny-chat.json", TINY_CHAT)
    put("traffic/tiny-train.json", TINY_TRAIN)
    chat = {"rate_rps": 3.0, "grace_s": 30.0, "check_requests": 3,
            "warm_new_tokens": 4, "trace_after_s": 0.5, "trace_seconds": 1.0,
            "late_share_limit": 20.0, "late_floor_ms": 250.0, "serving": {},
            "traced_replica": "benchmark.workers.traced_replica"}
    put("cells/tiny-chat.json", chat)
    # A sender held to a limit of nought: it always runs late by some
    # microseconds, so the guard on the sender is seen to bite.
    put("traffic/tiny-late.json", TINY_CHAT)
    put("cells/tiny-late.json", dict(chat, late_share_limit=0.0,
                                     late_floor_ms=0.0))
    put("cells/tiny-train.json", {"traced_steps": 3})
    put("traffic/tiny-long.json", dict(
        TINY_CHAT, output_tokens={"dist": "uniform", "min": 12, "max": 20}))
    big = dict(chat, check_requests=24, trace_after_s=0.2, trace_seconds=0.5)
    put("cells/tiny-long.json", big)
    put("cells/tiny-broken.json", dict(
        big, traced_replica="benchmark.tests.broken_replica"))
    put("readers/finished_count.py", TINY_READER)
    put("layer_metrics/finished_requests.json",
        {"name": "finished_requests", "unit": "requests",
         "reader": "finished_count", "args": {}})
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "tiny", "source": "throw-away",
                           "file": "benchmark/configs/tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [
        {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "test"},
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test"},
        {"name": "tiny-long", "config": "tiny", "traffic": "tiny-long",
         "chips": 1, "why": "test"},
        {"name": "tiny-broken", "config": "tiny", "traffic": "tiny-long",
         "chips": 1, "why": "test"},
        {"name": "tiny-late", "config": "tiny", "traffic": "tiny-late",
         "chips": 1, "why": "test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            kind = "tiny-train" if "train" in m.get(
                "moves", m["name"]) else "tiny-chat"
            m["workloads"] = m["workloads"] + (
                [kind] if kind == "tiny-train"
                else ["tiny-chat", "tiny-long", "tiny-broken", "tiny-late"])
    man["per_layer"].append(
        {"name": "finished_requests", "unit": "requests", "better": "higher",
         "source": "program_counter", "layer": "test", "moves": "ttft_p95_ms",
         "workloads": ["tiny-chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    after = {os.path.relpath(os.path.join(d, f), bench): os.path.getsize(
        os.path.join(d, f)) for d, _, fs in os.walk(bench) for f in fs}
    assert all(after[k] == v for k, v in before.items()), "a file changed"
    # The CPU has no published peak. Only so that a traced run can be
    # driven to its end here, the COPY's table gets a made-up one.
    path = os.path.join(bench, "peaks.json")
    with open(path) as f:
        table = json.load(f)
    table["devices"]["cpu"] = dict(table["devices"]["TPU v5e"])
    with open(path, "w") as f:
        json.dump(table, f)
    return root


def run_cell(root: str, workload: str, *, seed: int = 7, seconds: float = 4,
             trace: int = 0, control: str = "", devices: int = 1,
             timeout: float = 600.0):
    """Drive ``run_cell`` of the copy in a fresh interpreter on the CPU
    (the look for a chip skipped). Returns (result dict, output)."""
    import subprocess
    import sys

    code = (
        "import sys, json; sys.path.insert(0, %r); import os\n"
        "os.environ['PYTHONPATH'] = %r + os.pathsep + %r\n"
        "from benchmark.run import run_cell\n"
        "print(run_cell(%r, %d, %r, %r, require_tpu=False, control=%r, "
        "root=%r))\n" % (root, root, REPO, workload, seed, seconds,
                         bool(trace), control, root))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_cwd(),
                       capture_output=True, text=True, timeout=timeout)
    out = p.stdout + p.stderr
    if p.returncode != 0:
        raise RuntimeError(f"run_cell exited {p.returncode}:\n{out[-5000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1]), out


def tmp_cwd() -> str:
    return os.environ.get("TMPDIR", "/tmp")
