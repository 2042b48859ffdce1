"""The per-layer metrics that find the program's parts by the names
the program gives them (PR 25): their files, the reader of named
kernels on a recorded reduction, what a program without the names (the
parent) gives, and the tiny serving cell printing them. Run with
``pytest benchmark/tests`` (not part of tier-1)."""

import json
import os

import pytest

from benchmark import manifest
from benchmark.readers import (timing_stat, trace_named_kernel_roofline,
                               trace_program_time)
from benchmark.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
SERVING = ["decode_program_ms", "prefill_program_ms",
           "engine_host_ms_per_chunk", "prefill_span_p95_ms",
           "first_token_wait_ms"]
TRAINING = ["flash_fwd_roofline", "flash_bwd_roofline", "train_program_ms"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_named_reduction.json")) as f:
        return json.load(f)


def _args(name):
    return manifest.layer_metric(name)["args"]


def _train_ctx(trace):
    """The mini run's cell: 4 x 2048 tokens a step on one chip, 8 heads
    of 128."""
    return {"trace": trace, "worker": {"plan": {"dp": 1, "tp": 1}},
            "cfg": {"hidden_size": 1024, "num_attention_heads": 8},
            "mix": {"global_batch_sequences": 4, "sequence_tokens": 2048},
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("name", SERVING + TRAINING)
def test_new_metric_files_load_through_the_manifest(name):
    man = manifest.manifest()
    entry, = [m for m in man["per_layer"] if m["name"] == name]
    spec = manifest.layer_metric(name)
    for key in ("name", "unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[key], key
    cell = "baichuan7b-chat-steady" if name in SERVING \
        else "deepseek7b-train-fsdp4"
    assert entry["workloads"] == [cell]
    # an empty run context gives nothing to read and does not raise
    assert manifest.read_layer_metrics(
        {"per_layer": [entry]}, cell, {}) == {}


def test_named_kernel_rooflines_on_the_recorded_reduction(recorded):
    """Expected from the kernels' matmuls alone: 2 (forward), 3 (dq)
    and 4 (dkv) of 2 S^2 D a head over the causal half, at 197 TFLOP/s,
    four calls each (2 layers x 2 steps)."""
    mini = recorded["mini"]
    unit = 2.0 * 4 * 8 * 2048 * 2048 * 128 / 2.0 / 197e12
    seconds = {k.split(" = ")[0]: v for k, v in mini["op_seconds"].items()}
    fwd = trace_named_kernel_roofline.read(
        _train_ctx(mini), _args("flash_fwd_roofline"))
    bwd = trace_named_kernel_roofline.read(
        _train_ctx(mini), _args("flash_bwd_roofline"))
    assert fwd == pytest.approx(
        100 * 4 * 2 * unit / seconds["%kfx_flash_fwd.6"], rel=1e-9)
    assert bwd == pytest.approx(
        100 * 4 * 7 * unit / (seconds["%kfx_flash_dq.11"]
                              + seconds["%kfx_flash_dkv.11"]), rel=1e-9)
    assert 0 < fwd < 100 and 0 < bwd < 100
    # the fusion that only consumes %kfx_flash_dq.11 is no kernel
    assert not trace_named_kernel_roofline.named(
        next(k for k in mini["op_seconds"] if k.startswith("%fusion.339")),
        "kfx_flash_dq")
    # a kernel is told from another whose name it begins
    assert not trace_named_kernel_roofline.named(
        next(k for k in mini["op_seconds"] if k.startswith("%kfx_flash_dkv")),
        "kfx_flash_d")


@pytest.mark.parametrize("name, run, expected_ms", [
    # nearest-rank medians (benchmark/stats.py) of the recorded runs:
    # the lower of two steps; the third of five chunks, one of them cut
    # by the trace's edge, over 8 tokens; the second of four prefills
    ("train_program_ms", "mini", 22.339306),
    ("decode_program_ms", "serve", 574.43691 / 8),
    ("prefill_program_ms", "serve", 38.495218),
])
def test_named_programs_on_the_recorded_reduction(recorded, name, run,
                                                  expected_ms):
    ctx = {"trace": recorded[run], "serving": {"decode_chunk": 8}}
    got = trace_program_time.read(ctx, _args(name))
    assert got == pytest.approx(expected_ms, rel=1e-7)


def test_a_program_without_the_names_gives_nothing_to_read():
    """The parent of PR 25: every engine program is ``jit_run`` and the
    kernels are anonymous (benchmark/tests/recorded_trace.json). The
    new files read nothing there and raise nothing, as the driver asks
    of a metric the parent cannot have."""
    from benchmark import trace_reduce

    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        lines = [dict(l, events=[tuple(e) for e in l["events"]])
                 for l in json.load(f)]
    old = trace_reduce.reduce(lines)
    ctx = dict(_train_ctx(old), serving={"decode_chunk": 8},
               rows=[{"ok": True, "ttft_s": 1.0,
                      "timing": {"queue_wait_s": 0.1, "prefill_s": 0.8}}])
    for name in ("decode_program_ms", "prefill_program_ms",
                 "train_program_ms"):
        assert trace_program_time.read(ctx, _args(name)) is None
    for name in ("flash_fwd_roofline", "flash_bwd_roofline"):
        assert trace_named_kernel_roofline.read(ctx, _args(name)) is None
    for name in ("prefill_span_p95_ms", "first_token_wait_ms"):
        assert timing_stat.read(ctx, _args(name)) is None


def test_tiny_serving_cell_prints_the_new_serving_metrics(tmp_path):
    """The whole path on the CPU: replica, engine counters, the
    stream's ``done.timing``, the traced programs' names. The tiny
    cell's prompt chunk is 32 tokens, so in this COPY the prefill
    metric's file names that program (the real file names the real
    cell's, kfx_prefill_256)."""
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "benchmark", "layer_metrics",
                        "prefill_program_ms.json")
    spec = manifest.load_json(path)
    spec["args"]["match"] = "kfx_prefill_32"
    with open(path, "w") as f:
        json.dump(spec, f)
    res, out = tiny.run_cell(root, "tiny-chat", seconds=4, trace=1)
    assert res["correct"] is True, out[-3000:]
    got = res["metrics"]
    assert set(SERVING) <= set(got), sorted(got)
    assert all(got[n]["value"] > 0 for n in SERVING)
    # decode_hbm_pct finds its program by the same name
    assert got["decode_hbm_pct"]["value"] > 0
