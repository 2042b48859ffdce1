"""CPU-only tests of the benchmark's own arithmetic and data plumbing.
Run with ``pytest benchmark/tests`` (not part of tier-1)."""

import math
import os

import numpy as np
import pytest

from benchmark import flops, manifest, peaks, stats, traffic, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = manifest.traffic("chat-short")


def test_generator_is_deterministic_in_seed_and_describes_its_draw():
    a = traffic.serve_requests(CHAT, 64000, 3.0, 20, seed=2**31 + 11)
    b = traffic.serve_requests(CHAT, 64000, 3.0, 20, seed=2**31 + 11)
    c = traffic.serve_requests(CHAT, 64000, 3.0, 20, seed=12)
    assert a == b and a != c
    d = traffic.describe_lengths(a)
    assert d["n"] == 60 and d["prompt_tokens"]["min"] >= 16
    assert d["prompt_tokens"]["max"] <= 1024
    assert 8 <= d["output_tokens"]["min"] and d["output_tokens"]["max"] <= 256
    assert all(0 <= t < 64000 for r in a for t in r["prompt"])
    assert a == sorted(a, key=lambda r: r["due_s"]) and a[0]["due_s"] == 0


def test_every_seed_sends_the_same_sizes_and_gaps():
    sizes = lambda rs: (sorted(len(r["prompt"]) for r in rs),
                        sorted(r["max_new_tokens"] for r in rs))
    gaps = lambda rs: sorted(np.round(np.diff([r["due_s"] for r in rs]), 9))
    order = lambda rs: [(len(r["prompt"]), r["max_new_tokens"],
                         round(r["due_s"], 9)) for r in rs]
    # chat-short replays one schedule: the seed draws only the tokens
    a = traffic.serve_requests(CHAT, 64000, 3.0, 20, seed=1)
    b = traffic.serve_requests(CHAT, 64000, 3.0, 20, seed=2)
    assert CHAT["schedule_seed"] is not None and order(a) == order(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # a mix whose schedule_seed is null: the same multisets in another order
    free = dict(CHAT, schedule_seed=None)
    c = traffic.serve_requests(free, 64000, 3.0, 20, seed=1)
    d = traffic.serve_requests(free, 64000, 3.0, 20, seed=2)
    assert sizes(a) == sizes(c) == sizes(d) and gaps(c) == gaps(d)
    assert order(c) != order(d)
    assert abs(a[-1]["due_s"] - 20 * 59 / 60) < 0.5  # mean gap 1/rate


def test_shared_prefix_mix_shares_and_warms_its_prefixes():
    mix = dict(CHAT, shared_prefix={"count": 3, "tokens": 64, "zipf": 1.0,
                                    "send_in_setup": True})
    reqs = traffic.serve_requests(mix, 1000, 5.0, 10, seed=3)
    heads = {tuple(r["prompt"][:64]) for r in reqs}
    assert 1 < len(heads) <= 3
    setup = traffic.setup_requests(mix, 1000, seed=3)
    assert {tuple(r["prompt"]) for r in setup} >= heads
    assert traffic.warm_prompt_lengths(mix)[0] >= 64 + 16


def test_markov_batches_rows_all_differ_and_repeat_per_seed():
    mix = {"global_batch_sequences": 4, "sequence_tokens": 32, "branching": 8}
    a, b = (next(traffic.markov_batches(mix, 100, s)) for s in (5, 5))
    assert a.shape == (4, 33) and (a == b).all() and a.max() < 100
    assert len({tuple(r) for r in a}) == 4
    it = traffic.markov_batches(mix, 100, 5)
    assert not (next(it) == next(it)).all()


def test_percentile_counts_failures_as_the_worst():
    ok = [0.1] * 94
    vals = stats.with_failures(ok + [None] * 6)
    assert stats.percentile(vals, 95) == math.inf
    assert stats.percentile(
        stats.with_failures(ok + [0.1] + [None] * 5), 95) == 0.1
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.supported_percentile(200) == 95.0
    assert stats.supported_percentile(150) == 90.0
    assert stats.supported_percentile(2000) == 99.0


def test_delivered_rate_does_not_swing_with_one_hand_over_at_the_edge():
    """16 rows get 8 tokens each every 0.58 s; the window's edge falls
    just before or just after one hand-over."""
    from benchmark.serve_open_loop_cell import delivered_rate, request_rows

    def rate(first: float, seconds: float = 51.0) -> float:
        times = [first + 0.58 * k for k in range(120) for _ in range(8)]
        res = {"results": [dict(
            tokens=[1] * len(times), times=times, t_sent=0.0, t_first=first,
            t_last=times[-1], t_end=times[-1], done=True, error=None,
            timing=None)] * 16}
        reqs = [{"due_s": 0.0, "prompt": [1], "max_new_tokens": len(times)}
                ] * 16
        return delivered_rate(request_rows(reqs, res, seconds))

    inside, outside = rate(51.0 - 0.001 - 0.58 * 87), rate(51.0 + 0.001
                                                          - 0.58 * 87)
    assert abs(inside / outside - 1) < 0.003      # a fixed edge: 1.1 %
    assert abs(inside - 128 / 0.58) < 2.0


@pytest.mark.parametrize("p50_ms, limit_ms", [
    (60.0, 250.0),      # a fast program: the floor decides
    (1249.0, 250.0),
    (1251.0, 250.2),    # a slow one: the share of its median TTFT
    (5000.0, 1000.0),
])
def test_lateness_limit_is_the_larger_of_floor_and_share(p50_ms, limit_ms):
    from benchmark.serve_open_loop_cell import late_limit_ms

    cell = {"late_floor_ms": 250.0, "late_share_limit": 0.2}
    assert late_limit_ms(cell, p50_ms) == pytest.approx(limit_ms)
    real = manifest.cell("baichuan7b-chat-steady")
    assert late_limit_ms(real, 1.0) == real["late_floor_ms"] >= 200.0
    with pytest.raises(manifest.ManifestError, match="late_floor_ms"):
        late_limit_ms(manifest.table(os.path.join(
            manifest.BENCH_DIR, "cells", "deepseek7b-train-fsdp4.json")), 1.0)


def test_lateness_is_read_by_rank_over_the_requests_sent():
    from benchmark.serve_open_loop_cell import lateness_ms

    rows = [{"late_s": 0.002}] * 140 + [{"late_s": 0.09}, {"late_s": 0.3},
                                        {"late_s": None}]
    late = lateness_ms(rows)        # 142 sent: rank int(0.99 * 141) = 139
    assert late["max"] == pytest.approx(300.0)
    assert late["p99"] == pytest.approx(2.0)
    assert lateness_ms(rows + [{"late_s": 0.5}])["p99"] == pytest.approx(90.0)
    assert lateness_ms([{"late_s": None}])["p99"] == math.inf


@pytest.mark.parametrize("late_ms, correct", [(120.0, True), (251.0, False)])
def test_result_line_carries_the_compared_numbers_last(late_ms, correct):
    import json

    from benchmark import harness as H

    rows = H.print_comparison([
        {"name": "served_logit_gap_mean", "value": 0.005, "limit": 0.012},
        {"name": "compilations_in_window", "value": 0, "limit": 0},
        {"name": "generator_late_p99_ms", "value": late_ms, "limit": 250.0}])
    assert [r["held"] for r in rows] == [True, True, correct]
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1}
    metrics = {"setup_s": {"value": 1.0, "unit": "s"}}
    line = json.loads(H.result_line(rows, 143, 0, metrics, dev))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is correct
    assert line["compared"]["generator_late_p99_ms"] == {
        "value": late_ms, "limit": 250.0}
    traced = json.loads(H.result_line(rows, 143, 0, metrics, dev,
                                      {"device_ops": [["x", 1.0]]}))
    assert list(traced)[-2:] == ["breakdown", "compared"]


def test_interval_arithmetic():
    u = trace_reduce.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)] and trace_reduce.total(u) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace_reduce.subtract([(0, 2), (4, 6)], [(1, 5)]) == \
        [(0, 1), (5, 6)]


def test_trace_reduction_on_hand_made_lines():
    dev = "/device:TPU:0"
    lines = [
        {"plane": dev, "line": "XLA Ops", "events": [
            ("fusion.1", 0.0, 1.0), ("all-gather.2", 1.0, 1.0),
            ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-gather.2), "
             "kind=kLoop", 1.5, 1.0), ("fusion.1", 4.0, 1.0)]},
        {"plane": dev, "line": "XLA Modules", "events": [
            ("jit_run(1)", 0.0, 2.5), ("jit_run(1)", 4.0, 1.0),
            ("jit_other(2)", 2.6, 0.1)]},
        {"plane": "/host:CPU", "line": "python", "events": [
            ("$engine.py:1 _decode_once", 2.4, 1.7),
            ("$threading.py wait", 2.0, 10.0)]},
    ]
    r = trace_reduce.reduce(lines)
    assert r["devices"] == 1 and r["window_s"] == 5.0
    assert r["busy_s"] == pytest.approx(3.5)
    assert r["op_seconds"]["fusion.1"] == 2.0 and r["op_counts"]["fusion.1"] == 2
    # an op that only CONSUMES a collective's result is compute
    assert r["collective_s"] == 1.0
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["module_durations"]["jit_run(1)"] == [2.5, 1.0]
    assert r["breakdown"]["idle_gaps"][0] == \
        ["$engine.py:1 _decode_once", pytest.approx(1.5)]
    with pytest.raises(ValueError, match="nothing ran on the device"):
        trace_reduce.reduce(lines[2:])


def test_flops_and_bytes_match_hand_counts():
    bc = manifest.load_json(manifest.config_file(manifest.manifest(),
                                                 "baichuan-7b"))
    ds = manifest.load_json(manifest.config_file(manifest.manifest(),
                                                 "deepseek-llm-7b"))
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008            # 202 375 168
    assert flops.layer_params(bc) == flops.layer_params(ds) == layer == 202375168
    assert flops.matrix_params(bc) == 16 * layer + 4096 * 64000
    # DeepSeek, S=2048: per layer 2*layer + 4*2048*4096; head 2*4096*102400
    fwd = 8 * (2 * layer + 4 * 2048 * 4096) + 2 * 4096 * 102400
    assert flops.fwd_flops_per_token(ds, 2048) == fwd
    assert flops.train_flops_per_token(ds, 2048) == 3 * fwd
    assert 12.9e9 < 3 * fwd < 13.2e9
    # K/V: 2 tensors x 16 layers x 4096 x 2 bytes = 256 KiB a token
    assert flops.kv_bytes_per_token(bc) == 262144
    assert flops.decode_step_bytes(bc, 1000) == \
        2 * (16 * layer + 4096 * 64000) + 1000 * 262144
    # flash fwd, B=4 H=32 S=2048 D=128: 2 matmuls of 2*S*S*D, causal half
    f, b = flops.flash_kernel_cost("fwd", 4, 32, 2048, 128)
    assert f == 2 * 2 * 4 * 32 * 2048 * 2048 * 128 / 2
    assert b == 4 * (4 * 32 * 2048 * 128 * 2) + 4 * 32 * 2048 * 4
    assert flops.flash_kernel_cost("dkv", 4, 32, 2048, 128)[0] == 2 * f
    t, which = flops.least_seconds(f, b, peaks.peaks("TPU v5 lite"))
    assert which == "compute" and t == pytest.approx(f / 197e12)


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_a_data_file_has_no_defaults_in_code():
    """A key a data file lacks, a mix of an unknown kind or with an
    unknown arrival process: an error that names it, never a default."""
    cell = manifest.cell("baichuan7b-chat-steady")
    with pytest.raises(manifest.ManifestError,
                       match="baichuan7b-chat-steady.json has no 'nope'"):
        cell["nope"]
    with pytest.raises(manifest.ManifestError, match="resume"):
        manifest.cell_runner("resume")
    for kind in ("serve_open_loop", "train_stream"):
        assert callable(manifest.cell_runner(kind))
    with pytest.raises(ValueError, match="gamma"):
        traffic.serve_requests(dict(CHAT, arrivals={"process": "gamma"}),
                               64000, 3.0, 20, seed=1)


def test_manifest_meets_the_contract_limits():
    man = manifest.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in man["end_to_end"])
    e2e = {m["name"] for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for w in man["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        manifest.cell(w["name"]), manifest.traffic(w["traffic"])
        cfg = manifest.load_json(manifest.config_file(man, w["config"]))
        assert set(cfg["reduced"]) == set(next(
            c for c in man["configs"] if c["name"] == w["config"])["reduced"])
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in man["per_layer"]:
        spec = manifest.layer_metric(m["name"])
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for key in ("name", "unit", "layer", "moves", "source", "better"):
            assert spec[key] == m[key], (m["name"], key)
        manifest.reader(spec["reader"])
    layers = {m["layer"] for m in man["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)


def test_published_widths_are_unchanged():
    man = manifest.manifest()
    for name, vocab, layers in (("baichuan-7b", 64000, 32),
                                ("deepseek-llm-7b", 102400, 30)):
        cfg = manifest.load_json(manifest.config_file(man, name))
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["intermediate_size"], cfg["vocab_size"],
                cfg["rms_norm_eps"]) == (4096, 32, 11008, vocab, 1e-6)
        assert cfg["reduced"]["num_hidden_layers"]["published"] == layers
        assert cfg["num_hidden_layers"] == \
            cfg["reduced"]["num_hidden_layers"]["here"]


def test_trace_reduction_on_the_recorded_trace():
    """benchmark/tests/recorded_trace.json: 80 ms of the serving cell's
    trace on the v5e (my chip run, PR 24) round the end of one decode
    chunk, the idle gap behind it and the prefill that follows, every
    event that touches the slice kept whole, as ``trace_reduce.load``
    returns them with the long HLO names cut to name, shape, opcode.
    Expected values by an independent sweep over the end points."""
    import json

    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        lines = [dict(l, events=[tuple(e) for e in l["events"]])
                 for l in json.load(f)]
    ops = next(l for l in lines if l["line"] == "XLA Ops")["events"]
    depth, busy, last = 0, 0.0, None
    for t, k in sorted([(s, 1) for _, s, _ in ops]
                       + [(s + d, -1) for _, s, d in ops]):
        busy += (t - last) if depth > 0 else 0.0
        depth, last = depth + k, t
    r = trace_reduce.reduce(lines)
    assert r["devices"] == 1 and r["collective_s"] == 0
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.607219773, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.634850697, rel=1e-6)
    assert r["module_durations"] == {
        "jit_run(14452127497307884503)": [pytest.approx(0.578422617)],
        "jit_run(11522457608404312487)": [pytest.approx(0.03366437)]}
    convert = [k for k in r["op_seconds"] if k.startswith("%convert.54 ")][0]
    assert r["op_counts"][convert] == 3
    assert r["op_seconds"][convert] == pytest.approx(0.002916107)
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert not any(" while" in n for n in names) and len(names) == 10
    owner, gap = r["breakdown"]["idle_gaps"][0]
    assert gap == pytest.approx(0.027630518) and owner.startswith("$")


def test_a_trace_can_be_read_back_from_its_file(tmp_path):
    """``load`` on a trace this test records itself (the CPU backend:
    host planes only, which is why reducing it must refuse)."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jax.jit(lambda x: x @ x)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    lines = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert lines and all(l["plane"].startswith("/host:") or
                         not l["events"] or l["plane"] for l in lines)
    with pytest.raises(ValueError, match="nothing ran on the device"):
        trace_reduce.reduce(lines)
    stand_in = trace_reduce.host_as_device(lines)
    assert trace_reduce.reduce(stand_in)["busy_s"] > 0
