"""Whole runs of a throw-away tiny cell of the ``serve_hybrid`` kind on
the CPU (float32): the tiny ``granitemoehybrid`` configuration of
``tiny_granite.py`` through the plane, the operator, the replica, the
open-loop window and the check, with its two controls. Added to the copy
of the benchmark that ``tiny.make_root`` makes, as new files and new
manifest entries only. A red case here means the chip run would read
``correct`` false."""

import json
import os

import pytest

from benchmark.tests import tiny, tiny_granite

CELL, MANY = "tiny-hybrid", "tiny-hybrid-many"
NEW_METRICS = ("hybrid_decode_program_ms", "hybrid_prefill_program_ms",
               "ssm_rows_per_step", "ssm_decode_hbm_pct",
               "hybrid_serve_mfu_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(root, "benchmark")

    def put(rel, obj):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            json.dump(obj, f)

    cfg = tiny_granite.config(vocab_size=512)
    cfg["serving"].update(
        slots=4, max_seq_len=128, kv_page_size=8, decode_chunk=4,
        prefill_chunk=16, speculative={"enabled": False},
        prefix_cache=False)
    # A sound float32 run's logits lie 3e-6 from the reference's and a
    # bfloat16 state's 4e-3 (logits' std 0.42): the first serves the
    # reference's own choice but where two logits all but tie, the
    # second now and then another, some 1e-3 below.
    cfg["correct"] = {"served_logit_gap_max": 2e-5,
                      "served_logit_gap_mean": 1e-6,
                      "state_gap_max": 1e-4, "state_gap_mean": 1e-5}
    put("configs/tiny-granite.json", cfg)
    mix = {
        "kind": "serve_hybrid",
        "arrivals": {"process": "exponential_quantiles"},
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                          "min": 8, "max": 80},
        "output_tokens": {"dist": "uniform", "min": 6, "max": 16},
        "temperature": 0.0, "shared_prefix": None, "schedule_seed": 1}
    cell = {
        "rate_rps": 3.0, "grace_s": 60.0, "check_requests": 5,
        "check_reused_slots": 2, "check_states": 2, "check_pad_to": 16,
        "warm_new_tokens": 4, "trace_after_s": 0.5, "trace_seconds": 1.0,
        "late_share_limit": 20.0, "late_floor_ms": 250.0, "serving": {},
        "traced_replica": "benchmark.workers.traced_replica_scraped",
        "export_writer": "benchmark.workers.export_writer_granitemoehybrid",
        "check": "benchmark.check_serve_granitemoehybrid"}
    put("traffic/tiny-hybrid.json", mix)
    put(f"cells/{CELL}.json", cell)
    # For the controls: more served positions, every request checked (a
    # lower precision shows where it flips the largest logit).
    put("traffic/tiny-hybrid-many.json", dict(
        mix, output_tokens={"dist": "uniform", "min": 16, "max": 28}))
    put(f"cells/{MANY}.json", dict(cell, check_requests=24))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "tiny-granite", "source": "throw-away",
                           "file": "benchmark/configs/tiny-granite.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [{"name": name, "config": "tiny-granite",
                          "traffic": name, "chips": 1, "why": "test"}
                         for name in (CELL, MANY)]
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in NEW_METRICS + ("out_tokens_per_s",
                                       "device_idle_pct.serve"):
            m["workloads"] = m["workloads"] + [CELL, MANY]
    with open(path, "w") as f:
        json.dump(man, f)
    return root


def over(res):
    return {n for n, c in res["compared"].items() if c["value"] > c["limit"]}


def test_hybrid_cell_end_to_end(root):
    res, out = tiny.run_cell(root, CELL, seconds=4)
    assert res["correct"] is True and res["failed"] == 0, out[-3000:]
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert set(res["compared"]) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "state_gap_max", "state_gap_mean", "state_probes_short",
        "reused_slots_short", "compilations_in_window",
        "generator_late_p99_ms"}
    assert "span reference" in out and "span export.write" in out
    # two slots' states were read back from the replica and compared
    assert "states probed=2" in out and "span states" in out
    # slots were taken again, and the program said so
    assert "state_resets_total=" in out and "state_resets_total=0 " not in out
    # weights, pages and the slots' state
    assert res["device"]["memory_peak_bytes"] > 0


def test_hybrid_cell_traced_reads_its_layer_metrics(root):
    res, out = tiny.run_cell(root, CELL, seconds=4, trace=1)
    assert res["correct"] is True, out[-3000:]
    # (hybrid_prefill_program_ms names the real cell's 256-token
    # program, which the tiny cell never runs)
    assert set(res["metrics"]) >= {
        "hybrid_decode_program_ms", "ssm_rows_per_step",
        "ssm_decode_hbm_pct", "hybrid_serve_mfu_pct",
        "device_idle_pct.serve"}, out[-3000:]
    value = lambda n: res["metrics"][n]["value"]
    assert 0 < value("hybrid_serve_mfu_pct") < 100
    assert 0 < value("ssm_decode_hbm_pct") < 100
    # the traced second's own steps: no more rows than slots
    assert 0 < value("ssm_rows_per_step") <= 4


@pytest.mark.parametrize("control", ["bf16state", "int8kv"])
def test_hybrid_controls_are_not_correct(root, control):
    """bf16state: the slots' recurrent state in bfloat16 (the export's
    configuration carries the type, the engine reads it). int8kv: the
    K/V pool in int8. Each is a lower precision than the configuration
    states, and at this size, in float32, each fails the comparison.
    (At the published size the state's own comparison is what tells a
    bfloat16 state from a sound run: PERF.md section 2.)"""
    res, out = tiny.run_cell(root, MANY, seconds=8, control=control)
    assert res["correct"] is False and res["failed"] == 0, out[-3000:]
    assert over(res) & {"served_logit_gap_max", "served_logit_gap_mean"}, \
        res["compared"]
    # the state's own precision shows in the state a slot holds
    assert control == "int8kv" \
        or {"state_gap_max", "state_gap_mean"} <= over(res), res["compared"]


def test_the_configuration_file_states_the_catalog_row():
    """Every number of the catalog's ``config`` stands in
    ``configs/granite-4.0-h-micro.json`` under its key; nothing is
    reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    with open(os.path.join(tiny.BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == {}
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]


def test_the_cost_functions_count_the_published_model():
    """benchmark/flops_granitemoehybrid.py at the published sizes: 3.19
    G parameters, 75.5 MB of float32 state a row, and a decode step of
    64 rows whose state traffic is most of its bytes."""
    from benchmark import flops_granitemoehybrid as F

    with open(os.path.join(tiny.BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    assert F.layers(cfg) == (36, 4)
    held = F.every_token_params(cfg) + 2048 * 100352
    assert round(held / 1e9, 2) == 3.19
    assert 36 * 4 * F.state_numbers(cfg) == 75_497_472
    step = F.decode_step_bytes(cfg, 64, 64 * 512)
    state = 2 * 64 * 36 * 4 * F.state_numbers(cfg)
    assert 0.55 < state / step < 0.65
    # a token's FLOPs: the matrices, the head apart
    assert round(F.window_flops(cfg, 1, 0, 0) / 1e9, 1) == 6.1
