"""Whole runs of a throw-away tiny cell of the ``serve_mixedctx`` kind on
the CPU (float32): the tiny ``smallthinker`` configuration of
``tiny_smallthinker.py`` through the plane, the operator, the replica,
the open-loop window and the check, with its three controls. Added to
the copy of the benchmark that ``tiny.make_root`` makes, as new files
and new manifest entries only. A red case here means the chip run would
read ``correct`` false."""

import json
import os

import pytest

from benchmark.tests import tiny, tiny_smallthinker

CELL, MANY = "tiny-mixedctx", "tiny-mixedctx-many"
NEW_METRICS = ("mixedattn_decode_program_ms", "mixedattn_prefill_program_ms",
               "window_view_per_attended", "window_pages_freed_per_s",
               "moe64_rows_per_expert", "moe64_load_max_over_mean",
               "mixedattn_serve_mfu_pct", "mixedattn_decode_hbm_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(root, "benchmark")

    def put(rel, obj):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            json.dump(obj, f)

    cfg = tiny_smallthinker.config(vocab_size=512)
    cfg["serving"] = dict(
        dtype="float32", param_dtype="float32", slots=4, max_seq_len=256,
        kv_page_size=8, decode_chunk=4, prefill_chunk=16,
        speculative={"enabled": False}, prefix_cache=False)
    # A sound float32 run's logits lie some 1e-6 from the reference's
    # (logits' std 0.33): it serves the reference's own choice but
    # where two logits all but tie. A window layer that saw everything,
    # a router that read ln2's output and an int8 pool are each some
    # 1e-2 away.
    cfg["correct"] = {f"served_logit_gap_{stat}.{part}": limit
                      for part in ("short", "long")
                      for stat, limit in (("max", 2e-5), ("mean", 1e-6))}
    # The cache itself: float32 pages hold the reference's keys and
    # values to 1e-7 of their norm, int8 ones to some 5e-3.
    cfg["correct"].update({"kv_gap_median.full": 1e-5,
                           "kv_gap_median.window": 1e-5})
    put("configs/tiny-smallthinker.json", cfg)
    mix = {
        "kind": "serve_mixedctx",
        "arrivals": {"process": "exponential_quantiles"},
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                          "min": 6, "max": 120},
        "output_tokens": {"dist": "uniform", "min": 6, "max": 24},
        "temperature": 0.0, "shared_prefix": None, "schedule_seed": 1}
    cell = {
        "rate_rps": 3.0, "grace_s": 60.0, "check_requests": 6,
        "check_tokens": 100, "check_reused": 1, "short_positions_min": 4,
        "long_positions_min": 10, "warm_new_tokens": 4,
        "trace_after_s": 0.5, "trace_seconds": 1.0,
        "late_share_limit": 20.0, "late_floor_ms": 250.0, "serving": {},
        "kv_probe": {"prompt_tokens": 40, "new_tokens": 200,
                     "layers": [0, 1], "positions_min": 40 + 16},
        "traced_replica": "benchmark.workers.traced_replica_scraped",
        "export_writer": "benchmark.workers.export_writer_smallthinker",
        "check": "benchmark.check_serve_smallthinker"}
    put("traffic/tiny-mixedctx.json", mix)
    put(f"cells/{CELL}.json", cell)
    # For the controls: every request checked (a lower precision shows
    # where it flips the largest logit).
    put("traffic/tiny-mixedctx-many.json", mix)
    put(f"cells/{MANY}.json", dict(cell, check_requests=24,
                                   check_tokens=200))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "tiny-smallthinker",
                           "source": "throw-away",
                           "file": "benchmark/configs/tiny-smallthinker.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [{"name": name, "config": "tiny-smallthinker",
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


def test_mixedctx_cell_end_to_end(root):
    res, out = tiny.run_cell(root, CELL, seconds=4)
    assert res["correct"] is True and res["failed"] == 0, out[-3000:]
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert set(res["compared"]) == {
        "served_logit_gap_max.short", "served_logit_gap_mean.short",
        "served_logit_gap_max.long", "served_logit_gap_mean.long",
        "short_positions_short", "long_positions_short", "reused_short",
        "kv_gap_median.full", "kv_gap_median.window", "kv_positions_short",
        "compilations_in_window", "generator_late_p99_ms"}
    assert "span reference" in out and "span export.write" in out
    # the probe's row was read while it decoded: a full layer's pages
    # held every position so far, a window layer's (window 16, pages
    # of 8) those of its last two or three pages
    assert "span kv_probe" in out
    # rows outgrew the window of 16: pages went back, and the program
    # said so; weights and both pools are in the device's floor
    assert "window_pages_freed_total=" in out \
        and "window_pages_freed_total=0 " not in out
    assert res["device"]["memory_peak_bytes"] > 0


def test_mixedctx_cell_traced_reads_its_layer_metrics(root):
    res, out = tiny.run_cell(root, CELL, seconds=4, trace=1)
    assert res["correct"] is True, out[-3000:]
    # (mixedattn_prefill_program_ms names the real cell's 1024-token
    # program, which the tiny cell never runs)
    assert set(res["metrics"]) >= set(NEW_METRICS) - {
        "mixedattn_prefill_program_ms"} | {"device_idle_pct.serve"}, \
        out[-3000:]
    value = lambda n: res["metrics"][n]["value"]
    assert 0 < value("mixedattn_serve_mfu_pct") < 100
    assert 0 < value("mixedattn_decode_hbm_pct") < 100
    # a decode step's view of a window layer is 3 blocks of 8 whatever
    # the row holds, of which it reads at most the window's 16
    assert value("window_view_per_attended") >= 24 / 16
    assert value("window_pages_freed_per_s") > 0
    # 8 experts, 3 a token: (the metric's scale is the real cell's 64)
    assert value("moe64_rows_per_expert") > 0
    assert value("moe64_load_max_over_mean") >= 8


@pytest.mark.parametrize("control, part", [
    ("fullwindow", "long"), ("laterouter", "short"), ("int8kv", "short")])
def test_mixedctx_controls_are_not_correct(root, control, part):
    """fullwindow: the reference's window layers see every earlier
    position, and only the positions beyond one window tell. laterouter:
    the reference routes from ln2's output. int8kv: both pools in int8.
    Each fails the comparison at this size, in float32."""
    res, out = tiny.run_cell(root, MANY, seconds=8, control=control)
    assert res["correct"] is False and res["failed"] == 0, out[-3000:]
    assert {f"served_logit_gap_max.{part}",
            f"served_logit_gap_mean.{part}"} <= over(res), res["compared"]
    # the cache itself tells a precision in both layers read back; a
    # router that reads elsewhere changes what enters the second of
    # them, a window that sees everything neither
    assert over(res) & {"kv_gap_median.full", "kv_gap_median.window"} == {
        "int8kv": {"kv_gap_median.full", "kv_gap_median.window"},
        "laterouter": {"kv_gap_median.window"},
        "fullwindow": set()}[control], res["compared"]
    if control == "fullwindow":     # within one window nothing differs
        assert not over(res) & {"served_logit_gap_max.short",
                                "served_logit_gap_mean.short"}


def test_the_configuration_file_states_the_catalog_row():
    """Every number of the catalog's ``config`` stands in
    ``configs/smallthinker-21b-a3b.json`` under its key, the two layouts
    whole; the depth alone is reduced."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    with open(os.path.join(tiny.BENCH, "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} \
        == {"num_hidden_layers"}
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"]["published"] \
        == row["config"]["num_hidden_layers"]
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "smallthinker-21b-a3b")
    assert entry["reduced"] == ["num_hidden_layers"] \
        and entry["source"] == row["source_url"]


def test_the_cost_functions_count_the_published_model():
    """benchmark/flops_smallthinker.py at the published sizes: 21.1 M
    parameters a layer outside the experts, 5.90 M an expert, 3.97 G
    held at eight layers, and a decode step of 32 rows whose routed
    product is most of its matrices' bytes."""
    from benchmark import flops_smallthinker as F
    from benchmark import kfx_adapter_smallthinker as A

    with open(os.path.join(tiny.BENCH, "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    assert F.layers(cfg) == (2, 6)
    assert A.runs(cfg) == [("full", 1), ("window", 3)] * 2
    assert round((F.attention_params(cfg) + F.router_params(cfg)) / 1e6, 1) \
        == 21.1
    assert round(F.expert_params(cfg) / 1e6, 2) == 5.90
    assert round(F.held_params(cfg) / 1e9, 2) == 3.97
    assert F.kv_bytes(cfg) == 2048
    # 61 of 64 experts a layer hit, 32 rows of 2000 positions
    step = F.decode_step_bytes(cfg, 8 * 61, 32 * 2000, 32 * 2000)
    experts = 8 * 61 * F.expert_params(cfg) * 2
    assert 0.65 < experts / step < 0.8
    # a token's FLOPs: attention, router and its six experts, 8 layers
    assert round(F.window_flops(cfg, 1, 0, 8 * 6, 0, 0) / 1e9, 2) == 0.90
