"""Whole runs of a throw-away tiny cell of the ``serve_longctx`` kind on
the CPU (float32): the tiny ``glm_moe_dsa`` configuration of
``tiny_glm.py`` through the plane, the operator, the replica, the
open-loop window and the split check, with both controls. Added to the
copy of the benchmark that ``tiny.make_root`` makes, as new files and
new manifest entries only. A red case here means the chip run would
read ``correct`` false."""

import json
import os

import numpy as np
import pytest

from benchmark.tests import tiny, tiny_glm

CELL, MANY = "tiny-longctx", "tiny-longctx-many"
NEW_METRICS = ("sparse_attend_pct", "moe_rows_per_expert",
               "moe_load_max_over_mean", "longctx_decode_program_ms",
               "longctx_prefill_program_ms", "latent_decode_hbm_pct",
               "serve_mfu_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(root, "benchmark")

    def put(rel, obj):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            json.dump(obj, f)

    # index_topk 48 of contexts 8-120 and a vocabulary of 512 (closer
    # logits): both parts of the comparison get
    # positions to judge.
    put("configs/tiny-glm.json", dict(
        tiny_glm.config(index_topk=48, vocab_size=512),
        serving={"dtype": "float32", "param_dtype": "float32", "slots": 4,
                 "max_seq_len": 128, "kv_page_size": 8, "decode_chunk": 4,
                 "prefill_chunk": 16, "speculative": {"enabled": False},
                 "prefix_cache": False},
        # sound float32 runs read at most 2e-6; the controls 3e-4 and up
        correct={"served_logit_gap_max.full": 1e-4,
                 "served_logit_gap_mean.full": 1e-5,
                 "served_logit_gap_max.sparse": 1e-4,
                 "served_logit_gap_mean.sparse": 1e-5}))
    mix = {
        "kind": "serve_longctx",
        "arrivals": {"process": "exponential_quantiles"},
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                          "min": 8, "max": 96},
        "output_tokens": {"dist": "uniform", "min": 6, "max": 12},
        "temperature": 0.0, "shared_prefix": None, "schedule_seed": 1}
    cell = {
        "rate_rps": 3.0, "grace_s": 60.0, "check_requests": 8,
        "full_probes": {"count": 4, "prompt_min": 8, "prompt_max": 30,
                        "new_tokens": 12}, "full_positions_min": 48,
        "warm_new_tokens": 4, "trace_after_s": 0.5, "trace_seconds": 1.0,
        "late_share_limit": 20.0, "late_floor_ms": 250.0, "serving": {},
        "traced_replica": "benchmark.workers.traced_replica",
        "export_writer": "benchmark.workers.export_writer_glm_moe_dsa",
        "check": "benchmark.check_serve_glm_moe_dsa"}
    put("traffic/tiny-longctx.json", mix)
    put(f"cells/{CELL}.json", cell)
    # For the controls: more served positions, every request checked (a
    # lower precision shows where it flips the largest logit).
    put("traffic/tiny-longctx-many.json", dict(
        mix, output_tokens={"dist": "uniform", "min": 14, "max": 24}))
    put(f"cells/{MANY}.json", dict(cell, check_requests=24))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "tiny-glm", "source": "throw-away",
                           "file": "benchmark/configs/tiny-glm.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [{"name": name, "config": "tiny-glm",
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


def test_longctx_cell_end_to_end(root):
    res, out = tiny.run_cell(root, CELL, seconds=4)
    assert res["correct"] is True and res["failed"] == 0, out[-3000:]
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert set(res["compared"]) == {
        "served_logit_gap_max.full", "served_logit_gap_mean.full",
        "served_logit_gap_max.sparse", "served_logit_gap_mean.sparse",
        "full_positions_short", "compilations_in_window",
        "generator_late_p99_ms"}
    # both kinds of position were judged
    assert "full=0 " not in out and "sparse=0 " not in out
    assert "span reference" in out and "span export.write" in out


def test_longctx_cell_traced_reads_its_layer_metrics(root):
    res, out = tiny.run_cell(root, CELL, seconds=4, trace=1)
    assert res["correct"] is True, out[-3000:]
    # (longctx_prefill_program_ms names the real cell's 1024-token
    # program, which the tiny cell never runs)
    assert set(res["metrics"]) >= {
        "sparse_attend_pct", "moe_rows_per_expert",
        "moe_load_max_over_mean", "longctx_decode_program_ms",
        "latent_decode_hbm_pct", "serve_mfu_pct",
        "device_idle_pct.serve"}, out[-3000:]
    value = lambda n: res["metrics"][n]["value"]
    # the main attention scores the whole view: no less than is cached
    assert value("sparse_attend_pct") >= 100
    assert value("moe_load_max_over_mean") >= 1
    assert 0 < value("serve_mfu_pct") < 100
    assert 0 < value("latent_decode_hbm_pct") < 100


@pytest.mark.parametrize("control, part", [("recent", "sparse"),
                                           ("int8kv", "full")])
def test_longctx_controls_are_not_correct(root, control, part):
    """recent: the reference reads the most recent ``index_topk``
    positions in the place of the learned selection. int8kv: the latent
    pool in int8, the program's own lower-precision path. Each fails
    the part of the comparison that is there for it."""
    res, out = tiny.run_cell(root, MANY, seconds=8, control=control)
    assert res["correct"] is False and res["failed"] == 0, out[-3000:]
    assert any(n.endswith("." + part) for n in over(res)), res["compared"]
    if control == "recent":   # where all is selected, nothing differs
        assert not any(n.endswith(".full") for n in over(res))


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Query blocks and head groups (what lets a 25 000-token request
    fit) change no number."""
    from benchmark import reference_glm_moe_dsa as R

    cfg = tiny_glm.config()
    tokens = np.random.default_rng(0).integers(0, 128, size=64)
    whole = tiny_glm.reference_logits(cfg, 3, tokens)
    monkeypatch.setattr(R, "QUERY_BLOCK", 16)
    monkeypatch.setattr(R, "HEAD_GROUP", 2)
    np.testing.assert_allclose(tiny_glm.reference_logits(cfg, 3, tokens),
                               whole, atol=1e-6)


def test_a_lower_precision_indexer_moves_only_where_it_selects():
    """The reference's diagnostic (``index_dtype``): with the indexer's
    queries, keys and head weights rounded to bfloat16 and everything
    else float32, positions that predict from no more than
    ``index_topk`` tokens read the same logits to the last bit (all is
    selected, whatever the scores), and beyond it positions swap at
    the ``index_topk``-th score and the logits move."""
    import jax.numpy as jnp

    cfg = tiny_glm.config()                      # index_topk 16
    tokens = np.random.default_rng(1).integers(0, 128, size=96)
    whole = tiny_glm.reference_logits(cfg, 3, tokens)
    rounded = tiny_glm.reference_logits(cfg, 3, tokens,
                                        index_dtype=jnp.bfloat16)
    k = cfg["index_topk"]
    np.testing.assert_array_equal(rounded[:k], whole[:k])
    assert np.abs(rounded[k:] - whole[k:]).max() > 1e-4


def test_the_configuration_file_states_the_catalog_row():
    """Every number of the catalog's ``config`` stands in
    ``configs/glm-5.json`` under its key, but for the keys ``reduced``
    lists; no width is among those."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    with open(os.path.join(tiny.BENCH, "configs", "glm-5.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   and k != "vocab_size" for k in changed)
    for key, cut in cfg["reduced"].items():
        assert cut["published"] == row["config"][key]
        assert cut["here"] == cfg[key]
