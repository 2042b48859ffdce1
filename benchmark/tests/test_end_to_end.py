"""Whole runs of throw-away tiny cells on the CPU, through the plane,
the operators, the gang, the custom container and the reference: the
harness's look for a chip skipped, everything else as on the chip.

The tiny cells are added to a COPY of the benchmark as new files and
new manifest entries only (tests/tiny.py asserts that no file that was
there changed): what a later model_config or perf_opt PR has to be able
to do.
"""

import os
import subprocess
import sys

import pytest

from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_new_files_are_found_by_name(root):
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark import manifest as m\n"
            "man = m.manifest(%r)\n"
            "assert m.workload(man, 'tiny-chat')['config'] == 'tiny'\n"
            "assert m.cell('tiny-chat')['rate_rps'] == 3.0\n"
            "assert m.traffic('tiny-train')['kind'] == 'train_stream'\n"
            "assert m.config_file(man, 'tiny', %r).endswith('tiny.json')\n"
            "spec = m.layer_metric('finished_requests')\n"
            "assert m.reader(spec['reader'])({'rows': [{'ok': 1}]}, {}) == 1\n"
            "assert 'finished_requests' in [x['name'] for x in "
            "m.metrics_for(man, 'per_layer', 'tiny-chat')]\n"
            "assert 'finished_requests' not in [x['name'] for x in "
            "m.metrics_for(man, 'per_layer', 'baichuan7b-chat-steady')]\n"
            % (root, root, root))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_serving_cell_end_to_end(root):
    res, out = tiny.run_cell(root, "tiny-chat", seconds=4)
    assert res["correct"] is True and res["failed"] == 0, out[-3000:]
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "out_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    # the numbers compared: in the log, as the line's last key, and a
    # sound run's line has no other key than these
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert set(res["compared"]) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "compilations_in_window", "generator_late_p99_ms"}
    assert all(c["value"] <= c["limit"] for c in res["compared"].values())
    assert "compared served_logit_gap_max" in out
    assert "traffic {" in out and "generator_late_p99_ms" in out


def test_serving_cell_traced_reads_every_layer_metric(root):
    res, out = tiny.run_cell(root, "tiny-chat", seconds=4, trace=1)
    assert res["correct"] is True, out[-3000:]
    # (prefill_program_ms names the real cell's 256-token program,
    # which the tiny cell never runs; prefix_reuse_pct finds nothing to
    # read with the tiny cell's prefix cache off)
    assert set(res["metrics"]) >= {
        "plane_overhead_ms", "queue_wait_p95_ms",
        "ttft_p50_ms", "tpot_p50_ms",
        "tokens_per_dispatch", "decode_program_ms",
        "decode_hbm_pct", "device_idle_pct.serve", "finished_requests"}
    assert res["metrics"]["finished_requests"]["value"] == 12
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert list(res)[-2:] == ["breakdown", "compared"]


def test_serving_control_int8_kv_is_not_correct(root):
    sound, out = tiny.run_cell(root, "tiny-long", seconds=8)
    assert sound["correct"] is True, out[-3000:]
    res, out = tiny.run_cell(root, "tiny-long", seconds=8, control="int8kv")
    assert res["correct"] is False, out[-3000:]
    assert "OVER" in out
    # the line itself says which number failed, beside its limit
    over = {n for n, c in res["compared"].items() if c["value"] > c["limit"]}
    assert over and over <= {"served_logit_gap_mean", "served_logit_gap_max"}
    assert list(res)[-1] == "compared"


def test_a_late_sender_is_not_correct_and_the_line_says_so(root):
    """The guard on the harness's own sender: against a limit of
    nought every sender is late. The outputs are sound, ``correct`` is
    false all the same, and the line names the number that failed."""
    res, out = tiny.run_cell(root, "tiny-late", seconds=4)
    over = {n for n, c in res["compared"].items() if c["value"] > c["limit"]}
    assert res["correct"] is False and res["failed"] == 0, out[-3000:]
    assert over == {"generator_late_p99_ms"}
    assert res["compared"]["generator_late_p99_ms"]["limit"] == 0


def test_serving_token_altered_where_it_is_produced_is_not_correct(root):
    res, out = tiny.run_cell(root, "tiny-broken", seconds=8, trace=1)
    assert res["correct"] is False, out[-3000:]
    assert res["failed"] == 0   # every request finished; the tokens are wrong


def test_training_cell_end_to_end_on_four_devices(root):
    res, out = tiny.run_cell(root, "tiny-train", seconds=3, devices=4)
    assert res["correct"] is True and res["attempted"] > 3, out[-3000:]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert "plan=dp4/tp1/fsdp" in out and res["device"]["count"] == 4
    res, out = tiny.run_cell(root, "tiny-train", seconds=3, devices=4,
                             trace=1)
    assert {"train_step_ms", "train_mfu_pct",
            "device_idle_pct.train"} <= set(res["metrics"]), out[-3000:]


@pytest.mark.parametrize("control", ["bf16", "stuck"])
def test_training_controls_are_not_correct(root, control):
    """bf16: parameters in the precision below the one stated. stuck:
    the timed path broken underneath, a step that returns its state
    unchanged."""
    res, out = tiny.run_cell(root, "tiny-train", seconds=2, devices=4,
                             control=control)
    assert res["correct"] is False, out[-3000:]
    assert "param_change_norm_gap" in out and "OVER" in out
    gap = res["compared"]["param_change_norm_gap"]
    assert gap["value"] > gap["limit"]


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "baichuan7b-chat-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=tiny.REPO)
    assert p.returncode != 0
    assert "no accelerator" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
