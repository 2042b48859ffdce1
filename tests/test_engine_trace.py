"""What the decode engine writes into a profiler trace and onto
/metrics about its own loop: programs under kfx's names, the loop
thread's ``engine.iteration`` with its phases, the host-seconds
counters, and the named scopes inside the compiled decode program. On
the CPU backend: the names are the same on a chip, the times are not
(nothing here asserts a speed)."""

import glob
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

PHASES = ("engine.control", "engine.admit", "engine.prefill.enqueue",
          "engine.decode.enqueue", "engine.device_wait", "engine.deliver",
          "engine.bookkeeping")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine (float32, 2 layers, 4 slots) serving a few
    requests under ``jax.profiler.start_trace``: the engine, the trace's
    host lines as {line name: [(event name, start, end, stats)]}, the
    registry's counters before and after, and the run's wall time."""
    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from kubeflow_tpu.obs.metrics import MetricsRegistry
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            head_dim=16, n_layers=2, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    reg = MetricsRegistry()
    eng = DecodeEngine(cfg, params, n_slots=4, chunk_tokens=4,
                       name="lm-trace", kv_page_size=16,
                       prefill_chunk_tokens=16, registry=reg)
    out = tmp_path_factory.mktemp("trace")
    try:
        eng.warm([8, 16])
        eng.generate([[3, 4, 5]], max_new_tokens=4)   # compiled, warm
        before = _counters(reg)
        t0 = time.perf_counter()
        jax.profiler.start_trace(str(out))
        try:
            eng.generate([[5, 9, 11, 3, 7],
                          [(i * 5 + 1) % 60 + 2 for i in range(40)]],
                         max_new_tokens=12)
        finally:
            jax.profiler.stop_trace()
        wall = time.perf_counter() - t0
        after = _counters(reg)
        lines = _host_lines(out)
        yield {"engine": eng, "lines": lines, "before": before,
               "after": after, "wall": wall}
    finally:
        eng.close()


def _counters(reg):
    out = {}
    for name in ("kfx_lm_engine_host_seconds_total",
                 "kfx_lm_engine_device_wait_seconds_total",
                 "kfx_lm_engine_iterations_total",
                 "kfx_lm_engine_chunks_total"):
        out[name] = {tuple(sorted(lab.items())): v
                     for lab, v in reg.counter(name, "").samples()}
    return out


def _host_lines(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = {}
    warnings.simplefilter("ignore", DeprecationWarning)  # e.stats' type
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events)
    return lines


def _grew(traced, name):
    return sum(traced["after"][name].values()) \
        - sum(traced["before"][name].values())


def test_the_loop_thread_shows_iterations_with_their_phases(traced):
    holders = [n for n, ev in traced["lines"].items()
               if any(e[0] == "engine.iteration" for e in ev)]
    assert len(holders) == 1, holders       # one thread: the loop's
    events = traced["lines"][holders[0]]
    iterations = [e for e in events if e[0] == "engine.iteration"]
    assert len(iterations) >= 3
    numbers = [e[3]["iteration"] for e in iterations]
    assert numbers == sorted(numbers) and len(set(numbers)) == len(numbers)
    assert all({"active", "prefilling"} <= set(e[3]) for e in iterations)
    seen = set()
    first = min(a for _, a, _, _ in iterations)
    last = max(b for _, _, b, _ in iterations)
    for name, start, end, _ in events:
        # (an iteration under way when the trace started or stopped
        # left phases in the trace and not itself; times are floats of
        # nanoseconds since the epoch, good to a microsecond)
        if name in PHASES and first <= start <= last:
            seen.add(name)
            assert any(a - 1e3 <= start and end <= b + 1e3
                       for _, a, b, _ in iterations), \
                f"{name} lies outside every engine.iteration"
    assert seen == set(PHASES)
    # The spans the engine already had ride the same bridge.
    assert {"engine.chunk", "engine.prefill_chunk", "engine.admit"} <= \
        {e[0] for e in events}
    # No other thread carries a phase of the loop.
    for line, ev in traced["lines"].items():
        if line != holders[0]:
            assert not {e[0] for e in ev} & set(PHASES), line


def test_a_chunks_hand_out_lies_between_the_next_enqueue_and_its_wait(
        traced):
    """engine.deliver of chunk n runs behind chunk n + 1's enqueue and
    before the loop blocks on that chunk: the trace has iterations
    with the three phases in that order."""
    events = next(ev for ev in traced["lines"].values()
                  if any(e[0] == "engine.iteration" for e in ev))
    inside = lambda it, name: sorted(
        (s, e) for n, s, e, _ in events
        if n == name and it[1] - 1e3 <= s and e <= it[2] + 1e3)
    overlapped = 0
    for it in (e for e in events if e[0] == "engine.iteration"):
        enq, out, wait = (inside(it, "engine." + n) for n in (
            "decode.enqueue", "deliver", "device_wait"))
        if not (enq and out and wait):
            continue
        if enq[0][1] <= out[0][0] and out[0][1] <= wait[-1][0]:
            overlapped += 1
    assert overlapped >= 2


@pytest.mark.parametrize("program", ["kfx_decode_chunk", "kfx_prefill_8",
                                     "kfx_prefill_16"])
def test_programs_carry_kfx_names_in_the_trace(traced, program):
    """On the CPU a program's run is a ``PjitFunction(jit(<name>))``
    host event; on a chip the same name is the XLA module's."""
    names = {e[0] for ev in traced["lines"].values() for e in ev
             if e[0].startswith("PjitFunction(")}
    assert any(program + ")" in n for n in names), sorted(names)
    assert "PjitFunction(jit(run))" not in names


def test_host_and_wait_seconds_are_counted_once_an_iteration(traced):
    chunks = _grew(traced, "kfx_lm_engine_chunks_total")
    assert chunks >= 3
    assert _grew(traced, "kfx_lm_engine_iterations_total") >= chunks
    host = _grew(traced, "kfx_lm_engine_host_seconds_total")
    wait = _grew(traced, "kfx_lm_engine_device_wait_seconds_total")
    assert host > 0 and wait > 0
    assert host + wait <= traced["wall"]
    phases = {dict(k)["phase"] for k in
              traced["after"]["kfx_lm_engine_host_seconds_total"]}
    assert phases == {p[len("engine."):] for p in PHASES
                      if p != "engine.device_wait"} | {"other"}


def test_parked_time_is_in_no_counter(traced):
    """An idle engine parks on its condition variable: neither counter
    moves, whatever the wall clock does."""
    reg_before = _counters(traced["engine"]._reg())
    time.sleep(0.3)
    reg_after = _counters(traced["engine"]._reg())
    assert reg_before == reg_after


def _scoped(text, scope):
    return any(scope in line for line in text.splitlines()
               if "op_name=" in line)


@pytest.mark.parametrize("scope", [
    "/attn/", "/mlp/", "/lm_head/", "/kv_write/", "/kv_member/",
    "/scores/", "/pv/", "/sample/cond/"])
def test_the_decode_program_names_its_parts(traced, scope):
    """``op_name`` metadata of the compiled decode chunk: the module
    scopes flax gives (attn, mlp, lm_head) and the named scopes inside
    the decode attention and the sampler (whose scope stands outside
    the ``vmap``: the step picks the sampler's form once for the batch).
    The engine's default pool is its slots' logical view (4 x 64 = 16
    x 16), so the chunk attends the pool in place: ``kv_member``, and
    no gather."""
    text = traced["engine"]._decode().as_text()
    assert "jit(run_kfx_decode_chunk)" in text
    assert _scoped(text, scope)
    assert not _scoped(text, "/kv_gather/")


@pytest.mark.parametrize("scope, there", [
    ("/kv_gather/", True), ("/scores/", True), ("/pv/", True),
    ("/kv_member/", False)])
def test_a_prefill_program_still_gathers(traced, scope, there):
    """One row's logical view (64) is smaller than the pool (256): a
    prefill program gathers through its block table as it always did."""
    text = traced["engine"]._prefill_for(16).as_text()
    assert "jit(run_kfx_prefill_16)" in text
    assert _scoped(text, scope) is there


@pytest.mark.parametrize("program, positions", [
    ("decode_chunk", 16 * 16), ("prefill_8", 64), ("prefill_16", 64)])
def test_attend_positions_says_which_form_a_program_took(traced, program,
                                                         positions):
    """``kfx_lm_attend_positions{model,program}``: the pool's slots
    (pages x page size) where the program attends in place, the row's
    ``max_seq_len`` where it gathers."""
    gauge = traced["engine"]._reg().gauge("kfx_lm_attend_positions", "")
    assert gauge.value(model="lm-trace", program=program) == positions
