"""The main path's Pallas kernels compiled by the real TPU compiler for a
described (not attached) v5e, at the head shapes the presets train at.

Interpret mode (every other kernel test in this suite) cannot see what
Mosaic refuses: misaligned slices, too much VMEM, an unpartitionable
kernel. libtpu is installed here and compiles for a chip it is only told
about, so these cost seconds and no chip time. Kernel-only: whole-step
compiles stay in a builder's scratch (they take tens of seconds each).
Nothing runs, so nothing here is a result or a speed.
"""

import os
import subprocess
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# (batch, heads, seq, head_dim): the K/V block of every kernel is the
# WHOLE sequence and the lse block's last dimension is 1, so VMEM and
# tiling are decided by S and D.
SHAPES = {
    "base-S2048": (8, 16, 2048, 64),
    "large-S2048": (4, 16, 2048, 128),
    "large-S4096": (2, 16, 4096, 128),
}


def _compile(one_chip, shape, direction):
    """The flash kernels of one direction, compiled for the described
    chip: (executable, how many kernels it holds)."""
    from kubeflow_tpu.ops import flash_attention as fa

    B, H, S, D = shape
    bq = bk = fa._pick_block(S)
    x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    row = jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32, sharding=one_chip)
    if direction == "forward":
        fn = lambda q, k, v: fa._fwd(q, k, v, block_q=bq, block_k=bk)
        return jax.jit(fn).lower(x, x, x).compile(), 1
    # dq and dkv are two kernels of one backward
    fn = lambda q, k, v, o, lse, do: fa._bwd(
        bq, bk, (q, k, v, o, lse), do)
    return jax.jit(fn).lower(x, x, x, x, row, x).compile(), 2


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_kernels_compile_for_v5e(one_chip, shape, direction):
    compiled, kernels = _compile(one_chip, shape, direction)
    assert compiled.as_text().count("tpu_custom_call") == kernels


@pytest.mark.parametrize("kernel, direction", [
    ("kfx_flash_fwd", "forward"), ("kfx_flash_dq", "backward"),
    ("kfx_flash_dkv", "backward")])
def test_flash_kernels_carry_their_names_in_the_compiled_hlo(
        one_chip, kernel, direction):
    """What a profiler trace shows of a kernel is its instruction's
    text: the name given to ``pl.pallas_call`` is the instruction's own
    name and a scope of its ``op_name``, which is how the benchmark's
    reader finds each kernel (not by operand count)."""
    text = _compile(one_chip, SHAPES["large-S2048"], direction)[0].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and f"/{kernel}/" in line]
    assert len(calls) == 1, text[-3000:]
    assert calls[0].lstrip().startswith(f"%{kernel}")


def test_in_place_decode_attention_compiles_for_v5e(one_chip):
    """One layer-step of the paged decode attention at the serving
    cell's shapes (16 rows, 32 heads x 128, 288 pages of 32 under
    ``max_seq_len`` 1536: 16 x 1536 >= 288 x 32, so the pool is
    attended in place). The chip's compiler keeps it on the MXU (two
    ``convolution``s), widens no copy of a pool to float32 and builds
    no per-row view: its temporaries are the scores, not gigabytes."""
    import flax.linen as nn

    from kubeflow_tpu.models.transformer import (
        Attention, TransformerConfig, attends_pool_in_place, score_bytes)

    B, H, D, L, P, N = 16, 32, 128, 1536, 32, 288
    cfg = TransformerConfig(
        vocab_size=256, d_model=H * D, n_heads=H, head_dim=D, n_layers=1,
        d_ff=256, max_seq_len=L, dtype=jnp.bfloat16, decode=True,
        kv_page_size=P, kv_pages=N)
    assert attends_pool_in_place(B, L, N, P, score_bytes(cfg, 1))

    class Attend(Attention):
        @nn.compact
        def __call__(self, *args):
            return self._decode_attend(*args)

    def layer_step(cache, *args):
        out, vars_ = Attend(cfg).apply({"cache": cache}, *args,
                                       mutable=["cache"])
        return out, vars_["cache"]

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    pool = sds((1, N, P, H, D), jnp.bfloat16)      # a stack of one layer
    row = sds((B, 1, H, D), jnp.bfloat16)
    ids = sds((B, 1), jnp.int32)
    compiled = jax.jit(layer_step, donate_argnums=0).lower(
        {"cached_key": pool, "cached_value": pool,
         "cached_pos": sds((1, N, P), jnp.int32)},
        row, row, row, ids, sds((B, L // P), jnp.int32), ids).compile()
    text = compiled.as_text()
    assert text.count(" convolution(") == 2, text[-3000:]
    assert "kv_member" in text and "kv_gather" not in text
    for widened in ("f32[9216,32,128]", "f32[288,32,32,128]",
                    "[768,32,32,128]"):
        assert widened not in text, widened
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_256"])
def test_engine_programs_write_the_stacked_pool_in_place(one_chip,
                                                         monkeypatch,
                                                         program):
    """The engine's own decode chunk and 256-token prefill, built by
    ``DecodeEngine._build_decode`` / ``_build_prefill`` at the serving
    cell's pool (16 rows, 288 pages of 32, 32 heads x 128; 2 layers and
    a narrow ``d_ff``, which the pool's handling does not depend on).
    The layer scan carries the cache, so the compiler updates the
    stacked pools where they lie: it allocates no second stack, copies
    no stack, and writes no layer's pool back into one. (Scanned in and
    stacked out, the same programs held two ``AllocateBuffer``s and two
    whole-stack ``copy``s in the token loop: half the decode step.)"""
    import dataclasses
    import re

    from kubeflow_tpu.models.generate import decode_config
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, init_cache)
    from kubeflow_tpu.serving.engine import DecodeEngine

    layers, rows, L, P, N = 2, 16, 1536, 32, 288
    cfg = dataclasses.replace(decode_config(TransformerConfig(
        vocab_size=1024, d_model=4096, n_heads=32, head_dim=128,
        n_layers=layers, d_ff=256, max_seq_len=L, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)), kv_page_size=P, kv_pages=N)
    # The engine's builders on shapes alone: just what they read of an
    # engine, with no weights, no pool and no loop thread behind it.
    eng = object.__new__(DecodeEngine)
    eng.cfg, eng.model, eng.name = cfg, TransformerLM(cfg), "aot"
    eng.n_slots, eng.chunk_tokens, eng.n_blocks = rows, 8, L // P
    eng._donate, eng._apool, eng._registry = True, None, None
    eng.params = jax.eval_shape(
        lambda: TransformerLM(dataclasses.replace(cfg, decode=False)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    eng._cache = jax.eval_shape(lambda: init_cache(cfg))

    # ... and lowered for the described chip instead of this host's CPU.
    jit = jax.jit

    class ForTheChip:
        def __init__(self, fn, **kw):
            self.jitted = jit(fn, **kw)

        def lower(self, *specs):
            return self.jitted.lower(*jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one_chip), specs))

    monkeypatch.setattr(jax, "jit", ForTheChip)
    compiled = eng._build_decode() if program == "decode_chunk" \
        else eng._build_prefill(256)
    monkeypatch.undo()
    text = compiled.as_text()
    assert f"jit_run_kfx_{program}" in text.splitlines()[0]
    stack = re.escape(f"bf16[{layers},{N},{P},32,128]")
    made = [line.strip()[:200] for line in text.splitlines()
            if re.search(rf"= {stack}\S* (copy|copy-done|dynamic-update-slice|"
                         rf"custom-call)\(", line)]
    assert not made, made
    assert "AllocateBuffer" not in "".join(
        line for line in text.splitlines() if f"[{N},{P},32,128]" in line)
    # both pools are arguments the results alias, written by a scatter
    assert len(re.findall(rf"ROOT \S+ = {stack}\S* scatter\(", text)) == 2
    assert text.splitlines()[0].count("may-alias") >= 4
    if program == "prefill_256":
        # (the decode chunk keeps relayout copies of its q/k/v kernels)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_1024"])
def test_latent_pool_is_written_in_place_at_the_cells_size(one_chip,
                                                           monkeypatch,
                                                           program):
    """The engine's programs for ``benchmark/configs/glm-5.json`` as the
    cell serves it (16 rows, 6144 pages of 64, 32 768 positions; every
    width the published one, 1 dense + 5 expert layers): they compile
    for the chip, fit it, and update the latent pool where it lies. The
    ``cached_latent`` leaf is 640 wide, not 576: declared unpadded, the
    compiler copied the whole pool at the top of every dispatch (2.1 GiB,
    and 3.1 GiB of temporaries: PR 36). The routed experts' stacks reach
    the grouped product whole: no layer's slice of them is made."""
    import re

    from benchmark import kfx_adapter_glm_moe_dsa as A
    from benchmark.manifest import BENCH_DIR, load_json
    # (loaded before jax.jit is swapped below: it jits its selection as
    # it loads, and the model imports it only when a layer is traced)
    from kubeflow_tpu.models import latent  # noqa: F401
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, init_cache)
    from kubeflow_tpu.serving.engine import DecodeEngine

    published = load_json(os.path.join(BENCH_DIR, "configs", "glm-5.json"))
    serving = published["serving"]
    L, P, N = (serving["max_seq_len"], serving["kv_page_size"],
               serving["kv_pages"])
    cfg = TransformerConfig(**A.transformer_kwargs(
        published, max_seq_len=L, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, decode=True, kv_page_size=P, kv_pages=N))
    eng = object.__new__(DecodeEngine)
    eng.cfg, eng.model, eng.name = cfg, TransformerLM(cfg), "aot"
    eng.n_slots, eng.chunk_tokens, eng.n_blocks = serving["slots"], 8, L // P
    eng._donate, eng._apool, eng._registry = True, None, None
    tree, _ = A.host_views(published, jnp.bfloat16)   # shapes: never touched
    eng.params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    eng._cache = jax.eval_shape(lambda: init_cache(cfg))
    jit = jax.jit

    class ForTheChip:
        def __init__(self, fn, **kw):
            self.jitted = jit(fn, **kw)

        def lower(self, *specs):
            return self.jitted.lower(*jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one_chip), specs))

    monkeypatch.setattr(jax, "jit", ForTheChip)
    compiled = eng._build_decode() if program == "decode_chunk" \
        else eng._build_prefill(1024)
    monkeypatch.undo()
    text = compiled.as_text()
    assert f"jit_run_kfx_{program}" in text.splitlines()[0]
    pool = re.escape(f"[5,{N},{P},640]")
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= \w+{pool}\S* (copy|copy-done)\(", line)]
    assert not copies, copies
    experts = re.escape("bf16[16,6144,4096]")
    assert not re.search(rf"= {experts}\S* (fusion|copy|dynamic-slice)\(",
                         text)
    assert "ragged-dot" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 << 30
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < int(15.75 * 2 ** 30))


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_1024"])
def test_two_page_classes_fit_the_chip_at_the_cells_size(one_chip,
                                                         monkeypatch,
                                                         program):
    """The engine's programs for
    ``benchmark/configs/smallthinker-21b-a3b.json`` as the cell serves
    it (32 slots; the full layers' pool of 3072 pages of 64 and the
    window layers' of 32 x 82; every width the published one, two
    periods): they compile for the chip, fit it beside 7.9 GB of
    weights, update both pools where they lie through the four scans of
    the runs, and hand the routed experts' stacks (all eight layers')
    to the grouped product whole. A window layer's gathered view is 65
    blocks a decode step and 81 a prompt chunk of 1024, whatever
    ``max_seq_len``."""
    import re

    from benchmark import kfx_adapter_smallthinker as A
    from benchmark.manifest import BENCH_DIR, load_json
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, init_cache)
    from kubeflow_tpu.serving.engine import DecodeEngine

    published = load_json(os.path.join(BENCH_DIR, "configs",
                                       "smallthinker-21b-a3b.json"))
    serving = published["serving"]
    L, P, N = (serving["max_seq_len"], serving["kv_page_size"],
               serving["kv_pages"])
    W = serving["slots"] * (4096 // P + 2 + serving["prefill_chunk"] // P)
    cfg = TransformerConfig(**A.transformer_kwargs(
        published, max_seq_len=L, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, decode=True, kv_page_size=P, kv_pages=N,
        window_pages=W))
    eng = object.__new__(DecodeEngine)
    eng.cfg, eng.model, eng.name = cfg, TransformerLM(cfg), "aot"
    eng.n_slots, eng.chunk_tokens, eng.n_blocks = serving["slots"], 8, L // P
    eng._donate, eng._apool, eng._registry = True, None, None
    tree, _ = A.host_views(published, jnp.bfloat16)   # shapes: never touched
    eng.params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    eng._cache = jax.eval_shape(lambda: init_cache(cfg))
    jit = jax.jit

    class ForTheChip:
        def __init__(self, fn, **kw):
            self.jitted = jit(fn, **kw)

        def lower(self, *specs):
            return self.jitted.lower(*jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one_chip), specs))

    monkeypatch.setattr(jax, "jit", ForTheChip)
    compiled = eng._build_decode() if program == "decode_chunk" \
        else eng._build_prefill(1024)
    monkeypatch.undo()
    text = compiled.as_text()
    assert f"jit_run_kfx_{program}" in text.splitlines()[0]
    pools = "|".join(re.escape(f"bf16[{n},{pages},{P},512]")
                     for n, pages in ((1, N), (3, W)))
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= ({pools})\S* (copy|copy-done)\(", line)]
    assert not copies, copies
    experts = r"bf16\[(8,64|512),(2560,1536|768,2560)\]"
    assert not re.search(
        rf"= {experts}\S* (fusion|copy|copy-done|dynamic-slice)\(", text)
    assert "ragged-dot" in text
    # the window layers' view: 65 (a step) or 81 (a chunk) blocks of 64
    view = (1 + 4096 - 2) // P + 2 if program == "decode_chunk" \
        else (1024 + 4096 - 2) // P + 2
    assert re.search(rf"[\[,]({view * P}|{view},{P}),512[,\]]", text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 << 30
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < int(15.75 * 2 ** 30))


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_256"])
def test_slot_state_is_written_in_place_at_the_cells_size(one_chip,
                                                          monkeypatch,
                                                          program):
    """The engine's programs for
    ``benchmark/configs/granite-4.0-h-micro.json`` as the cell serves it
    (64 slots, 4096 pages of 32, every layer and width the published
    one): they compile for the chip, fit it beside 6.4 GB of weights,
    and update the slots' state and the grouped K/V pool where they
    lie, through all nine scans of the runs. What the first forms cost
    (AOT, PR 40): one scan over the four periods with a scan a run
    inside it sliced a whole period's kernels out of their stacks every
    step (a copy of all the weights a token); the convolution's window declared
    [..., 3, 4352] and the K/V entry [..., 8, 64] were padded to tiles
    and copied whole a dispatch (1.3 GB and 2 x 1.0 GB), and the
    published in_proj as one kernel of 8512 columns was copied into a
    padded layout a dispatch (1.25 GB)."""
    import re

    from benchmark import kfx_adapter_granitemoehybrid as A
    from benchmark.manifest import BENCH_DIR, load_json
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, init_cache)
    from kubeflow_tpu.serving.engine import DecodeEngine

    published = load_json(os.path.join(BENCH_DIR, "configs",
                                       "granite-4.0-h-micro.json"))
    serving = published["serving"]
    L, P, N = (serving["max_seq_len"], serving["kv_page_size"],
               serving["kv_pages"])
    cfg = TransformerConfig(**A.transformer_kwargs(
        published, max_seq_len=L, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, decode=True, kv_page_size=P, kv_pages=N,
        state_slots=serving["slots"]))
    eng = object.__new__(DecodeEngine)
    eng.cfg, eng.model, eng.name = cfg, TransformerLM(cfg), "aot"
    eng.n_slots, eng.chunk_tokens, eng.n_blocks = serving["slots"], 8, L // P
    eng._donate, eng._apool, eng._registry = True, None, None
    tree, _ = A.host_views(published, jnp.bfloat16)   # shapes: never touched
    eng.params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    eng._cache = jax.eval_shape(lambda: init_cache(cfg))
    jit = jax.jit

    class ForTheChip:
        def __init__(self, fn, **kw):
            self.jitted = jit(fn, **kw)

        def lower(self, *specs):
            return self.jitted.lower(*jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one_chip), specs))

    monkeypatch.setattr(jax, "jit", ForTheChip)
    compiled = eng._build_decode() if program == "decode_chunk" \
        else eng._build_prefill(256)
    monkeypatch.undo()
    text = compiled.as_text()
    assert f"jit_run_kfx_{program}" in text.splitlines()[0]
    carried = "|".join(re.escape(leaf) for leaf in (
        "f32[5,64,64,64,128]", "f32[9,64,64,64,128]",
        "f32[4,64,64,64,128]",                           # the state
        "bf16[5,64,13056]", "bf16[9,64,13056]",
        "bf16[4,64,13056]",                              # the windows
        f"bf16[1,{N},{P},512]"))                         # grouped K/V
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= ({carried})\S* (copy|copy-done)\(", line)]
    assert not copies, copies
    # no stack of kernels is copied or sliced out whole
    kernels = r"bf16\[[459],(2048,8448|2048,16384|8192,2048|4096,2048)\]"
    assert not re.search(
        rf"= {kernels}\S* (copy|copy-done|fusion|dynamic-slice)\(", text)
    # every carried leaf is an argument its result aliases
    assert text.splitlines()[0].count("may-alias") >= 20
    if program == "decode_chunk":
        # the sampler's three forms are still a conditional on the chip,
        # and the vocabulary sort stands in its third branch only (PR 45)
        assert re.search(r" conditional\(.*branch_computations="
                         r"\{[^},]+,[^},]+,[^},]+\}", text)
        sorts = [line for line in text.splitlines() if " sort(" in line]
        assert sorts and all("/sample/cond/branch_2_fun/" in line
                             for line in sorts), sorts
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < int(0.8 * 2 ** 30)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < int(15.75 * 2 ** 30))


def test_train_step_gathers_the_head_once_and_outside_every_loop(topo):
    """``LMTrainLoop``'s own step under fsdp on the four described
    chips, at a small size whose head is its largest leaf (2 layers, 4
    loss chunks). The chunked loss gathers the head once a step, before
    its loop, and reduces the head's gradient once, after it: no
    ``while`` body holds a collective that makes or takes an array of
    the head's shape, and the whole program holds one ``all-gather`` of
    it. (Read and cast inside the chunk body, the same step gathered it
    in the forward and in the backward loop and reduce-scattered its
    gradient there, once a chunk each.) The static form of the trace's
    one ``loss_head_gather`` event a step."""
    import re

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.parallel.lm_train import LMTrainLoop
    from kubeflow_tpu.parallel.mesh import make_mesh

    D, V, S = 256, 8192, 512
    cfg = TransformerConfig(
        vocab_size=V, d_model=D, n_heads=2, head_dim=128, n_layers=2,
        d_ff=512, max_seq_len=S, dtype=jnp.bfloat16, loss_chunk=S // 4)
    mesh, plan = make_mesh(devices=topo.devices, fsdp=True)
    loop = LMTrainLoop(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = jax.tree.map(
            lambda leaf, sharding: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding),
            jax.eval_shape(loop._init_fn,
                           jax.ShapeDtypeStruct((2,), jnp.uint32)),
            loop.state_shardings())
        tokens = jax.ShapeDtypeStruct((2 * plan.dp, S + 1), jnp.int32,
                                      sharding=loop.batch_sharding)
        text = loop._build_train_step().lower(state, tokens) \
            .compile().as_text()
    assert "loss_head_gather" in text and "loss_chunk" in text

    # computation -> its instructions; every instruction -> its type
    computations, types, name = {}, {}, None
    for line in text.splitlines():
        opened = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if opened:
            name = opened.group(1)
            computations[name] = []
        elif name and " = " in line:
            computations[name].append(line)
            result, rest = line.strip().split(" = ", 1)
            types[result.lstrip("%").removeprefix("ROOT %")] = \
                rest[:re.search(r" [\w-]+\(", rest).start()]

    def reached(name, seen):
        for line in computations.get(name, ()):
            for callee in re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.-]+)", line):
                if callee not in seen:
                    seen.add(callee)
                    reached(callee, seen)
        return seen

    bodies = set(re.findall(r"body=%?([\w.-]+)", text))
    assert len(bodies) == 3, bodies  # layers, loss chunks, layers back
    in_a_loop = set().union(*({b} | reached(b, set()) for b in bodies))
    head = re.compile(rf"\[{D},{V}\]")
    collective = re.compile(
        r" (all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(-start)?\((.*?)\)")
    of_the_head = {True: [], False: []}
    for name, lines in computations.items():
        for line in lines:
            found = collective.search(line.split(" = ", 1)[1])
            if not found:
                continue
            operands = re.findall(r"%([\w.-]+)", found.group(3))
            shapes = line.split(" = ", 1)[1][:found.start()] + " ".join(
                types.get(operand, "") for operand in operands)
            if head.search(shapes):
                of_the_head[name in in_a_loop].append(
                    (found.group(1), line.strip()[:160]))
    assert not of_the_head[True], of_the_head[True]
    assert [kind for kind, _ in of_the_head[False]].count("all-gather") \
        == 1, of_the_head[False]


def test_libtpu_registers_the_overlap_flags():
    """``lm_runner --collective-overlap`` hands these to libtpu through
    LIBTPU_INIT_ARGS; libtpu aborts on a flag it does not register (as
    jaxlib does for XLA_FLAGS, where they used to be put). Flags are
    read once per process, hence the child; it only describes a chip,
    so it may load libtpu beside this process."""
    from kubeflow_tpu.parallel.overlap import LIBTPU_ENV, OVERLAP_TPU_FLAGS

    code = ("from jax.experimental import topologies\n"
            f"topologies.get_topology_desc(platform='tpu', "
            f"topology_name={TOPOLOGY!r})\n"
            "print('libtpu_started')\n")
    env = dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               **{LIBTPU_ENV: " ".join(OVERLAP_TPU_FLAGS)})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    if "libtpu_started" not in out.stdout and \
            "Unknown command line flag" not in out.stderr:
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here")
    assert out.returncode == 0 and "libtpu_started" in out.stdout, \
        out.stderr[-2000:]
