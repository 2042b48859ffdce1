"""The dense configurations' engine programs are what they were before
the latent / sparse / routed-expert layers came into the model file
(PR 36): the StableHLO that ``kfx_decode_chunk`` and ``kfx_prefill_256``
lower to for a small dense configuration, hashed. The text carries no
source locations, so moving a line does not change it; a changed
constant, operand order or extra operation does. The hashes were made
on the parent commit (ceb1e7d) with this file; ``decode_chunk``'s was
made again in PR 45, on 15b9f16 with that PR's sampler (the step picks
the sampler's form once for the batch and counts the steps that drew
and sorted), the other two still as they were: nothing else moved."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM

PARENT = {
    "decode_chunk":
        "680c731bb4d3a9388b95d20e8318eb8bc81ac3aaa279e2bbae6bd855d694722c",
    "prefill_256":
        "299959f01e2760a00750888fd7dbc2c5a6341b2e2c6fd4c8ce7bc86a556bbac8",
    # the training forward and backward of the same block, under remat
    "train_grad": 
        "21a93569003e0cf41441462871341751474e4abdb124273bf524ea382fab58be",
}


def lowered_texts():
    """{program: its StableHLO} for a dense float32 engine of 4 slots,
    64 pages of 16 tokens, chunked prefill 256."""
    from kubeflow_tpu.serving import engine as E

    cfg = TransformerConfig(vocab_size=512, d_model=128, n_heads=4,
                            head_dim=32, n_layers=2, d_ff=256,
                            max_seq_len=512, dtype=jnp.float32,
                            param_dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    texts = {}
    real_jit = jax.jit

    class Recording:
        def __init__(self, fn, **kw):
            self.fn, self.jitted = fn, real_jit(fn, **kw)

        def lower(self, *specs):
            lowered = self.jitted.lower(*specs)
            texts[self.fn.__name__] = lowered.as_text()
            return lowered

    eng = E.DecodeEngine(cfg, params, n_slots=4, chunk_tokens=4,
                         kv_page_size=16, kv_pages=64, prefix_cache=False,
                         prefill_chunk_tokens=256, name="guard")
    try:
        jax.jit = lambda fn, **kw: Recording(fn, **kw)
        try:
            eng._build_decode()
            eng._build_prefill(256)
        finally:
            jax.jit = real_jit
    finally:
        eng.close()
    train = TransformerLM(dataclasses.replace(cfg, remat=True))

    def run_kfx_train_grad(p, tokens):
        return jax.grad(lambda p: jnp.mean(
            train.apply({"params": p}, tokens)))(p)

    texts["run_kfx_train_grad"] = jax.jit(run_kfx_train_grad).lower(
        params, jnp.zeros((2, 64), jnp.int32)).as_text()
    return {what: texts[f"run_kfx_{what}"] for what in PARENT}


@pytest.fixture(scope="module")
def texts():
    return lowered_texts()


@pytest.fixture(scope="module")
def programs(texts):
    return {what: hashlib.sha256(text.encode()).hexdigest()
            for what, text in texts.items()}


@pytest.mark.parametrize("program", sorted(PARENT))
def test_dense_program_lowers_to_the_parents_text(programs, program):
    assert programs[program] == PARENT[program]


def test_decode_chunk_holds_its_sort_inside_a_conditionals_branch(texts):
    """One conditional of three branches in the decode step (PR 45), the
    vocabulary sort called from inside it and from nowhere else: under
    ``vmap`` a cond on a row's own knobs lowered to a select, and the
    sort ran a row a step whoever asked."""
    lines = texts["decode_chunk"].splitlines()
    # MLIR closes an operation's regions at the operation's own indent
    first, = (i for i, line in enumerate(lines)
              if '"stablehlo.case"(' in line)
    indent = " " * (len(lines[first]) - len(lines[first].lstrip()))
    last = next(i for i in range(first + 1, len(lines))
                if lines[i].startswith(indent + "})"))
    branches = [i for i in range(first, last) if lines[i] == indent + "}, {"]
    assert len(branches) == 2
    # the sort is a function of its own, called from the conditional only
    owner, sorters = None, set()
    for line in lines:
        opened = re.match(r"\s*func\.func \w+ @(\w+)", line)
        owner = opened.group(1) if opened else owner
        if "stablehlo.sort" in line:
            sorters.add(owner)
    assert sorters and "main" not in sorters
    calls = [i for i, line in enumerate(lines)
             if (m := re.search(r"call @(\w+)", line))
             and m.group(1) in sorters]
    assert calls and all(first < i < last for i in calls)
    # the greedy branch is the first: an argmax, no draw and no sort
    greedy = "\n".join(lines[first:branches[0]])
    assert "argmax" in greedy
    assert "gumbel" not in greedy and "sort" not in greedy


if __name__ == "__main__":
    print({what: hashlib.sha256(text.encode()).hexdigest()
           for what, text in lowered_texts().items()})
