"""The dense configurations' engine programs are what they were before
the latent / sparse / routed-expert layers came into the model file
(PR 36): the StableHLO that ``kfx_decode_chunk`` and ``kfx_prefill_256``
lower to for a small dense configuration, hashed. The text carries no
source locations, so moving a line does not change it; a changed
constant, operand order or extra operation does. The hashes were made
on the parent commit (ceb1e7d) with this file."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM

PARENT = {
    "decode_chunk":
        "506d3bf6fefef913910de83912d9cbffe6186a882b4a992ef35caf91672b2e4e",
    "prefill_256":
        "299959f01e2760a00750888fd7dbc2c5a6341b2e2c6fd4c8ce7bc86a556bbac8",
    # the training forward and backward of the same block, under remat
    "train_grad": 
        "21a93569003e0cf41441462871341751474e4abdb124273bf524ea382fab58be",
}


def lowered_programs():
    """{program: sha256 of its StableHLO} for a dense float32 engine of
    4 slots, 64 pages of 16 tokens, chunked prefill 256."""
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
    return {what: hashlib.sha256(
        texts[f"run_kfx_{what}"].encode()).hexdigest() for what in PARENT}


@pytest.fixture(scope="module")
def programs():
    return lowered_programs()


@pytest.mark.parametrize("program", sorted(PARENT))
def test_dense_program_lowers_to_the_parents_text(programs, program):
    assert programs[program] == PARENT[program]


if __name__ == "__main__":
    print(lowered_programs())
