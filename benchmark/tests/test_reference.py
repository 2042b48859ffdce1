"""The plain reference against kfx's TransformerLM at a tiny size in
float32, and the seeded weights' two makers."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import kfx_adapter as K
from benchmark import reference as R
from benchmark import reference_compare as C
from benchmark import weights as W
from benchmark.tests.tiny import TINY_CONFIG as CFG


def test_reference_agrees_with_transformer_lm_in_float32():
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    leaf = lambda n, l: W.host_leaf(2**31 + 5, CFG, n, l, np.float32)
    tree = K.program_tree(leaf, CFG)
    tcfg = TransformerConfig(**K.transformer_kwargs(
        CFG, dtype=jnp.float32, param_dtype=jnp.float32))
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 48))
    with jax.default_matmul_precision("highest"):
        want = TransformerLM(tcfg).apply({"params": tree}, jnp.asarray(tokens))
        hidden = R.hidden_states(leaf, CFG, jnp.asarray(tokens))
        got = hidden @ jnp.asarray(leaf("lm_head", -1))
    assert want.shape == got.shape == (2, 48, CFG["vocab_size"])
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(want - got).max()) < 2e-5


def test_host_weights_repeat_per_seed_and_differ_per_leaf():
    a = W.host_leaf(3, CFG, "q_proj", 0, np.float32)
    assert (a == W.host_leaf(3, CFG, "q_proj", 0, np.float32)).all()
    assert not (a == W.host_leaf(3, CFG, "q_proj", 1, np.float32)).all()
    assert not (a == W.host_leaf(3, CFG, "k_proj", 0, np.float32)).all()
    assert not (a == W.host_leaf(4, CFG, "q_proj", 0, np.float32)).all()
    assert abs(float(a.std()) - 0.02) < 0.002
    scale = W.host_leaf(3, CFG, "norm", -1, np.float32)
    assert abs(float(scale.mean()) - 1.0) < 0.05 and scale.std() > 0.05


def test_device_weights_do_not_depend_on_the_sharding():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    key = W.device_key(2**31 + 9)
    make = lambda k: W.device_leaf(k, CFG, "gate_proj", 1, jnp.float32)
    plain = jax.jit(make)(key)   # eager rounds an ulp apart from jitted
    mesh = Mesh(np.array(jax.devices()), ("x",))
    assert mesh.size == 4
    sharded = jax.jit(
        make, out_shardings=NamedSharding(mesh, P("x", None)))(key)
    assert (np.asarray(plain) == np.asarray(sharded)).all()
    stacked = jax.jit(lambda k: K.program_tree(
        lambda n, l: W.device_leaf(k, CFG, n, l, jnp.float32), CFG,
        stack=jnp.stack, concat=lambda xs: jnp.concatenate(xs, -1)))(key)
    wi = np.asarray(stacked["layers"]["mlp"]["wi"]["kernel"])
    f = CFG["intermediate_size"]
    assert (wi[1, :, :f] == np.asarray(plain)).all()


def test_published_norms_name_every_leaf():
    leaf = lambda n, l: W.host_leaf(1, CFG, n, l, np.float32)
    tree = K.program_tree(leaf, CFG)
    norms = K.flatten_norms(jax.device_get(K.published_norms(
        jax.tree_util.tree_map(jnp.asarray, tree), CFG)))
    want = {n if l < 0 else f"{n}.{l}":
            float(np.sqrt((leaf(n, l).astype(np.float64) ** 2).sum()))
            for n, l in W.leaves(CFG)}
    assert sorted(norms) == sorted(want)
    assert all(abs(norms[k] - want[k]) < 1e-3 * want[k] for k in want)
    assert C.worst_leaf_gap(norms, want) < 1e-3
    broken = dict(norms, **{"q_proj.1": 0.0})
    assert C.worst_leaf_gap(broken, want) > 0.5  # against the median leaf


def test_streamed_export_is_what_load_lm_reads(tmp_path):
    """The export writer fills kfx's tree in place (host_views) and
    streams flax's msgpack format itself: kfx's load_lm must read back
    exactly the tree program_tree builds from the same leaves."""
    import json

    from benchmark.workers import export_writer
    from kubeflow_tpu.serving.lm_server import load_lm

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(CFG))
    out = str(tmp_path / "export")
    assert export_writer.main(["--config", str(path), "--seed", "9",
                               "--out", out, "--max-seq-len", "128"]) == 0
    tcfg, params = load_lm(out)
    want = K.program_tree(
        lambda n, l: W.host_leaf(9, CFG, n, l, np.float32), CFG)
    assert (tcfg.d_model, tcfg.max_seq_len) == (128, 128)
    same = jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and bool((np.asarray(a) == b).all()),
        params, want)
    assert jax.tree_util.tree_all(same)
