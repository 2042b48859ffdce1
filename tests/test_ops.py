"""Pallas kernel tests (ops/): flash attention numerics vs the dense
oracle, gradient parity, and the model-level attn_impl switch. On the
CPU test mesh the tests ask for the Pallas interpreter themselves (the
``interpret_flash`` fixture); the program never picks it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.usefixtures("interpret_flash")


def _dense(q, k, v):
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    mask = np.tril(np.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, jnp.float32)


class TestFlashAttention:
    def test_forward_matches_dense(self):
        from kubeflow_tpu.ops.flash_attention import flash_attention

        B, S, H, D = 2, 256, 2, 64
        q = _rand((B, S, H, D), 0, 1 / 8)
        k = _rand((B, S, H, D), 1)
        v = _rand((B, S, H, D), 2)
        out = jax.jit(flash_attention)(q, k, v)
        ref = _dense(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-2

    def test_gradients_match_dense(self):
        from kubeflow_tpu.ops.flash_attention import flash_attention

        B, S, H, D = 1, 128, 2, 64
        q = _rand((B, S, H, D), 3, 1 / 8)
        k = _rand((B, S, H, D), 4)
        v = _rand((B, S, H, D), 5)

        gf = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(_dense(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
            assert float(jnp.max(jnp.abs(a - b))) / scale < 2e-2

    def test_uneven_blocks(self):
        """S not divisible by the preferred block: _pick_block falls back
        to a divisor, numerics unchanged."""
        from kubeflow_tpu.ops.flash_attention import flash_attention

        B, S, H, D = 1, 384, 1, 64  # 384 = 3 * 128, not 256-divisible
        q = _rand((B, S, H, D), 6, 1 / 8)
        k = _rand((B, S, H, D), 7)
        v = _rand((B, S, H, D), 8)
        out = flash_attention(q, k, v)
        ref = _dense(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-2

    def test_supported_predicate(self):
        from kubeflow_tpu.ops.flash_attention import supported

        assert supported(512, 64) and supported(2048, 128)
        assert not supported(500, 64)   # seq not 128-divisible
        assert not supported(512, 80)   # head dim not lane-aligned


class TestModelAttnImpl:
    def _cfg(self, attn_impl, seq):
        from kubeflow_tpu.models.transformer import TransformerConfig

        return TransformerConfig(
            vocab_size=128, d_model=64, n_heads=1, head_dim=64, n_layers=2,
            d_ff=128, max_seq_len=seq, dtype=jnp.float32,
            attn_impl=attn_impl)

    def test_flash_matches_xla_in_model(self):
        from kubeflow_tpu.models.transformer import TransformerLM

        tokens = jnp.asarray(
            np.random.default_rng(9).integers(0, 128, (1, 128)), jnp.int32)
        m_x = TransformerLM(self._cfg("xla", 128))
        params = m_x.init(jax.random.PRNGKey(0), tokens)
        out_x = m_x.apply(params, tokens)
        m_f = TransformerLM(self._cfg("flash", 128))
        out_f = m_f.apply(params, tokens)
        assert float(jnp.max(jnp.abs(out_x - out_f))) < 5e-2

    def test_flash_rejects_bad_head_dim(self):
        import dataclasses

        from kubeflow_tpu.models.transformer import TransformerLM

        cfg = dataclasses.replace(self._cfg("flash", 128), head_dim=80)
        tokens = jnp.zeros((1, 128), jnp.int32)
        with pytest.raises(ValueError, match="attn_impl='flash'"):
            TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)

    def test_flash_auto_window_is_configurable(self):
        """The 'auto' window is a measured default, not a hardcoded law
        (round-2 review): flash_min_seq/flash_max_seq move it, and
        max<=0 removes the upper bound."""
        import dataclasses

        from kubeflow_tpu.models.transformer import flash_window_ok

        cfg = self._cfg("auto", 2048)
        assert not flash_window_ok(cfg, 512)
        assert flash_window_ok(cfg, 1024)  # r5 crossover (save_flash)
        assert flash_window_ok(cfg, 2048)
        assert not flash_window_ok(cfg, 4096)
        wide = dataclasses.replace(cfg, flash_min_seq=512,
                                   flash_max_seq=0)
        assert flash_window_ok(wide, 512)
        assert flash_window_ok(wide, 1 << 20)
        assert not flash_window_ok(wide, 256)

    def test_flash_falls_back_for_sub_block_seq(self):
        """The 8-token init sample (and any seq%128!=0 trace) rides the
        dense path even under attn_impl='flash'."""
        from kubeflow_tpu.models.transformer import TransformerLM

        tokens = jnp.zeros((1, 100), jnp.int32)
        model = TransformerLM(self._cfg("flash", 100))
        out = model.init_with_output(jax.random.PRNGKey(0), tokens)[0]
        assert out.shape == (1, 100, 128)

    def test_auto_is_xla_off_tpu(self):
        from kubeflow_tpu.models.transformer import Attention

        attn = Attention(self._cfg("auto", 128))
        if jax.default_backend() != "tpu":
            assert not attn._use_flash(128)

    def test_save_flash_remat_grads_match(self):
        """The save_flash policy (keep the flash kernel's o/lse so the
        remat backward skips the forward kernel) must be a pure
        scheduling change: loss and grads match full remat exactly-ish."""
        import dataclasses

        from kubeflow_tpu.models.transformer import TransformerLM

        tokens = jnp.asarray(
            np.random.default_rng(11).integers(0, 128, (2, 128)), jnp.int32)
        base = dataclasses.replace(self._cfg("flash", 128), remat=True)

        def loss_fn(cfg):
            model = TransformerLM(cfg)

            def loss(params):
                logits = model.apply({"params": params}, tokens)
                return jnp.mean(logits ** 2)

            return model, loss

        m0, loss0 = loss_fn(dataclasses.replace(base,
                                                remat_policy="nothing"))
        params = m0.init(jax.random.PRNGKey(0), tokens)["params"]
        l0, g0 = jax.value_and_grad(loss0)(params)
        _, loss1 = loss_fn(dataclasses.replace(base,
                                               remat_policy="save_flash"))
        l1, g1 = jax.value_and_grad(loss1)(params)
        assert abs(float(l0) - float(l1)) < 1e-5
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            scale = max(float(jnp.max(jnp.abs(a))), 1e-6)
            assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-3
