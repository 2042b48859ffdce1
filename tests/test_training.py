"""Training-stack tests: dataset determinism/sharding, model shapes,
sharded train loop convergence, checkpoint/resume, and the full runner
(single- and multi-process with crash-resume fault injection)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from kubeflow_tpu.data import get_dataset

PY = sys.executable


class TestSyntheticData:
    def test_determinism_across_instances(self):
        a = next(get_dataset("mnist").batches(128))
        b = next(get_dataset("mnist").batches(128))
        assert (a[0] == b[0]).all() and (a[1] == b[1]).all()

    def test_shard_disjointness_reassembles_global_batch(self):
        # Global batch of 256 over 4 shards == the concatenation contract.
        full_stream = get_dataset("mnist").batches(256, steps=2)
        shards = [get_dataset("mnist").batches(256, shard_index=i,
                                               num_shards=4, steps=2)
                  for i in range(4)]
        for step in range(2):
            parts = [next(s) for s in shards]
            assert all(p[0].shape[0] == 64 for p in parts)
            # Different shards differ (overwhelmingly likely)
            assert not (parts[0][0] == parts[1][0]).all()

    def test_eval_fixed(self):
        x1, y1 = get_dataset("mnist", split="eval").eval_arrays(256)
        x2, y2 = get_dataset("mnist", split="eval").eval_arrays(256)
        assert (x1 == x2).all() and (y1 == y2).all()

    def test_label_noise_bounds_accuracy(self):
        ds = get_dataset("mnist")
        _, labels = next(ds.batches(4096))
        # ~10% label noise: a perfect prototype classifier can't exceed ~91%.
        assert ds.label_noise == pytest.approx(0.10)

    def test_shapes(self):
        c = get_dataset("cifar10")
        im, lb = next(c.batches(32))
        assert im.shape == (32, 32, 32, 3)
        assert c.num_classes == 10

    def test_unknown(self):
        with pytest.raises(KeyError, match="unknown dataset"):
            get_dataset("mnist-real")


def test_peak_flops_has_no_default_for_an_unknown_device():
    """MFU against someone else's peak is not a measurement: a device
    kind outside the table (this suite's CPU) raises."""
    from kubeflow_tpu.utils.flops import PEAK_FLOPS, peak_flops_per_chip

    assert PEAK_FLOPS["TPU v5 lite"] == 197e12
    with pytest.raises(ValueError, match="device kind 'cpu'"):
        peak_flops_per_chip()


class TestModels:
    def test_mlp_forward(self):
        import jax
        from kubeflow_tpu.models import get_model

        m = get_model("mlp", num_classes=10)
        v = m.init(jax.random.PRNGKey(0), np.zeros((2, 28, 28, 1), np.float32))
        out = m.apply(v, np.zeros((2, 28, 28, 1), np.float32))
        assert out.shape == (2, 10)
        assert out.dtype == np.float32  # logits upcast for stable CE

    # ~14s of tier-1 wall, nearly all resnet compile, for a forward
    # shape check; the get_model forward contract stays covered by
    # the mlp/cnn/vit forwards, so this rides tier-2.
    @pytest.mark.slow
    def test_resnet18_forward_cifar_stem(self):
        import jax
        from kubeflow_tpu.models import get_model

        m = get_model("resnet18", num_classes=10)
        x = np.zeros((2, 32, 32, 3), np.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        assert "batch_stats" in v
        out, new_vars = m.apply(v, x, train=True, mutable=["batch_stats"])
        assert out.shape == (2, 10)

    def test_cnn_forward_and_trains(self):
        """The conv mnist model (tf-operator example parity): forward
        shape + a few sharded train steps reduce the loss."""
        import jax
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import TrainLoop

        m = get_model("cnn", num_classes=10)
        x = np.zeros((2, 28, 28, 1), np.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(v, x)
        assert out.shape == (2, 10) and out.dtype == np.float32

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("cnn"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        losses = []
        for images, labels in ds.batches(64, steps=8):
            state, loss, _ = loop.train_step(state, images, labels)
            losses.append(loss)
        assert losses[-1] < losses[0]

    def test_vit_forward(self):
        """Vision-transformer family: patch-embed shapes and the
        forward dtype contract (the train-steps soak is the slow-tier
        test_vit_trains — the compile alone is ~40s of tier-1 wall)."""
        import jax
        from kubeflow_tpu.models import get_model

        m = get_model("vit", num_classes=10)
        x = np.zeros((2, 28, 28, 1), np.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(v, x)
        assert out.shape == (2, 10) and out.dtype == np.float32

    @pytest.mark.slow
    def test_vit_trains(self):
        """A few train steps reduce the ViT loss (soak tier: the
        train_step compile dominates; the forward contract stays
        tier-1 in test_vit_forward)."""
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("vit"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        losses = []
        for images, labels in ds.batches(64, steps=8):
            state, loss, _ = loop.train_step(state, images, labels)
            losses.append(loss)
        assert losses[-1] < losses[0]

    def test_vit_rejects_indivisible_patches(self):
        import jax
        from kubeflow_tpu.models import get_model

        m = get_model("vit", num_classes=10)
        with pytest.raises(ValueError, match="patch_size"):
            m.init(jax.random.PRNGKey(0),
                   np.zeros((1, 30, 30, 1), np.float32))

    def test_registry_unknown(self):
        from kubeflow_tpu.models import get_model

        with pytest.raises(KeyError, match="unknown model"):
            get_model("gpt5")


class TestTrainLoop:
    def test_mlp_converges_on_8dev_mesh(self):
        """Loss must drop under the data-parallel sharded step (8 CPU devs)."""
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        assert loop.mesh.size == 8
        state = loop.init_state(ds.shape)
        losses = []
        for images, labels in ds.batches(256, steps=30):
            state, loss, acc = loop.train_step(state, images, labels)
            losses.append(loss)
        assert losses[-1] < losses[0] * 0.5, losses
        metrics = loop.evaluate(state, *ds.eval_arrays(1024))
        assert metrics["accuracy"] > 0.5

    @pytest.mark.slow
    def test_resnet_batchnorm_updates(self):
        """BN running stats move under the full ResNet TrainLoop (soak
        tier: the cifar train_step compile is ~50s of wall; tier-1
        keeps the mutable-batch_stats forward contract in
        test_resnet18_forward_cifar_stem)."""
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import TrainLoop
        import jax

        ds = get_dataset("cifar10")
        loop = TrainLoop(get_model("resnet18"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        stats0 = jax.device_get(state.batch_stats)
        for images, labels in ds.batches(64, steps=2):
            state, loss, acc = loop.train_step(state, images, labels)
        stats1 = jax.device_get(state.batch_stats)
        leaves0 = jax.tree.leaves(stats0)
        leaves1 = jax.tree.leaves(stats1)
        assert any(not np.allclose(a, b) for a, b in zip(leaves0, leaves1))


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        import jax
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        for images, labels in ds.batches(128, steps=3):
            state, *_ = loop.train_step(state, images, labels)
        ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1)
        ckpt.maybe_save(3, state, force=True)
        ckpt.wait()
        assert ckpt.latest_step() == 3

        fresh = loop.init_state(ds.shape)
        restored = ckpt.restore_latest(fresh)
        assert int(restored.step) == 3
        a = jax.tree.leaves(jax.device_get(state.params))
        b = jax.tree.leaves(jax.device_get(restored.params))
        assert all(np.allclose(x, y) for x, y in zip(a, b))
        ckpt.close()

    def test_resume_reapplies_cli_hyperparams(self, tmp_path):
        """lr lives in opt_state (inject_hyperparams — one compiled step
        for every HPO trial), so a resume must re-assert the CLI's lr
        over the checkpointed one: restarting with a new --learning-rate
        has to take effect, as it did when lr was a trace constant."""
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop

        ds = get_dataset("mnist")
        loop1 = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        state = loop1.init_state(ds.shape)
        ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1)
        ckpt.maybe_save(1, state, force=True)
        ckpt.wait()

        loop2 = TrainLoop(get_model("mlp"), learning_rate=5e-4)
        restored = ckpt.restore_latest(loop2.init_state(ds.shape))
        assert float(restored.opt_state.hyperparams[
            "learning_rate"]) == pytest.approx(1e-3)  # checkpointed value
        resumed = loop2.reapply_hyperparams(restored)
        assert float(resumed.opt_state.hyperparams[
            "learning_rate"]) == pytest.approx(5e-4)  # CLI wins
        ckpt.close()

    def test_legacy_checkpoint_migrates_into_injected_layout(
            self, tmp_path, capfd):
        """Checkpoints written before hyperparams moved into opt_state
        (inject_hyperparams) hold the bare inner optimizer state. A
        resume must MIGRATE that progress — graft the legacy opt_state
        under a fresh wrapper — not silently restart at step 0 and let
        the keep-rotation delete it (advisor r4, medium)."""
        import jax
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        for images, labels in ds.batches(128, steps=2):
            state, *_ = loop.train_step(state, images, labels)
        # What the pre-injection code saved: the inner optimizer state
        # directly (inject_hyperparams wraps, it does not restructure).
        legacy = state.replace(opt_state=state.opt_state.inner_state)
        ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1)
        ckpt.maybe_save(2, legacy, force=True)
        ckpt.wait()

        fresh = loop.init_state(ds.shape)
        restored = ckpt.restore_latest(
            fresh, legacy_layouts=loop.legacy_checkpoint_layouts(fresh))
        assert restored is not None
        assert int(restored.step) == 2
        assert "checkpoint_migrated" in capfd.readouterr().out
        # Progress carried over: params and adam moments match, and the
        # wrapper carries the configured lr so training can continue.
        a = jax.tree.leaves(jax.device_get(state.params))
        b = jax.tree.leaves(jax.device_get(restored.params))
        assert all(np.allclose(x, y) for x, y in zip(a, b))
        m_old = jax.tree.leaves(jax.device_get(
            state.opt_state.inner_state))
        m_new = jax.tree.leaves(jax.device_get(
            restored.opt_state.inner_state))
        assert all(np.allclose(x, y) for x, y in zip(m_old, m_new))
        assert float(restored.opt_state.hyperparams[
            "learning_rate"]) == pytest.approx(1e-3)
        restored, loss, acc = loop.train_step(
            restored, *next(iter(ds.batches(128, steps=1))))
        assert np.isfinite(loss)
        ckpt.close()

    def test_incompatible_structure_falls_back_to_fresh(self, tmp_path, capfd):
        """A checkpoint whose tree no longer matches the target (e.g.
        written before an optimizer-state layout change) must degrade to
        a fresh start, not crash the resuming job."""
        import jax.numpy as jnp
        from kubeflow_tpu.training import Checkpointer

        ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1)
        ckpt.maybe_save(1, {"old_layout": jnp.zeros((2,))}, force=True)
        ckpt.wait()
        out = ckpt.restore_latest({"new_layout": {"nested": jnp.zeros((3,))}})
        assert out is None
        assert "checkpoint_restore_incompatible" in capfd.readouterr().out
        ckpt.close()

    def test_corrupt_latest_falls_back_to_older_retained_step(
            self, tmp_path, capfd):
        """keep=2 retains an older good step precisely so a torn write
        of the newest can't kill the job: restore must quarantine the
        corrupt latest (observably, preserving its bytes) and resume
        from the previous retained step — never step 0."""
        import jax
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop
        from kubeflow_tpu.training.checkpoint import corrupt_step_dir

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1, keep=2)
        it = ds.batches(64, steps=2)
        state, *_ = loop.train_step(state, *next(it))
        ckpt.maybe_save(1, state, force=True)
        good_params = jax.tree.leaves(jax.device_get(state.params))
        state, *_ = loop.train_step(state, *next(it))
        ckpt.maybe_save(2, state, force=True)
        ckpt.wait()
        assert corrupt_step_dir(str(tmp_path / "ck"), 2) > 0

        restored = ckpt.restore_latest(loop.init_state(ds.shape))
        assert restored is not None
        assert int(restored.step) == 1  # the older retained step
        b = jax.tree.leaves(jax.device_get(restored.params))
        assert all(np.allclose(x, y) for x, y in zip(good_params, b))
        out = capfd.readouterr().out
        assert "checkpoint_unreadable step=2" in out
        assert "checkpoint_quarantined step=2" in out
        # Quarantine preserves the bad bytes for forensics and removes
        # the step from election: rotation continues cleanly.
        assert (tmp_path / "ck" / "quarantine-2").is_dir()
        assert not (tmp_path / "ck" / "2").exists()
        assert ckpt.latest_step() == 1
        ckpt.maybe_save(3, state, force=True)
        ckpt.wait()
        assert sorted(ckpt.manager.all_steps()) == [1, 3]
        ckpt.close()

    def test_chaos_save_corruption_point(self, tmp_path, capfd):
        """The checkpoint.save fault point corrupts the just-committed
        save in place — the deterministic seed for the restore-fallback
        path above."""
        from kubeflow_tpu import chaos
        from kubeflow_tpu.models import get_model
        from kubeflow_tpu.training import Checkpointer, TrainLoop

        ds = get_dataset("mnist")
        loop = TrainLoop(get_model("mlp"), learning_rate=1e-3)
        state = loop.init_state(ds.shape)
        chaos.reset()
        chaos.install(chaos.parse_spec(
            "checkpoint.save:mode=corrupt,after=1,count=1"))
        try:
            ckpt = Checkpointer(str(tmp_path / "ck"), save_every=1, keep=2)
            ckpt.maybe_save(1, state, force=True)   # draw 0: skipped
            ckpt.maybe_save(2, state, force=True)   # draw 1: corrupted
            ckpt.wait()
            assert "chaos_corrupt_checkpoint step=2" in \
                capfd.readouterr().out
            restored = ckpt.restore_latest(loop.init_state(ds.shape))
            assert restored is not None
            assert (tmp_path / "ck" / "quarantine-2").is_dir()
            ckpt.close()
        finally:
            chaos.reset()


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _runner_env(tmp_path, extra=None):
    env = dict(os.environ)
    prior = env.get("PYTHONPATH")
    env["PYTHONPATH"] = REPO_ROOT + (os.pathsep + prior if prior else "")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "KFX_CHECKPOINT_DIR": str(tmp_path / "ckpt"),
    })
    env.update(extra or {})
    return env


@pytest.mark.slow
class TestRunnerE2E:
    def test_single_process_with_export(self, tmp_path):
        out = subprocess.run(
            [PY, "-m", "kubeflow_tpu.runners.jax_runner", "--model=mlp",
             "--dataset=mnist", "--steps=30", "--batch-size=128",
             "--log-every=10", "--checkpoint-every=20",
             f"--export-dir={tmp_path}/export"],
            env=_runner_env(tmp_path), capture_output=True, text=True,
            timeout=300, cwd=str(tmp_path))
        assert out.returncode == 0, out.stdout + out.stderr
        assert "accuracy=" in out.stdout
        assert "exported_model" in out.stdout
        from kubeflow_tpu.serving.export import load_exported

        config, payload = load_exported(f"{tmp_path}/export")
        assert config["model"] == "mlp"
        assert "params" in payload

    def test_crash_resume(self, tmp_path):
        """Fault injection: crash at step 25, rerun, must resume from 20."""
        argv = [PY, "-m", "kubeflow_tpu.runners.jax_runner", "--model=mlp",
                "--dataset=mnist", "--steps=40", "--batch-size=128",
                "--log-every=10", "--checkpoint-every=20"]
        out1 = subprocess.run(argv + ["--fail-at-step=25"],
                              env=_runner_env(tmp_path), capture_output=True,
                              text=True, timeout=300, cwd=str(tmp_path))
        assert out1.returncode == 17
        assert "fault_injection_crash step=25" in out1.stdout
        out2 = subprocess.run(argv, env=_runner_env(tmp_path),
                              capture_output=True, text=True, timeout=300,
                              cwd=str(tmp_path))
        assert out2.returncode == 0, out2.stdout + out2.stderr
        assert "resumed_from_checkpoint step=20" in out2.stdout
        assert "train_done steps=40" in out2.stdout
