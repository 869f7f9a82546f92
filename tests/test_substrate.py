"""Substrate tests: data pipeline, checkpointing (fault tolerance, elastic
restore), optimizer, gradient compression, quantization, train loop."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _optional_deps import given, settings, st

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, make_pipeline
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule
from repro.optim.compression import compress_int8, decompress_int8
from repro.models.quant import dequantize_leaf, quantize_leaf, quantize_params

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# data pipeline: determinism + checkpointable stream position
# ---------------------------------------------------------------------------
class TestData:
    CFG = DataConfig(vocab=1000, seq_len=64, global_batch=4, seed=7)

    def test_deterministic(self):
        a = make_pipeline(self.CFG)
        b = make_pipeline(self.CFG)
        for _ in range(3):
            np.testing.assert_array_equal(a.next_batch(), b.next_batch())

    def test_resume_mid_stream(self):
        a = make_pipeline(self.CFG)
        for _ in range(5):
            a.next_batch()
        state = a.state()
        want = a.next_batch()
        b = make_pipeline(self.CFG, state)
        np.testing.assert_array_equal(b.next_batch(), want)

    def test_batches_differ_across_steps(self):
        a = make_pipeline(self.CFG)
        assert not np.array_equal(a.next_batch(), a.next_batch())

    def test_tokens_in_range(self):
        a = make_pipeline(self.CFG)
        batch = a.next_batch()
        assert batch.min() >= 0 and batch.max() < self.CFG.vocab


# ---------------------------------------------------------------------------
# checkpoint manager: atomicity, keep-K, elastic restore
# ---------------------------------------------------------------------------
class TestCheckpoint:
    def _tree(self, key=0):
        k = jax.random.PRNGKey(key)
        return {
            "w": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(5.0), "step": jnp.int32(3)},
        }

    def test_roundtrip(self, tmp_path):
        cm = CheckpointManager(tmp_path)
        tree = self._tree()
        cm.save(10, tree, extra={"data": {"seed": 1, "step": 10}})
        got, extra, step = cm.restore(None, tree)
        assert step == 10 and extra["data"]["step"] == 10
        jax.tree.map(np.testing.assert_array_equal, got, tree)

    def test_keep_k_prunes(self, tmp_path):
        cm = CheckpointManager(tmp_path, keep=2)
        t = self._tree()
        for s in (1, 2, 3, 4):
            cm.save(s, t)
        assert cm.steps() == [3, 4]

    def test_latest_and_explicit_step(self, tmp_path):
        cm = CheckpointManager(tmp_path, keep=5)
        cm.save(1, self._tree(1))
        cm.save(2, self._tree(2))
        got, _, step = cm.restore(1, self._tree())
        assert step == 1
        jax.tree.map(np.testing.assert_array_equal, got, self._tree(1))

    def test_interrupted_save_keeps_previous(self, tmp_path):
        """A .tmp dir left behind by a crash must not shadow the good ckpt."""
        cm = CheckpointManager(tmp_path)
        cm.save(5, self._tree())
        (tmp_path / "step_00000009.tmp").mkdir()
        assert cm.latest_step() == 5
        got, _, step = cm.restore(None, self._tree())
        assert step == 5

    def test_elastic_restore_other_mesh(self, tmp_path):
        """Save unsharded, restore onto a different sharding (mesh reshape)."""
        cm = CheckpointManager(tmp_path)
        tree = {"w": jnp.arange(32.0).reshape(8, 4)}
        cm.save(1, tree)
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = {"w": NamedSharding(mesh, P("data", None))}
        got, _, _ = cm.restore(None, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        assert got["w"].sharding == sh["w"]

    def test_structure_mismatch_rejected(self, tmp_path):
        cm = CheckpointManager(tmp_path)
        cm.save(1, self._tree())
        with pytest.raises(AssertionError):
            cm.restore(None, {"only_one": jnp.zeros(3)})


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
class TestOptim:
    def test_adamw_converges_quadratic(self):
        params = {"x": jnp.array([3.0, -2.0])}
        opt = adamw_init(params)
        for _ in range(300):
            grads = jax.grad(lambda p: jnp.sum(jnp.square(p["x"])))(params)
            params, opt = adamw_update(grads, opt, params, 0.05, weight_decay=0.0)
        assert float(jnp.max(jnp.abs(params["x"]))) < 0.05

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((4,), 10.0)}
        clipped, gn = clip_by_global_norm(g, 1.0)
        assert abs(float(gn) - 20.0) < 1e-4
        total = float(jnp.sqrt(jnp.sum(jnp.square(clipped["a"]))))
        assert abs(total - 1.0) < 1e-4

    def test_cosine_schedule_shape(self):
        lrs = [
            float(cosine_schedule(jnp.int32(s), peak_lr=1.0, warmup=10, total=100))
            for s in (0, 5, 10, 55, 100)
        ]
        assert lrs[0] == 0.0 and abs(lrs[2] - 1.0) < 1e-6
        assert lrs[1] < lrs[2] and lrs[3] < lrs[2] and lrs[4] <= lrs[3]

    def test_weight_decay_shrinks(self):
        params = {"x": jnp.array([1.0])}
        opt = adamw_init(params)
        zero_g = {"x": jnp.zeros(1)}
        p2, _ = adamw_update(zero_g, opt, params, 0.1, weight_decay=0.5)
        assert float(p2["x"][0]) < 1.0


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------
class TestCompression:
    def test_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        q, s, err = compress_int8(x)
        recon = decompress_int8(q, s)
        rel = float(jnp.linalg.norm(recon - x) / jnp.linalg.norm(x))
        assert rel < 0.01

    def test_error_feedback_unbiased_over_steps(self):
        """With error feedback, the accumulated transmitted signal tracks the
        accumulated true signal (the residual stays bounded)."""
        key = jax.random.PRNGKey(1)
        err = jnp.zeros((256,))
        sent = jnp.zeros((256,))
        total = jnp.zeros((256,))
        for i in range(50):
            key, k = jax.random.split(key)
            g = jax.random.normal(k, (256,)) * (1.0 + i % 3)
            total = total + g
            q, s, err = compress_int8(g, err)
            sent = sent + decompress_int8(q, s)
        drift = float(jnp.linalg.norm(sent - total) / jnp.linalg.norm(total))
        assert drift < 0.01

    @given(st.integers(0, 2**31 - 1), st.floats(0.1, 1e4))
    @settings(max_examples=20, deadline=None)
    def test_compression_scale_invariant(self, seed, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
        q, s, _ = compress_int8(x)
        recon = decompress_int8(q, s)
        assert float(jnp.max(jnp.abs(recon - x))) <= float(s) * 0.51 + 1e-6


# ---------------------------------------------------------------------------
# int8 weight-only quantization
# ---------------------------------------------------------------------------
class TestQuant:
    def test_leaf_roundtrip(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 1024)) * 0.02
        q = quantize_leaf(w)
        rel = float(
            jnp.linalg.norm(dequantize_leaf(q, jnp.float32) - w) / jnp.linalg.norm(w)
        )
        assert rel < 0.01

    def test_per_layer_scales(self):
        w = jnp.stack([jnp.ones((4, 4)) * 0.001, jnp.ones((4, 4)) * 100.0])
        q = quantize_leaf(w, per_layer=True)
        assert q["__s"].shape == (2,)
        back = dequantize_leaf(q, jnp.float32)
        np.testing.assert_allclose(back, w, rtol=0.01)

    def test_tree_quantization_targets_large_leaves(self):
        from repro.configs import reduced_config
        from repro.models import init_params

        cfg = reduced_config("qwen1.5-0.5b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        q = quantize_params(params)
        # Embedding is large -> quantized; norms stay float.
        assert "__q" in q["embed"]["embed"]
        assert not isinstance(q["final_norm"], dict)


# ---------------------------------------------------------------------------
# train loop end-to-end (subprocess; exercises checkpoint + resume + signals)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_train_loop_resume(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    base = [
        sys.executable, "-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
        "--reduced", "--global-batch", "4", "--seq-len", "64",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "4", "--log-every", "2",
    ]
    r1 = subprocess.run(
        base + ["--steps", "8"], env=env, capture_output=True, text=True,
        timeout=600, cwd=str(REPO),
    )
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(
        base + ["--steps", "12", "--resume"], env=env, capture_output=True,
        text=True, timeout=600, cwd=str(REPO),
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 8" in r2.stdout
