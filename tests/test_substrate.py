"""Substrate tests: optimizer, data, checkpointing, fault tolerance,
gradient compression, train/serve loops."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import REGISTRY
from repro.configs.base import ShapeSpec
from repro.data.pipeline import DataConfig, SyntheticLM, batches_for
from repro.distributed.compression import (
    ErrorFeedback, dequantize_int8, quantize_int8)
from repro.optim.adamw import AdamW, OptimizerConfig, schedule
from repro.runtime.train_loop import FaultInjected, TrainLoopConfig, train

SMOKE_SHAPE = ShapeSpec("smoke", "train", 64, 4)


# ---------------------------------------------------------------- optimizer
def test_adamw_converges_on_quadratic():
    opt = AdamW(OptimizerConfig(lr=0.1, warmup_steps=5, total_steps=200,
                                weight_decay=0.0, clip_norm=10.0))
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, gnorm = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_schedule_warmup_and_decay():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(schedule(cfg, jnp.int32(10))) <= 1.0
    assert float(schedule(cfg, jnp.int32(100))) == pytest.approx(
        cfg.min_lr_frac, rel=1e-3)


def test_grad_clip_bounds_update():
    opt = AdamW(OptimizerConfig(lr=0.1, clip_norm=1.0, warmup_steps=0,
                                total_steps=10))
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, gnorm = opt.update({"w": jnp.full(3, 1e6)}, state, params)
    assert float(gnorm) > 1e5  # reported raw norm


# --------------------------------------------------------------------- data
def test_data_determinism_and_restart():
    lm = SyntheticLM(DataConfig(seed=7, vocab=100, batch=4, seq_len=16))
    b5 = lm.batch_at(5)
    b5_again = lm.batch_at(5)
    np.testing.assert_array_equal(b5["tokens"], b5_again["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b5["tokens"][:, 1:], b5["labels"][:, :-1])


def test_batches_for_adds_modality_stubs():
    cfg = REGISTRY["whisper-tiny"].reduced()
    b = next(batches_for(cfg, SMOKE_SHAPE))
    assert b["audio_embeds"].shape == (4, cfg.enc_frames, cfg.d_model)
    cfg = REGISTRY["qwen2-vl-7b"].reduced()
    b = next(batches_for(cfg, SMOKE_SHAPE))
    assert b["vision"].shape == (4, cfg.vision_patches, cfg.d_model)
    assert b["tokens"].shape[1] == SMOKE_SHAPE.seq_len - cfg.vision_patches


# -------------------------------------------------------------- checkpoints
def test_checkpointer_roundtrip_retention_latest():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        state = {"a": jnp.arange(4.0), "nested": {"b": jnp.ones((2, 2))},
                 "t": (jnp.zeros(1), jnp.ones(1))}
        for step in (1, 2, 3):
            ck.save(step, state)
        assert ck.all_steps() == [2, 3]       # retention
        assert ck.latest_step() == 3
        restored, manifest = ck.restore(state)
        np.testing.assert_array_equal(restored["a"], state["a"])
        np.testing.assert_array_equal(restored["t"][1], state["t"][1])
        assert manifest["step"] == 3


def test_checkpointer_atomicity_no_partial_dirs():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=3)
        ck.save(1, {"x": jnp.ones(8)})
        names = set(os.listdir(d))
        assert not any(n.startswith("tmp.") for n in names)


# -------------------------------------------------------------- compression
def test_int8_quant_roundtrip_error_bounded():
    g = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q, s = quantize_int8(g)
    err = jnp.abs(dequantize_int8(q, s) - g)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_signal():
    """Sum of applied (compressed) grads + residual == sum of true grads."""
    ef = ErrorFeedback()
    params = {"w": jnp.zeros(64)}
    errors = ef.init(params)
    true_sum = jnp.zeros(64)
    applied_sum = jnp.zeros(64)
    for i in range(20):
        g = {"w": jax.random.normal(jax.random.PRNGKey(i), (64,)) * 0.1}
        true_sum = true_sum + g["w"]
        gq, errors = ef.apply(g, errors)
        applied_sum = applied_sum + gq["w"]
    drift = applied_sum + errors["w"] - true_sum
    np.testing.assert_allclose(np.asarray(drift), 0.0, atol=1e-4)


# --------------------------------------------------------------- train loop
def test_train_loss_decreases():
    with tempfile.TemporaryDirectory() as d:
        cfg = REGISTRY["deepseek-7b"].reduced()
        out = train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=15, ckpt_every=50, ckpt_dir=d))
        assert out["final_loss"] < out["first_loss"]


def test_train_fault_injection_and_recovery():
    with tempfile.TemporaryDirectory() as d:
        cfg = REGISTRY["deepseek-7b"].reduced()
        loop = TrainLoopConfig(steps=12, ckpt_every=4, ckpt_dir=d,
                               fail_at_step=9)
        with pytest.raises(FaultInjected):
            train(cfg, SMOKE_SHAPE, loop)
        # auto-resume from the last checkpoint (step 8) and finish
        loop2 = TrainLoopConfig(steps=12, ckpt_every=4, ckpt_dir=d)
        out = train(cfg, SMOKE_SHAPE, loop2)
        assert out["start_step"] == 8
        assert out["steps"] == 12


def test_train_restart_is_deterministic():
    """Run 10 straight vs 5+resume(10): same final loss (same data path)."""
    cfg = REGISTRY["deepseek-7b"].reduced()
    with tempfile.TemporaryDirectory() as d1:
        full = train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=10, ckpt_every=100, ckpt_dir=d1, seed=3))
    with tempfile.TemporaryDirectory() as d2:
        train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=5, ckpt_every=5, ckpt_dir=d2, seed=3))
        resumed = train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=10, ckpt_every=5, ckpt_dir=d2, seed=3))
    assert resumed["final_loss"] == pytest.approx(full["final_loss"],
                                                  rel=1e-4)


def test_train_with_compression_converges():
    with tempfile.TemporaryDirectory() as d:
        cfg = REGISTRY["deepseek-7b"].reduced()
        out = train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=15, ckpt_every=50, ckpt_dir=d, compress_grads=True))
        assert out["final_loss"] < out["first_loss"]


def test_train_autotune_respects_budget_and_persists():
    with tempfile.TemporaryDirectory() as d:
        cfg = REGISTRY["deepseek-7b"].reduced()
        out = train(cfg, SMOKE_SHAPE, TrainLoopConfig(
            steps=20, ckpt_every=10, ckpt_dir=d, autotune=True,
            tune_max_overhead=0.5, tune_invest=0.5))
        stats = out["autotune"]
        assert stats["regenerations"] >= 1
        assert os.path.exists(os.path.join(d, "tuned.json"))
        from repro.core import TunedRegistry
        reg = TunedRegistry.load(os.path.join(d, "tuned.json"))
        assert len(reg) >= 1


# --------------------------------------------------------------- serve loop
def test_serve_generates_tokens():
    from repro.runtime.serve_loop import ServeConfig, generate
    cfg = REGISTRY["deepseek-7b"].reduced()
    batch = {"tokens": jnp.ones((2, 12), jnp.int32)}
    out = generate(cfg, batch, ServeConfig(max_new_tokens=6))
    assert out["tokens"].shape == (2, 6)
    assert out["decode_tokens_per_s"] > 0
    assert out["logits_finite"] is True


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own setting and the
    launchers set no other; without it the cache is <checkout>/.jax_cache,
    a path fixed by the package's location."""
    import os

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_rwkv_state_decode():
    from repro.runtime.serve_loop import ServeConfig, generate
    cfg = REGISTRY["rwkv6-1.6b"].reduced()
    batch = {"tokens": jnp.ones((2, 12), jnp.int32)}
    out = generate(cfg, batch, ServeConfig(max_new_tokens=5))
    assert out["tokens"].shape == (2, 5)
