"""Per-architecture smoke tests (deliverable f): a REDUCED same-family
config runs one forward and one MPSL train step on CPU with finite
outputs and the right shapes. Full configs run on the chip (`chipbench/`,
`chip_smoke.py`); tests/test_sharding_rules.py resolves their shardings.

Tiering: tier-1 keeps one representative arch per code path (dense /
ssm / encoder-decoder); the full per-arch sweep and the decode-vs-full
comparisons are `slow` (several seconds of jit each)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import (MPSLConfig, RunConfig, SHAPES, get_config,
                           list_archs, reduced)
from repro.core import mpsl, split
from repro.models import layers, model as M
from repro.optim import schedules

ARCHS = list_archs()


def _tiered(archs, fast):
    """Parametrize: archs in ``fast`` run in tier-1, the rest are slow."""
    return [a if a in fast else pytest.param(a, marks=pytest.mark.slow)
            for a in archs]


def _batch_for(cfg, key, n, bn, s):
    batch = {
        "tokens": jax.random.randint(key, (n, bn, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, bn, s), 0, cfg.vocab_size),
        "mask": jnp.ones((n,), jnp.float32),
    }
    if cfg.family == "vlm":
        p = 4
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            key, (n, bn, p, cfg.d_model))
    if cfg.family == "audio":
        batch["frame_embeds"] = 0.02 * jax.random.normal(
            key, (n, bn, cfg.encoder_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize(
    "arch", _tiered(ARCHS, {"minitron-4b", "falcon-mamba-7b",
                            "whisper-tiny"}))
def test_forward_shapes_and_finite(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = M.init_lm(key, cfg)
    b, s = 2, 16
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    pos = layers.positions_from_shape(b, s)
    h = M.embed_tokens(params, tokens, cfg, dtype=jnp.float32)
    enc = None
    ckv = None
    if cfg.encoder_layers:
        fe = 0.02 * jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model))
        enc = M.run_encoder(params, fe, cfg, remat=False)
        ckv = M.compute_cross_kv_stacked(params, enc, cfg)
    hh, _, aux = M.forward_body(params, h, cfg, positions=pos, enc_out=enc,
                                cross_kv=ckv, remat=False)
    logits = M.lm_logits(params, hh, cfg)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", _tiered(ARCHS, {"minitron-4b"}))
def test_mpsl_train_step(arch):
    cfg = reduced(get_config(arch))
    mp = MPSLConfig(n_clients=2, trainable_blocks=1, head_adapter_rank=4)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32")
    key = jax.random.PRNGKey(1)
    params, frozen, plan = split.init_mpsl_lm(key, cfg, run)
    loss_fn = mpsl.make_lm_loss(cfg, run)
    step = jax.jit(mpsl.make_train_step(loss_fn, run,
                                        schedules.constant(1e-3)))
    state = mpsl.init_state(params, frozen)
    batch = _batch_for(cfg, key, n=2, bn=2, s=16)
    l0 = None
    for _ in range(3):
        state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        if l0 is None:
            l0 = float(metrics["loss"])
    assert float(metrics["loss"]) < l0, "loss should decrease on 3 steps"


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["minitron-4b", "falcon-mamba-7b",
                                  "hymba-1.5b", "qwen3-moe-235b-a22b",
                                  "whisper-tiny", "qwen2-vl-72b"])
def test_decode_matches_full_forward(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(2)
    params = M.init_lm(key, cfg)
    b, s = 2, 12
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    pos = layers.positions_from_shape(b, s)
    h = M.embed_tokens(params, tokens, cfg, dtype=jnp.float32)
    enc = None
    ckv = None
    if cfg.encoder_layers:
        fe = 0.02 * jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model))
        enc = M.run_encoder(params, fe, cfg, remat=False)
        ckv = M.compute_cross_kv_stacked(params, enc, cfg)
    full, _, _ = M.forward_body(params, h, cfg, positions=pos, enc_out=enc,
                                cross_kv=ckv, remat=False)
    cache = M.init_body_cache(cfg, b, cache_len=s, dtype=jnp.float32)
    outs = []
    for t in range(s):
        ht = M.embed_tokens(params, tokens[:, t:t + 1], cfg,
                            positions=pos[:, t:t + 1], dtype=jnp.float32)
        o, cache, _ = M.forward_body(params, ht, cfg,
                                     positions=pos[:, t:t + 1], cache=cache,
                                     enc_out=enc, cross_kv=ckv, remat=False)
        outs.append(o)
    inc = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(full - inc))) < 5e-5


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_analytic_matches_init(arch):
    cfg = reduced(get_config(arch))
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    actual = sum(x.size for x in jax.tree_util.tree_leaves(params))
    analytic = M.count_params_analytic(cfg)
    assert abs(actual - analytic) / max(actual, 1) < 0.02, \
        (arch, actual, analytic)
