"""Split/assemble, aggregation, fusion, losses, compression."""
import dataclasses

import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config, reduced
from repro.core import aggregation, compression, fusion, losses, split
from repro.models import layers, model as M


def test_split_segments_boundaries():
    cfg = reduced(get_config("hymba-1.5b"), num_layers=6,
                  global_layers=(0, 3, 5))
    segs = M.body_segments(cfg)
    assert sum(s.count for s in segs) == 6
    f, t = split.split_segments(segs, 4)
    assert sum(s.count for s in f) == 4
    assert sum(s.count for s in t) == 2


@pytest.mark.parametrize("arch,tb", [
    ("minitron-4b", 1),
    pytest.param("qwen2-moe-a2.7b", 2, marks=pytest.mark.slow),
    pytest.param("whisper-tiny", 1, marks=pytest.mark.slow),
])
def test_assemble_full_params_matches_split_forward(arch, tb):
    """[F_C ; F_S] reassembly (paper Sec. 3.3): running the assembled full
    model gives the same forward as running the split trees (frozen prefix
    + trainable server suffix)."""
    from repro.core.mpsl import _run_body
    cfg = reduced(get_config(arch))
    mp = MPSLConfig(n_clients=2, trainable_blocks=tb)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32", frozen_dtype="float32")
    key = jax.random.PRNGKey(0)
    params, frozen, plan = split.init_mpsl_lm(key, cfg, run)
    full = split.assemble_full_params(params, frozen, plan)

    b, s = 2, 8
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    pos = layers.positions_from_shape(b, s)

    # forward via the assembled tree
    h = M.embed_tokens(full, tokens, cfg, dtype=jnp.float32)
    enc = None
    if cfg.encoder_layers:
        fe = jnp.zeros((b, cfg.encoder_seq, cfg.d_model))
        enc = M.run_encoder(full, fe, cfg, remat=False)
    hh, _, _ = M.forward_body(full, h, cfg, positions=pos,
                              enc_out=enc, remat=False)
    l_full = M.lm_logits(full, hh, cfg)

    # forward via the split trees (frozen prefix + server suffix)
    h2 = M.embed_tokens(frozen, tokens, cfg, dtype=jnp.float32)
    enc2 = None
    if cfg.encoder_layers:
        fe = jnp.zeros((b, cfg.encoder_seq, cfg.d_model))
        enc2 = M.run_encoder(frozen, fe, cfg, remat=False)
    hh2, _ = _run_body(frozen, params["server"], cfg, h2, pos, enc_out=enc2)
    l_split = M.lm_logits(params["server"], hh2, cfg) \
        if "lm_head" in params["server"] else M.lm_logits(frozen, hh2, cfg)
    np.testing.assert_allclose(np.asarray(l_full), np.asarray(l_split),
                               atol=2e-5)


def test_fedavg_heads_weighted():
    heads = {"a": jnp.stack([jnp.ones((2,)), 3 * jnp.ones((2,))])}
    avg = aggregation.fedavg_heads(heads)
    np.testing.assert_allclose(np.asarray(avg["a"]), 2.0)
    w = jnp.array([3.0, 1.0])
    avg_w = aggregation.fedavg_heads(heads, w)
    np.testing.assert_allclose(np.asarray(avg_w["a"]), 1.5)


def test_broadcast_head_shapes():
    head = {"a": jnp.arange(4.0)}
    bank = aggregation.broadcast_head(head, 5)
    assert bank["a"].shape == (5, 4)


# ---------------------------------------------------------------------------
# Fusion


def test_fusion_early_late_shapes():
    tok = {"vision": jnp.ones((3, 10, 8)), "text": jnp.ones((3, 5, 8))}
    early = fusion.fuse_early(tok)
    assert early.shape == (3, 15, 8)
    late = fusion.fuse_late(tok)
    assert late.shape == (3, 2, 8)
    assert fusion.gap(early).shape == (3, 8)


def test_fusion_stacked_layout():
    tok = {"vision": jnp.ones((2, 3, 10, 8)), "text": jnp.ones((2, 3, 5, 8))}
    assert fusion.fuse_early(tok).shape == (2, 3, 15, 8)


# ---------------------------------------------------------------------------
# Losses


@pytest.mark.slow
@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(t=st.integers(3, 200), chunk=st.sampled_from([16, 64, 512]),
                  seed=st.integers(0, 100))
def test_chunked_ce_equals_direct(t, chunk, seed):
    key = jax.random.PRNGKey(seed)
    d, v = 16, 50
    h = jax.random.normal(key, (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)
    out = losses.chunked_softmax_xent(h, w, labels, chunk=chunk)
    direct = losses.softmax_xent(h @ w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               atol=1e-4, rtol=1e-4)


def test_chunked_ce_gradients_match():
    key = jax.random.PRNGKey(0)
    t, d, v = 37, 8, 20
    h = jax.random.normal(key, (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)
    g1 = jax.grad(lambda h: losses.chunked_softmax_xent(
        h, w, labels, chunk=16).mean())(h)
    g2 = jax.grad(lambda h: losses.softmax_xent(h @ w, labels).mean())(h)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_contrastive_loss_prefers_aligned():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (16, 8))
    aligned = float(losses.contrastive_loss(a, a).mean())
    shuffled = float(losses.contrastive_loss(a, jnp.roll(a, 1, 0)).mean())
    assert aligned < shuffled


def test_recall_at_k():
    a = jnp.eye(5)
    assert float(losses.recall_at_k(a, a, k=1)) == 1.0
    assert float(losses.recall_at_k(a, jnp.roll(a, 1, 0), k=1)) == 0.0


# ---------------------------------------------------------------------------
# Compression


def test_compression_bounded_error_and_ste():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 128))
    y = compression.compress_activations(x, None)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    assert float(jnp.max(jnp.abs(y - x) / scale)) <= 0.5 + 1e-5
    g = jax.grad(lambda x: (compression.compress_activations(x, None)
                            * 2.0).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 2.0, atol=1e-6)


def test_stochastic_rounding_unbiased():
    key = jax.random.PRNGKey(1)
    x = jnp.full((1, 64), 0.31)        # sits between int8 levels
    keys = jax.random.split(key, 512)
    ys = jax.vmap(lambda k: compression.compress_activations(x, k))(keys)
    assert abs(float(ys.mean()) - 0.31) < 5e-3


def test_gradient_compression_applies_to_cotangent():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (8, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (8, 64))
    g_id = jax.grad(lambda x: (x * w).sum())(x)

    def f(x):
        return (compression.compress_gradients(x, key) * w).sum()
    g_q = jax.grad(f)(x)
    # cotangent was quantized: equal up to int8 resolution, not bitwise
    scale = float(jnp.max(jnp.abs(w))) / 127.0
    assert float(jnp.max(jnp.abs(g_q - g_id))) <= 1.5 * scale
    assert float(jnp.max(jnp.abs(g_q - g_id))) > 0.0


def test_compressed_bytes_accounting():
    n = compression.compressed_bytes((4, 16, 128))
    assert n == 4 * 16 * 128 + 4 * 16 * 4


@pytest.mark.parametrize("shape,bits,expect", [
    # int8 uplink: 1 byte/elem + f32 scale per token (core.costs act_bytes=1)
    ((4, 16, 128), 8, 4 * 16 * 128 + 4 * 16 * 4),
    # int4: half-byte payload, same per-token scale overhead
    ((4, 16, 128), 4, 4 * 16 * 128 // 2 + 4 * 16 * 4),
    # bf16-equivalent wire size
    ((4, 16, 128), 16, 4 * 16 * 128 * 2 + 4 * 16 * 4),
    # sub-byte payload rounds UP to whole bytes on the wire
    ((3, 33), 4, (3 * 33 * 4 + 7) // 8 + 3 * 4),
])
def test_compressed_bytes_arbitrary_bits(shape, bits, expect):
    """Wire sizes pinned for the bit widths the cost model quotes."""
    assert compression.compressed_bytes(shape, bits=bits) == expect
