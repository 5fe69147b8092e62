"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref, executed under interpret=True on CPU."""
import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.quant8 import quant_dequant_fwd
from repro.kernels.ref import (flash_attention_ref, quant_dequant_ref,
                               selective_scan_ref)
from repro.kernels.selective_scan import selective_scan_fwd

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
# The fused forward accumulates h in fp32 VMEM scratch, so fp32 outputs
# track the jnp oracle tighter than the generic kernel tolerance.
SS_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kh,hd,bq,bk,causal,window,prefix",
    [
        pytest.param(1, 128, 128, 4, 4, 32, 64, 64, True, 0, 0,
                     id="1-128-128-4-4-32-64-64"),     # MHA square
        pytest.param(2, 128, 256, 8, 2, 64, 64, 128, True, 0, 0,
                     marks=pytest.mark.slow,
                     id="2-128-256-8-2-64-64-128"),    # GQA, rectangular
        pytest.param(1, 256, 128, 6, 3, 16, 128, 64, True, 0, 0,
                     marks=pytest.mark.slow,
                     id="1-256-128-6-3-16-128-64"),    # odd head count
        pytest.param(2, 64, 64, 2, 1, 128, 64, 64, True, 0, 0,
                     marks=pytest.mark.slow,
                     id="2-64-64-2-1-128-64-64"),      # MQA, wide head
        # the ViT's class: no mask, one block spanning a sequence that is
        # no multiple of 8, every head in one grid step
        pytest.param(2, 34, 34, 12, 12, 64, 512, 512, False, 0, 0,
                     id="vit-2-34-34-12-12-64-nomask"),
        # hymba's classes at a small size: GQA, 7 x 7 blocks over a
        # sequence that is no block multiple, so whole tiles are skipped;
        # windowed with a visible prefix, and causal alone
        pytest.param(2, 400, 400, 4, 2, 32, 64, 64, True, 96, 16,
                     id="2-400-400-4-2-32-64-64-window96-prefix16"),
        pytest.param(2, 400, 400, 4, 2, 32, 64, 64, True, 0, 0,
                     id="2-400-400-4-2-32-64-64-causal"),
    ])
def test_flash_vs_ref_shapes(b, sq, sk, h, kh, hd, bq, bk, causal, window,
                             prefix, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, sq, h, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kh, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kh, hd), dtype)
    qp = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk)).astype(jnp.int32)
    out = flash_attention_fwd(q, k, v, qp, kp, causal=causal, window=window,
                              prefix=prefix, block_q=bq, block_k=bk,
                              interpret=True)
    ref = flash_attention_ref(q, k, v, qp, kp, causal=causal, window=window,
                              prefix=prefix)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("s,causal,window,prefix,block,layout,want", [
    (400, True, 96, 16, 64, "contiguous", None),
    (400, True, 0, 0, 64, "contiguous", None),
    (400, False, 96, 16, 64, "contiguous", None),
    (400, False, 0, 0, 64, "contiguous", None),   # masked by padding only
    (400, True, 96, 16, 64, "shuffled", None),
    (400, True, 96, 16, 64, "ragged", None),
    # hymba-1.5b as published: 4224 positions, blocks of 256 (its plan)
    (4224, True, 1024, 128, 256, "contiguous", (289, 87)),
    (4224, True, 0, 0, 256, "contiguous", (289, 153)),
])
def test_flash_tile_skip_matches_mask(s, causal, window, prefix, block,
                                      layout, want):
    """The kernels' skip test keeps every tile in which the materialized
    mask keeps a pair, and (contiguous positions) no other; the tile count
    that `attn/impl` reports matches it."""
    b = 2
    pos = np.broadcast_to(np.arange(s), (b, s))
    valid = None
    if layout == "shuffled":       # any positions: the test stays sound
        pos = np.stack([np.random.default_rng(i).permutation(s)
                        for i in range(b)])
    elif layout == "ragged":       # decode-style: keys past 300 unfilled
        valid = jnp.asarray(pos < 300)
    pos = jnp.asarray(pos, jnp.int32)
    ones = jnp.zeros((b, s, 1, 1))
    _, _, _, qp, kp, kv = fa._pad_inputs(ones, ones, ones, pos, pos, valid,
                                         block, block)
    n = qp.shape[1] // block
    bounds = np.asarray(fa._tile_bounds(qp, kp, kv, s, block, block,
                                        causal, window, prefix))
    (q_lo, q_hi, k_lo, k_hi, k_first, k_last, q_first,
     q_last) = np.split(bounds.reshape(b, 8 * n), 8, axis=1)
    live = np.broadcast_to(fa._may_see(
        q_lo[:, :, None], q_hi[:, :, None], k_lo[:, None], k_hi[:, None],
        causal, window, prefix), (b, n, n))
    # the clamped index maps never turn a live step away from its block
    idx = np.arange(n)
    assert not (live & ((idx < k_first[:, :, None])
                        | (idx > k_last[:, :, None]))).any()
    assert not (live & ((idx[:, None] < q_first[:, None])
                        | (idx[:, None] > q_last[:, None]))).any()
    qp, kp = np.asarray(qp)[:, :, None], np.asarray(kp)[:, None, :]
    ok = np.asarray(kv)[:, None, :] & (np.arange(n * block) < s)[None, :,
                                                                  None]
    if causal:
        ok = ok & (kp <= qp)
    if window:
        ok = ok & (((qp - kp) < window) | (kp < prefix))
    seen = ok.reshape(b, n, block, n, block).any(axis=(2, 4))
    assert not (seen & ~live).any()          # never skips a visible pair
    if layout == "contiguous":
        np.testing.assert_array_equal(live, seen)
        # hymba's widths leave `_plan` its 256-row blocks; the small
        # case names its blocks
        h, kh, hd, itemsize, blocks = (25, 5, 64, 2, {}) if block == 256 \
            else (4, 2, 32, 4, dict(block_q=block, block_k=block))
        counts = fa.tile_counts(s, s, h, kh, hd, itemsize, causal=causal,
                                window=window, prefix=prefix, **blocks)
        assert counts == (n * n, int(seen[0].sum()))
        assert want in (None, counts)


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 32)])
def test_flash_masks(causal, window):
    key = jax.random.PRNGKey(3)
    b, s, h, kh, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kh, hd))
    p = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    out = flash_attention_fwd(q, k, v, p, p, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, p, p, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (96, 96, 64, 64),        # seq not a block multiple
    (70, 130, 64, 64),       # both axes odd
    pytest.param(3840, 0, 512, 512,
                 marks=pytest.mark.slow),  # VLM text region, sk = sq
])
def test_flash_non_multiple_seq_lengths(sq, sk, bq, bk):
    """Non-block-multiple sequence lengths run via grid padding + k_valid
    masking instead of crashing the kernel path."""
    sk = sk or sq
    key = jax.random.PRNGKey(6)
    b, h, kh, hd = 1, 2, 2, 16
    q = jax.random.normal(key, (b, sq, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kh, hd))
    qp = (jnp.arange(sq)[None] + (sk - sq)).astype(jnp.int32)
    kp = jnp.arange(sk)[None].astype(jnp.int32)
    out = flash_attention_fwd(q, k, v, qp, kp, causal=True,
                              block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, qp, kp, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_lse_residual_matches_ref():
    """The forward's LSE output equals the materialized logsumexp of the
    masked scores (the backward's correctness hinges on this)."""
    key = jax.random.PRNGKey(8)
    b, s, h, hd = 2, 128, 4, 32
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd))
    p = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    _, lse = flash_attention_fwd(q, k, v, p, p, causal=True, block_q=64,
                                 block_k=64, return_lse=True, interpret=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    ref = jax.nn.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=2e-5)


def test_flash_kv_validity_mask():
    """Decode layout: only the first L slots of the cache are populated."""
    key = jax.random.PRNGKey(4)
    b, sq, sk, h, kh, hd = 1, 64, 128, 2, 2, 32
    valid_len = 70
    q = jax.random.normal(key, (b, sq, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kh, hd))
    qp = (jnp.arange(sq)[None] + valid_len - sq).astype(jnp.int32) \
        * jnp.ones((b, 1), jnp.int32)
    kp = jnp.where(jnp.arange(sk) < valid_len, jnp.arange(sk),
                   -1)[None].astype(jnp.int32) * jnp.ones((b, 1), jnp.int32)
    kv = (kp >= 0)
    out = flash_attention_fwd(q, k, v, qp, kp, causal=True, k_valid=kv,
                              block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, qp, kp, causal=True, k_valid=kv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# selective scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,di,ds,chunk,bd", [
    (1, 32, 16, 4, 8, 16),
    pytest.param(2, 64, 32, 8, 16, 16, marks=pytest.mark.slow),
    pytest.param(1, 128, 64, 16, 32, 32, marks=pytest.mark.slow),
])
def test_selective_scan_vs_ref(b, s, di, ds, chunk, bd, dtype):
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (b, s, di)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                            (b, s, di))) * 0.1).astype(dtype)
    bi = jax.random.normal(jax.random.fold_in(key, 2), (b, s, ds)).astype(dtype)
    ci = jax.random.normal(jax.random.fold_in(key, 3), (b, s, ds)).astype(dtype)
    al = jnp.log(jnp.abs(jax.random.normal(jax.random.fold_in(key, 4),
                                           (di, ds))) + 0.5)
    y, h = selective_scan_fwd(x, dt, bi, ci, al, chunk=chunk, block_d=bd,
                              interpret=True)
    yr, hr = selective_scan_ref(x, dt, bi, ci, al)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=SS_TOL[dtype], rtol=SS_TOL[dtype])
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=SS_TOL[dtype], rtol=SS_TOL[dtype])


def test_selective_scan_h0_and_grad():
    key = jax.random.PRNGKey(7)
    b, s, di, ds = 2, 32, 16, 4
    x = jax.random.normal(key, (b, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, di))) * 0.1
    bi = jax.random.normal(jax.random.fold_in(key, 2), (b, s, ds))
    ci = jax.random.normal(jax.random.fold_in(key, 3), (b, s, ds))
    al = jnp.log(jnp.abs(jax.random.normal(jax.random.fold_in(key, 4),
                                           (di, ds))) + 0.5)
    h0 = jax.random.normal(jax.random.fold_in(key, 5), (b, di, ds)) * 0.3
    y, h = ops.selective_scan(x, dt, bi, ci, al, h0, 8)
    yr, hr = selective_scan_ref(x, dt, bi, ci, al, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)

    g = jax.grad(lambda x: ops.selective_scan(x, dt, bi, ci, al,
                                              None, 8)[0].sum())(x)
    gr = jax.grad(lambda x: selective_scan_ref(x, dt, bi, ci,
                                               al)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5)


# ---------------------------------------------------------------------------
# quant8


@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(
    rows=st.integers(1, 300),
    d=st.sampled_from([32, 128, 384]),
    seed=st.integers(0, 1000),
)
def test_quant_dequant_property(rows, d, seed):
    """Kernel == oracle on arbitrary row counts (incl. ragged padding),
    and the int8 reconstruction error is bounded by scale/2 per element."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d))
    y = quant_dequant_fwd(x, block_rows=64, interpret=True)
    ref = quant_dequant_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)
    scale = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(np.asarray(y - x)) <= scale / 2 + 1e-7)


def test_quant_straight_through_grad():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    g = jax.grad(lambda x: (ops.quant_dequant(x) * 3.0).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 3.0 * np.ones((8, 64)),
                               atol=1e-6)
