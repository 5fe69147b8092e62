"""Property tests for the selective-scan algebra.

The fused backward's correctness rests on two algebraic facts about the
recurrence h_t = a_t h_{t-1} + b_t:

  * associativity — scanning segment-by-segment while carrying the
    boundary state equals the one-shot scan for ANY segmentation (this is
    exactly what the kernel's chunk checkpoints exploit);
  * h0 linearity — the map h0 -> (y, h_final) is affine, so
    scan(x, h0) == scan(x, 0) + scan(0, h0) with dt/A held fixed (the
    property the pre-fusion jnp ``_h0_propagation`` term relied on, kept
    here as the algebraic regression even though the kernel now seeds h0
    directly).

Shapes stay tiny on purpose: these check algebra via the jnp reference
(plus one kernel-path segmentation case), not kernel tilings — those live
in test_kernel_grads.py / test_kernels.py.
"""
import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ref import selective_scan_ref
from repro.models.mamba import chunked_selective_scan

B, DI, DS = 2, 8, 4


def _inputs(seed, s):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (B, s, DI)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, s, DI))) * 0.1
    bi = jax.random.normal(jax.random.fold_in(key, 2), (B, s, DS))
    ci = jax.random.normal(jax.random.fold_in(key, 3), (B, s, DS))
    al = jnp.log(jnp.abs(jax.random.normal(jax.random.fold_in(key, 4),
                                           (DI, DS))) + 0.5)
    h0 = jax.random.normal(jax.random.fold_in(key, 5), (B, DI, DS)) * 0.3
    return x, dt, bi, ci, al, h0


@hypothesis.settings(max_examples=3, deadline=None)
@hypothesis.given(seed=st.integers(0, 10_000),
                  splits=st.lists(st.integers(1, 12), min_size=1, max_size=4),
                  use_h0=st.booleans())
def test_segmented_scan_equals_one_shot(seed, splits, use_h0):
    """Associativity of the checkpointed recurrence: scanning each segment
    of a random split while carrying h across boundaries == one shot."""
    s = sum(splits)
    x, dt, bi, ci, al, h0 = _inputs(seed, s)
    h = h0 if use_h0 else None
    ys = []
    t0 = 0
    for seg in splits:
        sl = slice(t0, t0 + seg)
        y, h = selective_scan_ref(x[:, sl], dt[:, sl], bi[:, sl], ci[:, sl],
                                  al, h)
        ys.append(y)
        t0 += seg
    y_ref, h_ref = selective_scan_ref(x, dt, bi, ci, al,
                                      h0 if use_h0 else None)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(y_ref), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


@hypothesis.settings(max_examples=3, deadline=None)
@hypothesis.given(seed=st.integers(0, 10_000),
                  chunk=st.sampled_from([1, 3, 8, 16, 64]),
                  use_h0=st.booleans())
def test_chunked_scan_equals_one_shot(seed, chunk, use_h0):
    """The jnp chunked scan (the kernel's structural mirror) is invariant
    to the chunk size, including non-divisor chunks that hit padding."""
    s = 24
    x, dt, bi, ci, al, h0 = _inputs(seed, s)
    h = h0 if use_h0 else None
    y_c, h_c = chunked_selective_scan(x, dt, bi, ci, al, h, chunk=chunk)
    y_r, h_r = selective_scan_ref(x, dt, bi, ci, al, h)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_r),
                               atol=1e-5, rtol=1e-5)


@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(seed=st.integers(0, 10_000))
def test_h0_linearity(seed):
    """scan(x, h0) == scan(x, 0) + scan(0, h0): the recurrence is affine
    in (x-drive, h0) for fixed dt/A, so the h0 contribution separates —
    the identity the pre-fusion wrapper's propagation term was built on."""
    s = 16
    x, dt, bi, ci, al, h0 = _inputs(seed, s)
    zeros = jnp.zeros_like(x)
    y_full, h_full = selective_scan_ref(x, dt, bi, ci, al, h0)
    y_x, h_x = selective_scan_ref(x, dt, bi, ci, al)
    y_h, h_h = selective_scan_ref(zeros, dt, bi, ci, al, h0)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_x + y_h),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h_x + h_h),
                               atol=1e-5, rtol=1e-5)


def test_segmented_scan_equals_one_shot_kernel_path():
    """One kernel-path segmentation case: resuming ops.selective_scan from
    its own h_final (the decode/prefill resume pattern) == one shot."""
    x, dt, bi, ci, al, h0 = _inputs(3, 32)
    y1, h1 = ops.selective_scan(x[:, :16], dt[:, :16], bi[:, :16],
                                ci[:, :16], al, h0, 8)
    y2, h2 = ops.selective_scan(x[:, 16:], dt[:, 16:], bi[:, 16:],
                                ci[:, 16:], al, h1, 8)
    y_ref, h_ref = selective_scan_ref(x, dt, bi, ci, al, h0)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_ref),
        atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)
