"""Training-grade kernel validation: Pallas backward passes against
jax.vjp through the pure-jnp references, in interpret mode on CPU.

Covers the four fused-backward kernel families (flash attention, quant8
straight-through, fused softmax-xent, the checkpointed selective-scan
adjoint) across causal / windowed / GQA / MQA and odd
(non-block-multiple) shapes plus nontrivial (chunk, block_d) scan
tilings, and the memory-analysis acceptance checks: no [Sq, Sk]-, [T, V]-
or [B, S, di, ds]-shaped intermediate anywhere in the train-direction
jaxprs at production-like sequence lengths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, losses
from repro.kernels import ops
from repro.kernels.ref import (flash_attention_ref, quant_dequant_ref,
                               selective_scan_ref, softmax_xent_ref)

ATOL = 2e-4
BF16_TOL = 5e-2  # bf16 inputs and cotangents: test_flash_backward_bf16's
SS_ATOL = 1e-4   # fused scan adjoint vs reference VJP, fp32


def _qkv(key, b, sq, sk, h, kh, hd, dtype=jnp.float32):
    q = jax.random.normal(key, (b, sq, h, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kh, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kh, hd), dtype)
    qp = jnp.broadcast_to(jnp.arange(sq)[None] + (sk - sq),
                          (b, sq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk)).astype(jnp.int32)
    return q, k, v, qp, kp


# ---------------------------------------------------------------------------
# flash attention backward


def _case(*args, prefix=0, dtype=jnp.float32, slow=True, id=None):
    """A case of the backward sweep, named as before the dtype and the
    prefix joined."""
    return pytest.param(*args, prefix, dtype,
                        id=id or "-".join(map(str, args)),
                        marks=[pytest.mark.slow] if slow else [])


@pytest.mark.parametrize(
    "b,sq,sk,h,kh,hd,bq,bk,causal,window,prefix,dtype",
    [
        _case(1, 128, 128, 4, 4, 32, 64, 64, True, 0,
              slow=False),                 # MHA causal
        _case(2, 128, 256, 8, 2, 64, 64, 128, True, 0),   # GQA rectangular
        _case(1, 128, 128, 4, 2, 32, 64, 64, False, 0),   # full attention
        _case(2, 64, 64, 2, 1, 128, 64, 64, True, 32),    # MQA sliding window
        _case(1, 96, 96, 4, 2, 32, 64, 64, True, 0),      # Sq % block != 0
        _case(1, 70, 130, 6, 3, 16, 64, 64, True, 33),    # odd + window
        _case(1, 200, 456, 4, 4, 32, 128, 128, False, 0),  # odd, non-causal
        # the ViT's class: bf16, no mask, one block spanning a sequence
        # that is no multiple of 8, every head in one grid step
        _case(2, 34, 34, 12, 12, 64, 512, 512, False, 0, dtype=jnp.bfloat16,
              slow=False, id="vit-2-34-34-12-12-64-nomask-bf16"),
        # hymba's classes at a small size: GQA, 7 x 7 blocks over a
        # sequence that is no block multiple, so whole tiles are skipped
        # in all three kernels; windowed with a visible prefix, and causal
        _case(2, 400, 400, 4, 2, 32, 64, 64, True, 96, prefix=16,
              slow=False, id="2-400-400-4-2-32-64-64-True-96-prefix16"),
        _case(2, 400, 400, 4, 2, 32, 64, 64, True, 0, slow=False),
    ])
def test_flash_backward_matches_ref_vjp(b, sq, sk, h, kh, hd, bq, bk,
                                        causal, window, prefix, dtype):
    key = jax.random.PRNGKey(42)
    q, k, v, qp, kp = _qkv(key, b, sq, sk, h, kh, hd, dtype)
    tol = ATOL if dtype == jnp.float32 else BF16_TOL

    def f_ker(q, k, v):
        return ops.flash_attention(q, k, v, qp, kp, causal=causal,
                                   window=window, block_q=bq, block_k=bk,
                                   prefix=prefix)

    def f_ref(q, k, v):
        return flash_attention_ref(q, k, v, qp, kp, causal=causal,
                                   window=window, prefix=prefix)

    out_k, vjp_k = jax.vjp(f_ker, q, k, v)
    out_r, vjp_r = jax.vjp(f_ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)
    g = jax.random.normal(jax.random.fold_in(key, 3), out_k.shape, dtype)
    for name, dk_, dr_ in zip("dq dk dv".split(), vjp_k(g), vjp_r(g)):
        assert dk_.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(dk_, np.float32),
                                   np.asarray(dr_, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.slow
def test_flash_backward_kv_validity_mask_under_jit():
    """Decode/ragged layout: the k_valid mask is a TRACED array under jit;
    forward and backward must resolve the identical mask (regression for
    the mask living in static nondiff args)."""
    key = jax.random.PRNGKey(7)
    b, sq, sk, h, kh, hd = 1, 64, 128, 4, 2, 32
    valid_len = 70
    q, k, v, _, _ = _qkv(key, b, sq, sk, h, kh, hd)
    qp = (jnp.arange(sq)[None] + valid_len - sq).astype(jnp.int32) \
        * jnp.ones((b, 1), jnp.int32)
    kp = jnp.where(jnp.arange(sk) < valid_len, jnp.arange(sk),
                   -1)[None].astype(jnp.int32) * jnp.ones((b, 1), jnp.int32)
    kv = kp >= 0

    @jax.jit
    def grads_ker(q, k, v, kv):
        def f(q, k, v):
            return ops.flash_attention(q, k, v, qp, kp, causal=True,
                                       k_valid=kv, block_q=64, block_k=64)
        return jax.grad(lambda q, k, v: (f(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    def grads_ref(q, k, v, kv):
        def f(q, k, v):
            return flash_attention_ref(q, k, v, qp, kp, causal=True,
                                       k_valid=kv)
        return jax.grad(lambda q, k, v: (f(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    for name, a, r in zip("dq dk dv".split(), grads_ker(q, k, v, kv),
                          grads_ref(q, k, v, kv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=ATOL, rtol=ATOL, err_msg=name)


@pytest.mark.slow
def test_flash_backward_bf16():
    key = jax.random.PRNGKey(11)
    q, k, v, qp, kp = _qkv(key, 2, 128, 128, 4, 2, 32, jnp.bfloat16)

    def f_ker(q, k, v):
        return ops.flash_attention(q, k, v, qp, kp, causal=True,
                                   block_q=64, block_k=64)

    def f_ref(q, k, v):
        return flash_attention_ref(q, k, v, qp, kp, causal=True)

    g = jax.random.normal(key, q.shape[:2] + (4, 32)).astype(jnp.bfloat16)
    _, vjp_k = jax.vjp(f_ker, q, k, v)
    _, vjp_r = jax.vjp(f_ref, q, k, v)
    for name, a, r in zip("dq dk dv".split(), vjp_k(g), vjp_r(g)):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def _collect_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
        for param in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    param, is_leaf=lambda x: hasattr(x, "eqns")):
                if hasattr(sub, "eqns"):
                    _collect_avals(sub, out)
                elif hasattr(sub, "jaxpr"):
                    _collect_avals(sub.jaxpr, out)
    return out


def test_no_quadratic_intermediate_at_4k():
    """Acceptance: the fwd+bwd jaxpr of the kernel attention path holds no
    (4096, 4096)-shaped value anywhere (the blockwise kernels cap live
    intermediates at block_q x block_k)."""
    s, h, hd = 4096, 1, 64
    q = jax.ShapeDtypeStruct((1, s, h, hd), jnp.float32)
    p = jax.ShapeDtypeStruct((1, s), jnp.int32)

    def loss(q, k, v, qp, kp):
        return ops.flash_attention(q, k, v, qp, kp, causal=True).sum()

    jaxpr = jax.make_jaxpr(
        lambda q, k, v, qp, kp: jax.grad(loss, argnums=(0, 1, 2))(
            q, k, v, qp, kp))(q, q, q, p, p)
    shapes = _collect_avals(jaxpr.jaxpr, [])
    quadratic = [sh for sh in shapes
                 if sum(1 for d in sh if d >= s) >= 2]
    assert not quadratic, quadratic


# ---------------------------------------------------------------------------
# selective scan fused backward


def _scan_inputs(key, b, s, di, ds, dtype=jnp.float32):
    x = (jax.random.normal(key, (b, s, di)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                            (b, s, di))) * 0.1).astype(dtype)
    bi = jax.random.normal(jax.random.fold_in(key, 2), (b, s, ds)).astype(dtype)
    ci = jax.random.normal(jax.random.fold_in(key, 3), (b, s, ds)).astype(dtype)
    al = jnp.log(jnp.abs(jax.random.normal(jax.random.fold_in(key, 4),
                                           (di, ds))) + 0.5)
    h0 = jax.random.normal(jax.random.fold_in(key, 5), (b, di, ds)) * 0.3
    return x, dt, bi, ci, al, h0


@pytest.mark.parametrize("b,s,di,ds,chunk,bd,with_h0", [
    (1, 32, 16, 4, 8, 16, False),     # multi-chunk, single d-block
    pytest.param(2, 64, 32, 8, 16, 8, True,
                 marks=pytest.mark.slow),  # multi-chunk x multi-d-block
    pytest.param(1, 48, 24, 4, 48, 8, True,
                 marks=pytest.mark.slow),  # single chunk, d-blocked
    pytest.param(2, 64, 32, 8, 64, 32, False,
                 marks=pytest.mark.slow),  # degenerate tiling (nc = nd = 1)
])
def test_selective_scan_fused_backward_matches_ref_vjp(b, s, di, ds, chunk,
                                                       bd, with_h0):
    key = jax.random.PRNGKey(17)
    x, dt, bi, ci, al, h0 = _scan_inputs(key, b, s, di, ds)
    h0 = h0 if with_h0 else None

    def f_ker(x, dt, bi, ci, al):
        return ops.selective_scan(x, dt, bi, ci, al, h0, chunk, bd)

    def f_ref(x, dt, bi, ci, al):
        return selective_scan_ref(x, dt, bi, ci, al, h0)

    out_k, vjp_k = jax.vjp(f_ker, x, dt, bi, ci, al)
    out_r, vjp_r = jax.vjp(f_ref, x, dt, bi, ci, al)
    for name, a, r in zip(["y", "h_final"], out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=SS_ATOL, rtol=SS_ATOL, err_msg=name)
    gy = jax.random.normal(jax.random.fold_in(key, 6), out_k[0].shape)
    gh = jax.random.normal(jax.random.fold_in(key, 7), out_k[1].shape)
    for name, a, r in zip("dx ddt dB dC dA_log".split(),
                          vjp_k((gy, gh)), vjp_r((gy, gh))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=SS_ATOL, rtol=SS_ATOL, err_msg=name)


@pytest.mark.slow
def test_selective_scan_fused_backward_dh0():
    """The h0 cotangent (the carry after chunk 0's adjoint sweep) matches
    the reference VJP — this is the cut-layer gradient of a resumed scan."""
    key = jax.random.PRNGKey(19)
    b, s, di, ds = 2, 48, 16, 4
    x, dt, bi, ci, al, h0 = _scan_inputs(key, b, s, di, ds)

    def f_ker(h0):
        return ops.selective_scan(x, dt, bi, ci, al, h0, 16, 8)

    def f_ref(h0):
        return selective_scan_ref(x, dt, bi, ci, al, h0)

    gy = jax.random.normal(jax.random.fold_in(key, 6), (b, s, di))
    gh = jax.random.normal(jax.random.fold_in(key, 7), (b, di, ds))
    dk = jax.vjp(f_ker, h0)[1]((gy, gh))[0]
    dr = jax.vjp(f_ref, h0)[1]((gy, gh))[0]
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               atol=SS_ATOL, rtol=SS_ATOL)


@pytest.mark.slow
def test_selective_scan_fused_backward_bf16():
    key = jax.random.PRNGKey(23)
    b, s, di, ds = 2, 32, 16, 4
    x, dt, bi, ci, al, h0 = _scan_inputs(key, b, s, di, ds, jnp.bfloat16)

    def f_ker(x, dt, bi, ci):
        return ops.selective_scan(x, dt, bi, ci, al, None, 8, 8)[0]

    def f_ref(x, dt, bi, ci):
        return selective_scan_ref(x, dt, bi, ci, al)[0]

    g = jax.random.normal(key, (b, s, di)).astype(jnp.bfloat16)
    _, vjp_k = jax.vjp(f_ker, x, dt, bi, ci)
    _, vjp_r = jax.vjp(f_ref, x, dt, bi, ci)
    for name, a, r in zip("dx ddt dB dC".split(), vjp_k(g), vjp_r(g)):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=7e-2, rtol=7e-2, err_msg=name)


@pytest.mark.slow
def test_selective_scan_fused_backward_under_jit_grad():
    """The full custom_vjp path composes with jit + grad (the training
    loop's usage through apply_mamba)."""
    key = jax.random.PRNGKey(29)
    b, s, di, ds = 1, 32, 16, 4
    x, dt, bi, ci, al, _ = _scan_inputs(key, b, s, di, ds)

    @jax.jit
    def g_ker(x):
        return jax.grad(lambda x: ops.selective_scan(
            x, dt, bi, ci, al, None, 8)[0].sum())(x)

    g_ref = jax.grad(lambda x: selective_scan_ref(
        x, dt, bi, ci, al)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g_ker(x)), np.asarray(g_ref),
                               atol=SS_ATOL, rtol=SS_ATOL)


def _has_state_history(shapes, s, di, ds):
    """True when some aval holds distinct axes >= (s, di, ds) — i.e. a
    [B, S, di, ds]-sized state history."""
    thresholds = sorted((s, di, ds), reverse=True)
    for sh in shapes:
        if len(sh) < 3:
            continue
        dims = sorted(sh, reverse=True)[:3]
        if all(d >= t for d, t in zip(dims, thresholds)):
            return True
    return False


def test_no_state_history_intermediate_at_long_seq():
    """Acceptance: the fwd+bwd jaxpr of the fused scan holds nothing
    [B, S, di, ds]-sized at S = 2048 (the checkpointed-recompute backward
    caps live state at [chunk, block_d, ds] + the [B, nc, di, ds]
    boundary checkpoints); the legacy recompute-through-reference VJP
    DOES materialize the full state history (positive control)."""
    b, s, di, ds = 1, 2048, 256, 16
    x = jax.ShapeDtypeStruct((b, s, di), jnp.float32)
    bc = jax.ShapeDtypeStruct((b, s, ds), jnp.float32)
    al = jax.ShapeDtypeStruct((di, ds), jnp.float32)

    def make(bwd):
        def loss(x, dt, bi, ci, al):
            y, h = ops.selective_scan(x, dt, bi, ci, al, None, 256, 256, bwd)
            return y.sum() + h.sum()
        return jax.make_jaxpr(
            lambda x, dt, bi, ci, al: jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, dt, bi, ci, al))(x, x, bc, bc, al)

    fused_shapes = _collect_avals(make("fused").jaxpr, [])
    assert not _has_state_history(fused_shapes, s, di, ds), [
        sh for sh in fused_shapes if _has_state_history([sh], s, di, ds)]
    recompute_shapes = _collect_avals(make("recompute").jaxpr, [])
    assert _has_state_history(recompute_shapes, s, di, ds)


# ---------------------------------------------------------------------------
# quant8 straight-through cotangent


@pytest.mark.parametrize("use_key", [False, True])
def test_quant_ste_cotangent(use_key):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (37, 96))          # odd row count
    qkey = jax.random.PRNGKey(1) if use_key else None
    g = jax.grad(lambda x: (ops.quant_dequant(x, qkey) * 3.0).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 3.0, atol=1e-6)


def test_quant_kernel_stochastic_unbiased():
    """Mean-error unbiasedness of the fused stochastic-rounding lowering
    over non-degenerate rows (values strictly between int8 levels)."""
    base = jnp.linspace(-1.0, 1.0, 64)[None, :] + 0.003
    keys = jax.random.split(jax.random.PRNGKey(3), 768)
    ys = jax.vmap(lambda k: ops.quant_dequant(base, k))(keys)
    scale = float(jnp.max(jnp.abs(base)) / 127.0)
    mean_err = float(jnp.max(jnp.abs(ys.mean(0) - base)))
    # unbiased estimator: mean error shrinks ~ scale / sqrt(n_keys)
    assert mean_err < 3.0 * scale / np.sqrt(len(keys)) + 1e-6, mean_err


def test_quant_kernel_matches_jnp_oracle():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (50, 33, 64))
    np.testing.assert_allclose(
        np.asarray(ops.quant_dequant(x)),
        np.asarray(quant_dequant_ref(x)), atol=1e-6)
    # same uniforms => identical stochastic decision as the jnp lowering
    qk = jax.random.PRNGKey(9)
    np.testing.assert_allclose(
        np.asarray(ops.quant_dequant(x.reshape(-1, 64), qk)),
        np.asarray(compression._quant_dequant_jnp(x.reshape(-1, 64), qk)),
        atol=1e-6)


# ---------------------------------------------------------------------------
# fused softmax-xent


@pytest.mark.parametrize("t,d,v,bt,bv", [
    (64, 32, 128, 32, 64),       # aligned
    pytest.param(100, 48, 300, 32, 64,
                 marks=pytest.mark.slow),  # odd T and V
    (7, 16, 50, 32, 64),         # T < block_t, V < block_v
    pytest.param(128, 64, 1000, 64, 256,
                 marks=pytest.mark.slow),  # multi-tile vocab
])
def test_fused_ce_matches_ref_vjp(t, d, v, bt, bv):
    key = jax.random.PRNGKey(21)
    h = jax.random.normal(key, (t, d)) * 0.5
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.1
    labels = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)

    def f_ker(h, w):
        return ops.softmax_xent_tokens(h, w, labels, block_t=bt, block_v=bv)

    def f_ref(h, w):
        return softmax_xent_ref(h, w, labels)[0]

    loss_k, vjp_k = jax.vjp(f_ker, h, w)
    loss_r, vjp_r = jax.vjp(f_ref, h, w)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_r),
                               atol=1e-5, rtol=1e-5)
    g = jax.random.normal(jax.random.fold_in(key, 3), (t,))
    for name, a, r in zip(["dh", "dw"], vjp_k(g), vjp_r(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=ATOL, rtol=ATOL, err_msg=name)


@pytest.mark.slow
def test_chunked_ce_pallas_impl_matches_jnp_impl():
    """The fused CE kernel path (impl="pallas") == the checkpointed jnp
    oracle, value and gradient, with a validity mask."""
    key = jax.random.PRNGKey(31)
    t, d, v = 90, 32, 250
    h = jax.random.normal(key, (t, d)) * 0.5
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.1
    labels = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)
    valid = (jnp.arange(t) % 5 != 0)

    def mean_loss(impl):
        def f(h, w):
            per = losses.chunked_softmax_xent(h, w, labels, valid=valid,
                                              chunk=32, impl=impl)
            return per.mean()
        return f

    l_j, g_j = jax.value_and_grad(mean_loss("jnp"), argnums=(0, 1))(h, w)
    l_p, g_p = jax.value_and_grad(mean_loss("pallas"), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(l_j), float(l_p), atol=1e-6)
    for name, a, r in zip(["dh", "dw"], g_p, g_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


def test_no_tv_logits_intermediate():
    """The fused CE jaxpr never holds a [T, V] tensor (T = 4096 tokens,
    V = 32k vocab) in either direction."""
    t, d, v = 4096, 64, 32_768
    h = jax.ShapeDtypeStruct((t, d), jnp.float32)
    w = jax.ShapeDtypeStruct((d, v), jnp.float32)
    labels = jax.ShapeDtypeStruct((t,), jnp.int32)

    def loss(h, w, labels):
        return ops.softmax_xent_tokens(h, w, labels).sum()

    jaxpr = jax.make_jaxpr(
        lambda h, w, labels: jax.grad(loss, argnums=(0, 1))(h, w, labels))(
            h, w, labels)
    shapes = _collect_avals(jaxpr.jaxpr, [])
    big = [sh for sh in shapes if len(sh) >= 2 and sh[-2] >= t
           and sh[-1] >= v]
    assert not big, big
