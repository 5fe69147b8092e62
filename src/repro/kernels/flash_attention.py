"""Flash attention as Pallas TPU kernels — fused forward AND backward.

TPU adaptation of the memory-hierarchy insight behind FlashAttention:
HBM -> VMEM blocking with an online softmax so the S x S score matrix is
never materialized, in either direction of the train step.

Forward: grid (batch, q-head, q-block, kv-block); the TPU grid executes
the LAST axis sequentially per core, so the f32 accumulator / running
max / normalizer live in VMEM scratch across the kv-block sweep
(revolving accumulation — the Pallas-TPU analogue of the CUDA version's
per-SM shared-memory loop). The kernel additionally emits the per-row
logsumexp (LSE) residual so the backward can reconstruct probabilities
blockwise without saving them.

Backward: the standard two-kernel split.
  * dq  — grid (batch, q-head, q-block, kv-block); dq accumulates in
    VMEM scratch across the kv sweep.
  * dkv — grid (batch, kv-head, kv-block, q-block); dk/dv accumulate in
    VMEM scratch across the q sweep, summing the G query heads of each
    kv head in-block (GQA without KV gradient scatter).
Both recompute p = exp(s - lse) from (q, k, v, lse); the only extra
residuals beyond the inputs are LSE and delta = rowsum(dO * O), each
O(S) per head. Peak live intermediates stay O(block_q * block_k).

GQA is handled by BlockSpec index maps: q head h reads kv head h // G —
no KV duplication in VMEM. Masking (causal / sliding window / validity)
is by absolute positions streamed as int32 blocks, so the same kernels
serve training, prefill and ragged decode layouts.

Sequence lengths that do not divide the block sizes are padded up to the
block grid with `k_valid=False` keys and zero dO rows; masked key columns
contribute nothing in either direction, and padded query rows produce
zero output/LSE (note: a *fully masked* real row also yields output 0
here, where the jnp reference's softmax degrades to a uniform average —
don't construct such rows in oracle comparisons).

Block shapes are MXU-aligned (multiples of 128 on the contracting dims;
hd itself is 64/128 for every assigned arch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_mask(qp, kp, kv, causal: bool, window: int):
    """[bq, bk] validity from absolute positions + key-validity bits.

    qp is a [bq, 1] column, kp / kv are [1, bk] rows."""
    ok = kv != 0
    if causal:
        ok = ok & (kp <= qp)
    if window > 0:
        ok = ok & ((qp - kp) < window)
    return ok


def _col(row_ref):
    """A [1, n] row block as an [n, 1] column (per-query-row statistics
    live as rows in HBM, which keeps their blocks lane-dense)."""
    return row_ref[...].reshape(1, -1).T


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(qpos_ref, kpos_ref, kvalid_ref, q_ref, k_ref, v_ref,  # in
                o_ref, lse_ref,                                       # out
                acc_ref, m_ref, l_ref,                                # scratch
                *, causal: bool, window: int, nk: int, scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                           # [bq, hd]
    k = k_ref[0, 0]                           # [bk, hd]
    v = v_ref[0, 0]                           # [bk, hd]
    qp = _col(qpos_ref)                       # [bq, 1] int32
    kp = kpos_ref[0]                          # [1, bk] int32
    kv = kvalid_ref[0]                        # [1, bk] int32

    s = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((1,), (1,)), ((), ())))             # [bq, bk]
    ok = _block_mask(qp, kp, kv, causal, window)
    s_masked = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                       # [bq, 1]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_masked, axis=-1, keepdims=True))
    # explicit p-masking (not just the NEG_INF bias) so fully-masked rows
    # keep l == 0 and the LSE residual stays well-defined
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                        0.0)
        lse_ref[0, 0] = lse.T                 # [1, bq]


def _pad_axis(x, axis: int, pad: int, value=0):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_inputs(q, k, v, q_pos, k_pos, k_valid, block_q, block_k):
    """Pad seq axes up to the block grid; padded keys are marked invalid."""
    sq, sk = q.shape[1], k.shape[1]
    pad_q, pad_k = (-sq) % block_q, (-sk) % block_k
    if k_valid is None:
        k_valid = jnp.ones(k_pos.shape, bool)
    if pad_q:
        q = _pad_axis(q, 1, pad_q)
        q_pos = _pad_axis(q_pos, 1, pad_q)
    if pad_k:
        k = _pad_axis(k, 1, pad_k)
        v = _pad_axis(v, 1, pad_k)
        k_pos = _pad_axis(k_pos, 1, pad_k, value=-1)
        k_valid = _pad_axis(k_valid, 1, pad_k, value=False)
    return q, k, v, q_pos, k_pos, k_valid


def _to_kernel_layout(q, k, v, q_pos, k_pos, k_valid):
    """[B,S,H,hd] -> head-major [B,H,S,hd]; positions and key validity ->
    int32 rows [B,1,S]. Every block then ends in (seq-block, hd) or
    (1, seq-block), the shapes the TPU tiling accepts."""
    def heads(x):
        return x.transpose(0, 2, 1, 3)

    def row(x):
        return x.astype(jnp.int32)[:, None, :]
    return heads(q), heads(k), heads(v), row(q_pos), row(k_pos), row(k_valid)


def flash_attention_fwd(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                        k_valid=None, block_q: int = 512,
                        block_k: int = 512, return_lse: bool = False,
                        interpret: bool = False):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd] -> [B,Sq,H,hd] (+ LSE [B,H,Sq] f32).

    Sq/Sk need not divide the block sizes — inputs are padded to the
    block grid and outputs sliced back."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q, k, v, q_pos, k_pos, k_valid = _pad_inputs(
        q, k, v, q_pos, k_pos, k_valid, block_q, block_k)
    sq_p, sk_p = q.shape[1], k.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k
    qt, kt, vt, qp, kp, kv = _to_kernel_layout(q, k, v, q_pos, k_pos,
                                               k_valid)

    grid = (b, h, nq, nk)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               window=int(window), nk=nk, scale=hd ** -0.5)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda bi, hi, iq, ik: (bi, hi, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, hi, iq, ik: (bi, hi // g, ik, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, iq, ik: (bi, 0, iq)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, iq, ik: (bi, 0, ik)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, iq, ik: (bi, 0, ik)),
            q_spec, kv_spec, kv_spec,
        ],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, iq, ik: (bi, hi, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),       # acc
            pltpu.VMEM((block_q, 1), jnp.float32),        # m
            pltpu.VMEM((block_q, 1), jnp.float32),        # l
        ],
        interpret=interpret,
    )(qp, kp, kv, qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)[:, :sq]
    if return_lse:
        return out, lse[:, :, 0, :sq]
    return out


# ---------------------------------------------------------------------------
# backward


def _bwd_dq_kernel(qpos_ref, kpos_ref, kvalid_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref,                        # in
                   dq_ref,                                            # out
                   acc_ref,                                           # scratch
                   *, causal: bool, window: int, nk: int, scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)               # [bk, hd]
    do = do_ref[0, 0].astype(jnp.float32)             # [bq, hd]
    lse = _col(lse_ref)                               # [bq, 1]
    delta = _col(delta_ref)                           # [bq, 1]
    qp = _col(qpos_ref)
    kp = kpos_ref[0]
    kv = kvalid_ref[0]

    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))
    ok = _block_mask(qp, kp, kv, causal, window)
    p = jnp.where(ok, jnp.exp(s - lse), 0.0)                    # [bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))   # [bq, bk]
    ds = p * (dp - delta)
    acc_ref[...] += jax.lax.dot(ds, k) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qpos_ref, kpos_ref, kvalid_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref,                       # in
                    dk_ref, dv_ref,                                   # out
                    dk_acc, dv_acc,                                   # scratch
                    *, causal: bool, window: int, nq: int, g: int,
                    scale: float):
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0].astype(jnp.float32)                # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)                # [bk, hd]
    qp = _col(qpos_ref)
    kp = kpos_ref[0]
    kv = kvalid_ref[0]
    ok = _block_mask(qp, kp, kv, causal, window)       # [bq, bk]

    # the G query heads of this kv head, unrolled (G is a small static int)
    for gi in range(g):
        q = q_ref[0, gi].astype(jnp.float32)           # [bq, hd]
        do = do_ref[0, gi].astype(jnp.float32)         # [bq, hd]
        lse = _col(lse_ref.at[0, gi])                  # [bq, 1]
        delta = _col(delta_ref.at[0, gi])              # [bq, 1]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, q_pos, k_pos, k_valid, out, lse, do, *,
                        causal=True, window=0, block_q: int = 512,
                        block_k: int = 512, interpret: bool = False):
    """Blockwise VJP: (residuals, dO) -> (dq, dk, dv).

    Probabilities are recomputed from (q, k, lse) tile-by-tile; nothing
    [Sq, Sk]-shaped is ever live."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = hd ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    # delta_i = rowsum(dO_i * O_i)  -> [B, H, Sq] f32 (O(S) per head)
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       out.astype(jnp.float32))

    q_p, k_p, v_p, qp, kp, kv = _pad_inputs(q, k, v, q_pos, k_pos, k_valid,
                                            block_q, block_k)
    pad_q = q_p.shape[1] - sq
    do_p = _pad_axis(do, 1, pad_q)
    lse_p = _pad_axis(lse, 2, pad_q)[:, :, None, :]          # [B,H,1,Sq]
    delta_p = _pad_axis(delta, 2, pad_q)[:, :, None, :]
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k
    qt, kt, vt, qp, kp, kv = _to_kernel_layout(q_p, k_p, v_p, qp, kp, kv)
    dot = do_p.transpose(0, 2, 1, 3)

    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda bi, hi, iq, ik: (bi, hi, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, hi, iq, ik: (bi, hi // g, ik, 0))
    row_q = pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, iq, ik: (bi, hi, 0, iq))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=int(window),
                          nk=nk, scale=scale),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, iq, ik: (bi, 0, iq)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, iq, ik: (bi, 0, ik)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, iq, ik: (bi, 0, ik)),
            q_spec, kv_spec, kv_spec, q_spec, row_q, row_q,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qp, kp, kv, qt, kt, vt, dot, lse_p, delta_p)

    # grid (b, kv-head, kv-block, q-block): the G query heads of kv head
    # ki are the contiguous head block ki of the head-major layout
    qg_spec = pl.BlockSpec((1, g, block_q, hd),
                           lambda bi, ki, ik, iq: (bi, ki, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, hd),
                          lambda bi, ki, ik, iq: (bi, ki, ik, 0))
    rowg_q = pl.BlockSpec((1, g, 1, block_q),
                          lambda bi, ki, ik, iq: (bi, ki, 0, iq))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=int(window),
                          nq=nq, g=g, scale=scale),
        grid=(b, kh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda bi, ki, ik, iq: (bi, 0, iq)),
            pl.BlockSpec((1, 1, block_k), lambda bi, ki, ik, iq: (bi, 0, ik)),
            pl.BlockSpec((1, 1, block_k), lambda bi, ki, ik, iq: (bi, 0, ik)),
            qg_spec, k_spec, k_spec, qg_spec, rowg_q, rowg_q,
        ],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, sk_p, hd), k.dtype),
            jax.ShapeDtypeStruct((b, kh, sk_p, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, kv, qt, kt, vt, dot, lse_p, delta_p)

    def seq_major(x, n):
        return x.transpose(0, 2, 1, 3)[:, :n]
    return seq_major(dq, sq), seq_major(dk, sk), seq_major(dv, sk)
