"""Fused LM-head cross-entropy as Pallas TPU kernels.

The LM loss `lse(h @ w) - (h @ w)[label]` is the last place the train
step could materialize a [T, V] f32 tensor (V = 32k-152k for the
assigned archs). These kernels stream the vocabulary in tiles with an
online softmax — the same revolving-accumulator pattern as the flash
attention kernels, applied to the classifier axis:

  forward  — grid (t-block, v-block); running max / normalizer / gold
             logit live in VMEM scratch across the vocab sweep. The
             gold logit is gathered with an in-tile one-hot reduction
             (no dynamic gather on the lane axis). Emits per-token loss
             AND the LSE residual.
  backward — dlogits = g * (softmax - onehot) is reconstructed tile by
             tile from (h, w, lse); dh accumulates over the vocab sweep
             (grid (nt, nv)), dw over the token sweep (grid (nv, nt)).

Peak live intermediates are O(block_t * block_v) in both directions —
the lowering replaces the jax.lax.map + checkpoint schedule in
core.losses with one read of h/w per sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Double-buffered h / w tiles (plus, backward, an f32 [D, block_v] or
# [block_t, D] accumulator) take ~15-27 MB at D = 3072, over the 16 MB
# default scoped-VMEM limit (v5e has 128 MiB of VMEM per core).
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _tile_logits(h_ref, w_ref, iv, block_v: int, v_total: int):
    """[bt, bv] f32 logits for vocab tile iv, padding columns at -inf."""
    logits = jax.lax.dot(h_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)   # [bt, bv]
    col = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    return jnp.where(col < v_total, logits, NEG_INF), col


def _fwd_kernel(lab_ref, h_ref, w_ref,                 # in
                loss_ref, lse_ref,                     # out
                m_ref, l_ref, gold_ref,                # scratch
                *, block_v: int, nv: int, v_total: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        gold_ref[...] = jnp.zeros_like(gold_ref)

    logits, col = _tile_logits(h_ref, w_ref, iv, block_v, v_total)
    lab = _col(lab_ref)                                # [bt, 1] int32

    m_prev = m_ref[...]                                # [bt, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True)
    m_ref[...] = m_new
    # one-hot gather of the gold logit (labels land in exactly one tile)
    onehot = col == lab
    gold_ref[...] += jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1,
                             keepdims=True)

    @pl.when(iv == nv - 1)
    def _finalize():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[...] = lse.T
        loss_ref[...] = (lse - gold_ref[...]).T


def _bwd_dh_kernel(lab_ref, g_ref, lse_ref, h_ref, w_ref,  # in
                   dh_ref,                                 # out
                   acc_ref,                                # scratch
                   *, block_v: int, nv: int, v_total: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logits, col = _tile_logits(h_ref, w_ref, iv, block_v, v_total)
    p = jnp.exp(logits - _col(lse_ref))                # [bt, bv]
    onehot = (col == _col(lab_ref)).astype(jnp.float32)
    ds = (p - onehot) * _col(g_ref)
    # ds @ w^T  -> [bt, D]
    acc_ref[...] += jax.lax.dot_general(
        ds, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())))

    @pl.when(iv == nv - 1)
    def _finalize():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(lab_ref, g_ref, lse_ref, h_ref, w_ref,  # in
                   dw_ref,                                 # out
                   acc_ref,                                # scratch
                   *, block_v: int, nt: int, v_total: int):
    iv, it = pl.program_id(0), pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logits, col = _tile_logits(h_ref, w_ref, iv, block_v, v_total)
    p = jnp.exp(logits - _col(lse_ref))
    onehot = (col == _col(lab_ref)).astype(jnp.float32)
    ds = (p - onehot) * _col(g_ref)                    # [bt, bv]
    # h^T @ ds  -> [D, bv]
    acc_ref[...] += jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), ds, (((0,), (0,)), ((), ())))

    @pl.when(it == nt - 1)
    def _finalize():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _col(row_ref):
    """A [1, n] row block as an [n, 1] column. Per-token vectors (labels,
    loss, LSE, cotangent) live as [1, T] rows in HBM: a 1-D block would
    not match the layout XLA gives the array."""
    return row_ref[...].T


def _row(x, pad: int):
    """[T] -> [1, T + pad] (zero padded)."""
    return jnp.pad(x, (0, pad))[None, :] if pad else x[None, :]


def _pad_tokens(h, labels, block_t):
    t = h.shape[0]
    pad = (-t) % block_t
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
    return h, _row(labels, pad)


def softmax_xent_fwd(h, w, labels, *, block_t: int = 256,
                     block_v: int = 512, interpret: bool = False):
    """h [T, D], w [D, V], labels [T] -> (loss [T], lse [T]), f32."""
    t, d = h.shape
    v = w.shape[1]
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    h_p, lab_p = _pad_tokens(h, labels.astype(jnp.int32), block_t)
    t_p = h_p.shape[0]
    pad_v = (-v) % block_v
    w_p = jnp.pad(w, ((0, 0), (0, pad_v))) if pad_v else w
    nt, nv = t_p // block_t, w_p.shape[1] // block_v

    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, nv=nv, v_total=v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
            pl.BlockSpec((block_t, d), lambda it, iv: (it, 0)),
            pl.BlockSpec((d, block_v), lambda it, iv: (0, iv)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
            pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, t_p), jnp.float32),
            jax.ShapeDtypeStruct((1, t_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),     # m
            pltpu.VMEM((block_t, 1), jnp.float32),     # l
            pltpu.VMEM((block_t, 1), jnp.float32),     # gold
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lab_p, h_p, w_p)
    return loss[0, :t], lse[0, :t]


def softmax_xent_bwd(h, w, labels, lse, g, *, block_t: int = 256,
                     block_v: int = 512, interpret: bool = False):
    """(residuals, per-token cotangent g [T]) -> (dh [T, D], dw [D, V])."""
    t, d = h.shape
    v = w.shape[1]
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    h_p, lab_p = _pad_tokens(h, labels.astype(jnp.int32), block_t)
    t_p = h_p.shape[0]
    pad_t = t_p - t
    g_p = _row(g.astype(jnp.float32), pad_t)
    lse_p = _row(lse, pad_t)
    pad_v = (-v) % block_v
    w_p = jnp.pad(w, ((0, 0), (0, pad_v))) if pad_v else w
    nt, nv = t_p // block_t, w_p.shape[1] // block_v

    tok_specs = [
        pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
        pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
        pl.BlockSpec((1, block_t), lambda it, iv: (0, it)),
        pl.BlockSpec((block_t, d), lambda it, iv: (it, 0)),
        pl.BlockSpec((d, block_v), lambda it, iv: (0, iv)),
    ]
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_v=block_v, nv=nv, v_total=v),
        grid=(nt, nv),
        in_specs=tok_specs,
        out_specs=pl.BlockSpec((block_t, d), lambda it, iv: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((t_p, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lab_p, g_p, lse_p, h_p, w_p)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_v=block_v, nt=nt, v_total=v),
        grid=(nv, nt),                    # token sweep minor-most
        in_specs=[
            pl.BlockSpec((1, block_t), lambda iv, it: (0, it)),
            pl.BlockSpec((1, block_t), lambda iv, it: (0, it)),
            pl.BlockSpec((1, block_t), lambda iv, it: (0, it)),
            pl.BlockSpec((block_t, d), lambda iv, it: (it, 0)),
            pl.BlockSpec((d, block_v), lambda iv, it: (0, iv)),
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda iv, it: (0, iv)),
        out_shape=jax.ShapeDtypeStruct((d, w_p.shape[1]), w.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_v), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lab_p, g_p, lse_p, h_p, w_p)
    if pad_v:
        dw = dw[:, :v]
    return dh[:t], dw
