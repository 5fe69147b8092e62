"""Flash attention as Pallas TPU kernels — fused forward AND backward.

TPU adaptation of the memory-hierarchy insight behind FlashAttention:
HBM -> VMEM blocking with an online softmax so the S x S score matrix is
never materialized, in either direction of the train step.

Forward: grid (batch, head-block, q-block, kv-block); the TPU grid
executes the LAST axis sequentially per core, so the f32 accumulator /
running max / normalizer live in VMEM scratch across the kv-block sweep
(revolving accumulation — the Pallas-TPU analogue of the CUDA version's
per-SM shared-memory loop). The kernel additionally emits the per-row
logsumexp (LSE) residual so the backward can reconstruct probabilities
blockwise without saving them. When one kv block spans every key, the
softmax is taken in one pass and no scratch is kept.

Backward: the standard two-kernel split.
  * dq  — grid (batch, head-block, q-block, kv-block); dq accumulates in
    VMEM scratch across the kv sweep.
  * dkv — grid (batch, kv-head-block, kv-block, q-block); dk/dv
    accumulate in VMEM scratch across the q sweep, summing the G query
    heads of each kv head in-block (GQA without KV gradient scatter).
    When one kv block spans every key, dq of each q block is complete in
    this kernel's step, so it writes dq too and the dq kernel is skipped.
Both recompute p = exp(s - lse) from (q, k, lse) and take
delta = rowsum(dO * O) from the O and dO blocks; the only residual
beyond the inputs and O is the LSE, O(S) per head. Peak live
intermediates stay O(block_q * block_k).

Layout: q / k / v / O and their gradients stay in the callers' [B, S, H,
hd] order, viewed as [B, S, H * hd] (a free reshape). Blocks are
[seq-block, heads * hd] slabs, lane-dense for any head_dim, and each
head is a static lane slice of its slab: nothing is transposed to
head-major and back around a call, and no 64-wide head is padded to 128
lanes in HBM. Each grid step holds as many heads as the VMEM budget
takes (`_plan`), so that short sequences with many heads and samples run
a few hundred grid steps per call instead of one per (sample, head).
GQA: q head h reads kv head h // G of the kv slab — no KV duplication in
VMEM. Masking (causal / sliding window / validity) is by absolute
positions streamed as int32 blocks, so the same kernels serve training,
prefill and ragged decode layouts; a call with no mask (non-causal, no
window, every key valid) streams no positions and evaluates no mask.

Precision: the QK, PV, dO·Vᵀ and Pᵀ·dO matmuls take q / k / v / dO in
their own dtype and P in v's, accumulating in f32; dS·K and dSᵀ·Q take
f32 operands. The softmax statistics (m, l, LSE, delta) and dS are f32.

Sequence lengths that do not divide the block sizes are padded up to the
block grid with `k_valid=False` keys and zero dO rows; masked key columns
contribute nothing in either direction, and padded query rows are sliced
away (note: a *fully masked* real row yields output 0 here, where the
jnp reference's softmax degrades to a uniform average — don't construct
such rows in oracle comparisons).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Scoped VMEM the kernels may use (v5e has 128 MiB per core; the default
# limit is 16 MiB), and what one grid step's blocks and f32 tiles may
# take of it, as `_step_bytes` counts them.
_VMEM_LIMIT = 64 * 1024 * 1024
_STEP_BUDGET = 24 * 1024 * 1024
_LANES = 128
_TRANS_B = (((1,), (1,)), ((), ()))     # x @ y.T
_TRANS_A = (((0,), (0,)), ((), ()))     # x.T @ y


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _step_bytes(nh: int, nkv: int, bq: int, bk: int, hd: int,
                itemsize: int) -> int:
    """VMEM of a grid step with nh query heads and nkv kv heads, counted
    for the largest kernel (the fused backward): its q / O / dO / dq and
    k / v / dk / dv slabs double-buffered, f32 accumulators and softmax
    statistics, and four f32 [bq, bk] tiles of the head in flight (heads
    run one after another)."""
    rows_q, rows_k = _round_up(bq, 16), _round_up(bk, 16)
    hd_l = _round_up(hd, _LANES)
    slabs = 4 * 2 * itemsize * (rows_q * _round_up(nh * hd, _LANES)
                                + rows_k * _round_up(nkv * hd, _LANES))
    f32 = 4 * (nh * rows_q * (hd_l + 2 * _LANES) + 2 * nkv * rows_k * hd_l
               + 4 * _round_up(bq, 8) * _round_up(bk, _LANES))
    return slabs + f32


def _kv_heads(hb: int, h: int, kh: int, hd: int):
    """The kv heads a block of hb query heads reads (None when they do
    not form a block whose slab the TPU tiling accepts)."""
    g = h // kh
    if h % hb or (hb * hd % _LANES and hb != h):
        return None
    nkv = hb // g if hb % g == 0 else (1 if g % hb == 0 else 0)
    if not nkv or (nkv * hd % _LANES and nkv != kh):
        return None
    return nkv


def _plan(h: int, kh: int, hd: int, itemsize: int, block_q: int,
          block_k: int, whole_groups: bool = False):
    """(query heads per grid step, block_q, block_k): the most heads whose
    slabs are lane-aligned and fit `_STEP_BUDGET`, halving blocks longer
    than 128 rows while no head count fits (and else the fewest heads).
    `whole_groups` keeps every query head of a kv head in one step (the
    dkv kernel)."""
    valid = [hb for hb in range(1, h + 1) if _kv_heads(hb, h, kh, hd)
             and not (whole_groups and hb % (h // kh))]
    while True:
        fits = [hb for hb in valid
                if _step_bytes(hb, _kv_heads(hb, h, kh, hd), block_q,
                               block_k, hd, itemsize) <= _STEP_BUDGET]
        if fits or max(block_q, block_k) <= _LANES:
            return max(fits or valid[:1]), block_q, block_k
        block_q, block_k = (n if n <= _LANES
                            else max(_LANES, n // 2 // _LANES * _LANES)
                            for n in (block_q, block_k))


def _head_block_specs(h: int, kh: int, hd: int, hb: int, block_q: int,
                      block_k: int):
    """q-slab, kv-slab and LSE-row block specs for a grid (batch,
    head-block, q-block, kv-block) of hb query heads per step."""
    nkv = _kv_heads(hb, h, kh, hd)
    return (pl.BlockSpec((1, block_q, hb * hd),
                         lambda bi, hj, iq, ik: (bi, iq, hj)),
            pl.BlockSpec((1, block_k, nkv * hd),
                         lambda bi, hj, iq, ik:
                         (bi, ik, hj * hb // (h // kh) // nkv)),
            pl.BlockSpec((1, hb, 1, block_q),
                         lambda bi, hj, iq, ik: (bi, hj, 0, iq)))


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


def _split_mask_refs(refs, masked: bool, causal: bool, window: int):
    """(mask or None, remaining refs): a masked kernel's first three refs
    are the q-position, k-position and key-validity blocks."""
    if not masked:
        return None, refs
    qpos_ref, kpos_ref, kvalid_ref = refs[:3]
    ok = _block_mask(_col(qpos_ref), kpos_ref[0], kvalid_ref[0], causal,
                     window)
    return ok, refs[3:]


def _lanes(i: int, hd: int):
    """Head i's lanes in its slab. In a step of fewer query heads than G,
    query head i // G == 0 reads the step's one kv head."""
    return slice(i * hd, (i + 1) * hd)


def _scores(q, k, scale: float):
    """f32 [bq, bk] scaled scores from q [bq, hd], k [bk, hd] in their
    own dtype."""
    return jax.lax.dot_general(q, k, _TRANS_B,
                               preferred_element_type=jnp.float32) * scale


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(*refs, causal: bool, window: int, masked: bool, nk: int,
                g: int, hd: int, scale: float):
    ok, refs = _split_mask_refs(refs, masked, causal, window)
    q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch = refs
    hb = q_ref.shape[2] // hd

    def head(i):
        j = _lanes(i // g, hd)
        return (_scores(q_ref[0, :, _lanes(i, hd)], k_ref[0, :, j], scale),
                v_ref[0, :, j])

    if nk == 1:
        # every key in one block: a one-pass softmax, nothing carried
        # (the online update's scratch traffic made this forward 1.5x
        # slower at [256, 274, 12, 64] on a v5e)
        for i in range(hb):
            s, v = head(i)
            if ok is not None:
                s = jnp.where(ok, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            if ok is not None:
                p = jnp.where(ok, p, 0.0)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jax.lax.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
            o_ref[0, :, _lanes(i, hd)] = (o / jnp.maximum(l, 1e-30)).astype(
                o_ref.dtype)
            lse = m + jnp.log(jnp.maximum(l, 1e-30))
            if ok is not None:
                lse = jnp.where(l > 0, lse, 0.0)
            lse_ref[0, i] = lse.T             # [1, bq]
        return

    acc_ref, m_ref, l_ref = scratch
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for i in range(hb):
        s, v = head(i)
        s_masked = s if ok is None else jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[i]                     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s_masked, axis=-1,
                                            keepdims=True))
        # explicit p-masking (not just the NEG_INF bias) so fully-masked
        # rows keep l == 0 and the LSE residual stays well-defined
        p = jnp.exp(s - m_new)
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[i] = l_ref[i] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[i] = acc_ref[i] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[i] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        for i in range(hb):
            l = l_ref[i]
            o_ref[0, :, _lanes(i, hd)] = (
                acc_ref[i] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            lse = jnp.where(l > 0, m_ref[i] + jnp.log(jnp.maximum(l, 1e-30)),
                            0.0)
            lse_ref[0, i] = lse.T             # [1, bq]


def _pad_axis(x, axis: int, pad: int, value=0):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_inputs(q, k, v, q_pos, k_pos, k_valid, block_q, block_k):
    """Pad seq axes up to the block grid; padded keys are marked invalid
    (a `k_valid` of None stays None when no key is padded)."""
    sq, sk = q.shape[1], k.shape[1]
    pad_q, pad_k = (-sq) % block_q, (-sk) % block_k
    if pad_k and k_valid is None:
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


def _slab(x):
    """[B, S, H, hd] -> [B, S, H * hd] (free)."""
    return x.reshape(x.shape[:2] + (-1,))


def _mask_inputs(q_pos, k_pos, k_valid, block_q, block_k, index_q,
                 index_k):
    """Positions and key validity as int32 rows [B,1,S] with their block
    specs, for a masked call; each block ends in (1, seq-block), a shape
    the TPU tiling accepts. `index_q(*grid) -> (b, q-block)`, likewise
    `index_k`."""
    def row(x):
        return x.astype(jnp.int32)[:, None, :]
    if k_valid is None:
        k_valid = jnp.ones(k_pos.shape, bool)
    q_spec = pl.BlockSpec((1, 1, block_q),
                          lambda *ix: (index_q(*ix)[0], 0, index_q(*ix)[1]))
    k_spec = pl.BlockSpec((1, 1, block_k),
                          lambda *ix: (index_k(*ix)[0], 0, index_k(*ix)[1]))
    return [row(q_pos), row(k_pos), row(k_valid)], [q_spec, k_spec, k_spec]


def flash_attention_fwd(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                        k_valid=None, block_q: int = 512,
                        block_k: int = 512, return_lse: bool = False,
                        interpret: bool = False):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd] -> [B,Sq,H,hd] (+ LSE [B,H,Sq] f32).

    Sq/Sk need not divide the block sizes — inputs are padded to the
    block grid and outputs sliced back."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    hb, block_q, block_k = _plan(h, kh, hd, q.dtype.itemsize,
                                 min(block_q, sq), min(block_k, sk))
    q, k, v, q_pos, k_pos, k_valid = _pad_inputs(
        q, k, v, q_pos, k_pos, k_valid, block_q, block_k)
    masked = causal or window > 0 or k_valid is not None
    sq_p, sk_p = q.shape[1], k.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(_fwd_kernel, causal=causal,
                               window=int(window), masked=masked, nk=nk,
                               g=h // kh, hd=hd, scale=hd ** -0.5)
    q_spec, kv_spec, lse_spec = _head_block_specs(h, kh, hd, hb, block_q,
                                                  block_k)
    args, specs = [], []
    if masked:
        args, specs = _mask_inputs(q_pos, k_pos, k_valid, block_q, block_k,
                                   lambda bi, hj, iq, ik: (bi, iq),
                                   lambda bi, hj, iq, ik: (bi, ik))
    scratch = [] if nk == 1 else [
        pltpu.VMEM((hb, block_q, hd), jnp.float32),       # acc
        pltpu.VMEM((hb, block_q, 1), jnp.float32),        # m
        pltpu.VMEM((hb, block_q, 1), jnp.float32),        # l
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h // hb, nq, nk),
        in_specs=specs + [q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq_p, h * hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(*args, _slab(q), _slab(k), _slab(v))
    out = out.reshape(b, sq_p, h, hd)[:, :sq]
    if return_lse:
        return out, lse[:, :, 0, :sq]
    return out


# ---------------------------------------------------------------------------
# backward


def _head_grads(q, k, v, o, do, lse, ok, scale: float):
    """One head's recomputed probabilities and score gradient: (p, dS),
    both f32 [bq, bk]."""
    p = jnp.exp(_scores(q, k, scale) - lse)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # [bq, 1]
    dp = jax.lax.dot_general(do, v, _TRANS_B,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _bwd_dq_kernel(*refs, causal: bool, window: int, masked: bool, nk: int,
                   g: int, hd: int, scale: float):
    ok, refs = _split_mask_refs(refs, masked, causal, window)
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,         # in
     dq_ref,                                              # out
     acc_ref) = refs                                      # scratch
    hb = q_ref.shape[2] // hd
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(hb):
        qi, j = _lanes(i, hd), _lanes(i // g, hd)
        k = k_ref[0, :, j]
        _, ds = _head_grads(q_ref[0, :, qi], k, v_ref[0, :, j],
                            o_ref[0, :, qi], do_ref[0, :, qi],
                            _col(lse_ref.at[0, i]), ok, scale)
        acc_ref[i] += jax.lax.dot(ds, k.astype(jnp.float32)) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        for i in range(hb):
            dq_ref[0, :, _lanes(i, hd)] = acc_ref[i].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal: bool, window: int, masked: bool, nq: int,
                    g: int, hd: int, scale: float, with_dq: bool):
    ok, refs = _split_mask_refs(refs, masked, causal, window)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest = refs
    dq_ref = rest.pop(0) if with_dq else None
    dk_ref, dv_ref, *acc = rest
    iq = pl.program_id(3)

    if nq > 1:
        dk_acc, dv_acc = acc

        @pl.when(iq == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    for j in range(k_ref.shape[2] // hd):
        kj = _lanes(j, hd)
        k, v = k_ref[0, :, kj], v_ref[0, :, kj]            # [bk, hd]
        k32 = k.astype(jnp.float32)
        dk = dv = 0.0
        # the G query heads of this kv head, unrolled (G is a small int)
        for i in range(j * g, (j + 1) * g):
            qi = _lanes(i, hd)
            q, do = q_ref[0, :, qi], do_ref[0, :, qi]      # [bq, hd]
            p, ds = _head_grads(q, k, v, o_ref[0, :, qi], do,
                                _col(lse_ref.at[0, i]), ok, scale)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, _TRANS_A,
                preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                ds, q.astype(jnp.float32), _TRANS_A) * scale
            if with_dq:
                dq_ref[0, :, qi] = (jax.lax.dot(ds, k32) * scale).astype(
                    dq_ref.dtype)
        if nq == 1:     # scratch accumulators made it 7% slower here
            dk_ref[0, :, kj] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, kj] = dv.astype(dv_ref.dtype)
        else:
            dk_acc[j] += dk
            dv_acc[j] += dv

    if nq > 1:
        @pl.when(iq == nq - 1)
        def _finalize():
            for j in range(k_ref.shape[2] // hd):
                dk_ref[0, :, _lanes(j, hd)] = dk_acc[j].astype(dk_ref.dtype)
                dv_ref[0, :, _lanes(j, hd)] = dv_acc[j].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, q_pos, k_pos, k_valid, out, lse, do, *,
                        causal=True, window=0, block_q: int = 512,
                        block_k: int = 512, interpret: bool = False):
    """Blockwise VJP: (residuals, dO) -> (dq, dk, dv).

    Probabilities are recomputed from (q, k, lse) tile-by-tile; nothing
    [Sq, Sk]-shaped is ever live. `k_valid` may be None (every key
    valid)."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    itemsize = q.dtype.itemsize
    # grid (b, kv-head-block, kv-block, q-block): the G query heads of
    # each kv head are contiguous lanes of the q slab
    hb, block_q, block_k = _plan(h, kh, hd, itemsize, min(block_q, sq),
                                 min(block_k, sk), whole_groups=True)
    nkv = hb // g

    q_p, k_p, v_p, qp, kp, kv = _pad_inputs(q, k, v, q_pos, k_pos, k_valid,
                                            block_q, block_k)
    masked = causal or window > 0 or kv is not None
    pad_q = q_p.shape[1] - sq
    o_p, do_p = _pad_axis(out, 1, pad_q), _pad_axis(do, 1, pad_q)
    lse_p = _pad_axis(lse, 2, pad_q)[:, :, None, :]          # [B,H,1,Sq]
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k
    slabs = [_slab(x) for x in (q_p, k_p, v_p, o_p, do_p)] + [lse_p]
    common = dict(causal=causal, window=int(window), masked=masked,
                  scale=hd ** -0.5, g=g, hd=hd)

    # one kv block: each q block's dq is complete in the dkv step, which
    # saves the dq kernel's recompute (a fifth of the backward at
    # [256, 274, 12, 64] on a v5e)
    with_dq = nk == 1
    q_spec = pl.BlockSpec((1, block_q, hb * hd),
                          lambda bi, kj, ik, iq: (bi, iq, kj))
    k_spec = pl.BlockSpec((1, block_k, nkv * hd),
                          lambda bi, kj, ik, iq: (bi, ik, kj))
    lse_spec = pl.BlockSpec((1, hb, 1, block_q),
                            lambda bi, kj, ik, iq: (bi, kj, 0, iq))
    args, specs = [], []
    if masked:
        args, specs = _mask_inputs(qp, kp, kv, block_q, block_k,
                                   lambda bi, kj, ik, iq: (bi, iq),
                                   lambda bi, kj, ik, iq: (bi, ik))
    dq_shape = [jax.ShapeDtypeStruct((b, sq_p, h * hd), q.dtype)] \
        if with_dq else []
    acc = [] if nq == 1 else [pltpu.VMEM((nkv, block_k, hd), jnp.float32)] * 2
    outs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, with_dq=with_dq,
                          **common),
        grid=(b, kh // nkv, nk, nq),
        in_specs=specs + [q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec],
        out_specs=[q_spec] * with_dq + [k_spec, k_spec],
        out_shape=dq_shape + [
            jax.ShapeDtypeStruct((b, sk_p, kh * hd), k.dtype),
            jax.ShapeDtypeStruct((b, sk_p, kh * hd), v.dtype),
        ],
        scratch_shapes=acc,
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(*args, *slabs)
    dk, dv = outs[-2:]

    if with_dq:
        dq = outs[0]
    else:
        hb, _, _ = _plan(h, kh, hd, itemsize, block_q, block_k)
        q_spec, kv_spec, lse_spec = _head_block_specs(h, kh, hd, hb,
                                                      block_q, block_k)
        args, specs = [], []
        if masked:
            args, specs = _mask_inputs(qp, kp, kv, block_q, block_k,
                                       lambda bi, hj, iq, ik: (bi, iq),
                                       lambda bi, hj, iq, ik: (bi, ik))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, nk=nk, **common),
            grid=(b, h // hb, nq, nk),
            in_specs=specs + [q_spec, kv_spec, kv_spec, q_spec, q_spec,
                              lse_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((b, sq_p, h * hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((hb, block_q, hd), jnp.float32)],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=interpret,
        )(*args, *slabs)

    def heads(x, n, s):
        return x.reshape(b, -1, n, hd)[:, :s]
    return heads(dq, h, sq), heads(dk, kh, sk), heads(dv, kh, sk)
