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
VMEM. Masking (causal / sliding window, with the keys below a visible
`prefix` kept beside the window / validity) is by absolute positions
streamed as int32 blocks, so the same kernels serve training, prefill
and ragged decode layouts; a call with no mask (non-causal, no window,
every key valid) streams no positions and evaluates no mask.

Tile skipping: a masked call with more than one kv block scalar-prefetches
into SMEM each block's least and greatest query position over its real
rows and key position over its valid keys (`_tile_bounds`). A grid step
whose tile the mask hides entirely (`_may_see` false: every key after
every query, or out of the window and not in the prefix, or none valid)
does no MXU or VPU work in any of the three kernels; init and finalize
still run, so a row with no live tile keeps l == 0 and LSE 0 and a kv
block with no live tile writes zero dk / dv. The test is exact for
contiguous positions and conservative for any others. The swept
operand's index map is clamped to the span of live blocks of its row
(`_clamp`), so a skipped step outside that span re-uses the resident
block and moves nothing from HBM: the causal tail in every kernel, and
in the dkv kernel every dead step of a windowed layer. Unmasked and
one-kv-block calls keep every step and their plain grid.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# A bound past every position: the span of a block with no valid one.
_FAR = 1 << 30

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
                      block_k: int, kv_block=None):
    """q-slab, kv-slab and LSE-row block specs for a grid (batch,
    head-block, q-block, kv-block) of hb query heads per step; the kv
    slab is `kv_block(bi, iq, ik, bounds)`'s block where given."""
    nkv = _kv_heads(hb, h, kh, hd)
    if kv_block is None:
        def kv_block(bi, iq, ik):
            return ik
    return (pl.BlockSpec((1, block_q, hb * hd),
                         lambda bi, hj, iq, ik, *_: (bi, iq, hj)),
            pl.BlockSpec((1, block_k, nkv * hd),
                         lambda bi, hj, iq, ik, *b:
                         (bi, kv_block(bi, iq, ik, *b),
                          hj * hb // (h // kh) // nkv)),
            pl.BlockSpec((1, hb, 1, block_q),
                         lambda bi, hj, iq, ik, *_: (bi, hj, 0, iq)))


def _block_mask(qp, kp, kv, causal: bool, window: int, prefix: int):
    """[bq, bk] validity from absolute positions + key-validity bits: with
    a window, keys at positions below `prefix` stay visible.

    qp is a [bq, 1] column, kp / kv are [1, bk] rows."""
    ok = kv != 0
    if causal:
        ok = ok & (kp <= qp)
    if window > 0:
        near = (qp - kp) < window
        if prefix > 0:
            near = near | (kp < prefix)
        ok = ok & near
    return ok


def _may_see(q_lo, q_hi, k_lo, k_hi, causal: bool, window: int,
             prefix: int):
    """Whether a tile whose query positions span [q_lo, q_hi] and whose
    valid keys' span [k_lo, k_hi] can hold a pair `_block_mask` keeps
    (exact for contiguous positions). Takes ints, numpy arrays, or
    scalars read in a kernel."""
    live = k_lo <= k_hi
    if causal:
        live = live & (k_lo <= q_hi)
    if window > 0:
        near = q_lo - k_hi < window
        if prefix > 0:
            near = near | (k_lo < prefix)
        live = live & near
    return live


def _tile_bounds(q_pos, k_pos, k_valid, sq: int, block_q: int,
                 block_k: int, causal: bool, window: int, prefix: int):
    """int32 [B * 4 * (nq + nk)]: per batch element, the least and the
    greatest position of each q block over its first `sq` (real) rows,
    then of each kv block over its valid keys (padded rows and keys widen
    no bounds; a block with none spans [_FAR, -_FAR]); then the first and
    the last live kv block of each q block, and the first and the last
    live q block of each kv block (0 and 0 where none is live)."""
    b, sq_p = q_pos.shape

    def lo_hi(pos, valid, block):
        pos = pos.reshape(b, -1, block)
        valid = valid.reshape(b, -1, block)
        return (jnp.min(jnp.where(valid, pos, _FAR), axis=-1),
                jnp.max(jnp.where(valid, pos, -_FAR), axis=-1))

    def span(live, axis):
        idx = jnp.arange(live.shape[axis]).reshape(
            (-1, 1) if axis == 1 else (1, -1))
        first = jnp.min(jnp.where(live, idx, _FAR), axis=axis)
        last = jnp.max(jnp.where(live, idx, -1), axis=axis)
        none = first > last
        return jnp.where(none, 0, first), jnp.where(none, 0, last)

    q_real = jnp.broadcast_to(jnp.arange(sq_p) < sq, (b, sq_p))
    k_ok = jnp.ones(k_pos.shape, bool) if k_valid is None else k_valid
    (q_lo, q_hi), (k_lo, k_hi) = (lo_hi(q_pos, q_real, block_q),
                                  lo_hi(k_pos, k_ok, block_k))
    live = jnp.broadcast_to(
        _may_see(q_lo[:, :, None], q_hi[:, :, None], k_lo[:, None],
                 k_hi[:, None], causal, window, prefix),
        (b, q_lo.shape[1], k_lo.shape[1]))
    return jnp.concatenate((q_lo, q_hi, k_lo, k_hi) + span(live, 2)
                           + span(live, 1),
                           axis=1).astype(jnp.int32).reshape(-1)


def _clamp(nq: int, nk: int, q_rows: bool):
    """Index map of the swept block (of a grid whose rows are q blocks
    sweeping kv blocks when `q_rows`, else kv blocks sweeping q blocks):
    a step outside its row's live span re-reads the span's edge block,
    which is resident, so a skipped step there moves nothing from HBM."""
    off, n = (2 * (nq + nk), nq) if q_rows else (2 * (nq + nk) + 2 * nq, nk)

    def block(bi, row, i, bounds):
        r = bi * 4 * (nq + nk) + off + row
        return jnp.minimum(jnp.maximum(i, bounds[r]), bounds[r + n])
    return block


def tile_counts(sq: int, sk: int, h: int, kh: int, hd: int, itemsize: int,
                *, causal: bool, window: int = 0, prefix: int = 0,
                block_q: int = 512, block_k: int = 512):
    """(grid tiles, live tiles) of the forward kernel per batch element
    and head block, for contiguous positions 0..S-1 and no `k_valid`:
    the tiles a call visits and those that do work."""
    _, block_q, block_k = _plan(h, kh, hd, itemsize, min(block_q, sq),
                                min(block_k, sk))
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    if nk == 1 or not (causal or window > 0 or sk % block_k):
        return nq * nk, nq * nk
    q_lo, k_lo = np.arange(nq) * block_q, np.arange(nk) * block_k
    live = _may_see(q_lo[:, None],
                    np.minimum(q_lo + block_q, sq)[:, None] - 1,
                    k_lo[None], np.minimum(k_lo + block_k, sk)[None] - 1,
                    causal, int(window), int(prefix))
    return nq * nk, int(np.broadcast_to(live, (nq, nk)).sum())


def _col(row_ref):
    """A [1, n] row block as an [n, 1] column (per-query-row statistics
    live as rows in HBM, which keeps their blocks lane-dense)."""
    return row_ref[...].reshape(1, -1).T


def _split_mask_refs(refs, masked: bool, causal: bool, window: int,
                     prefix: int, tiles=None):
    """(mask, live, remaining refs). `mask()` is the step's [bq, bk] mask
    or None: a masked kernel's first three refs are the q-position,
    k-position and key-validity blocks. `tiles` = (q-block axis, kv-block
    axis, nq, nk) marks a kernel that skips dead tiles: its first ref is
    the `_tile_bounds` (scalar-prefetched to SMEM), `live` its step's
    `_may_see`, and it builds its mask only in the steps it keeps; else
    `live` is None."""
    live = None
    if tiles is not None:
        bounds_ref, refs = refs[0], refs[1:]
        q_axis, k_axis, nq, nk = tiles
        base = pl.program_id(0) * 4 * (nq + nk)
        iq = base + pl.program_id(q_axis)
        ik = base + 2 * nq + pl.program_id(k_axis)
        live = _may_see(bounds_ref[iq], bounds_ref[iq + nq],
                        bounds_ref[ik], bounds_ref[ik + nk], causal, window,
                        prefix)
    if not masked:
        return lambda: None, live, refs
    qpos_ref, kpos_ref, kvalid_ref = refs[:3]

    def mask():
        return _block_mask(_col(qpos_ref), kpos_ref[0], kvalid_ref[0],
                           causal, window, prefix)
    if live is not None:
        return mask, live, refs[3:]
    ok = mask()
    return lambda: ok, live, refs[3:]


def _when(live):
    """Decorator: run the body in every grid step, or only where `live`
    (a kernel that skips dead tiles)."""
    return (lambda body: body()) if live is None else pl.when(live)


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


def _fwd_kernel(*refs, causal: bool, window: int, prefix: int,
                masked: bool, skip: bool, nq: int, nk: int, g: int, hd: int,
                scale: float):
    mask, live, refs = _split_mask_refs(refs, masked, causal, window,
                                        prefix, (2, 3, nq, nk) if skip
                                        else None)
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
        ok = mask()
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

    @_when(live)
    def _step():
        ok = mask()
        for i in range(hb):
            s, v = head(i)
            s_masked = s if ok is None else jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[i]                     # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s_masked, axis=-1,
                                                keepdims=True))
            # explicit p-masking (not just the NEG_INF bias) so
            # fully-masked rows keep l == 0 and the LSE residual stays
            # well-defined
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


def _mask_args(q_pos, k_pos, k_valid, sq: int, block_q: int, block_k: int,
               skip, causal: bool, window: int, prefix: int):
    """A masked call's mask inputs: the `_tile_bounds` of its `sq` real
    query rows when it skips dead tiles, then positions and key validity
    as int32 rows [B, 1, S]."""
    def row(x):
        return x.astype(jnp.int32)[:, None, :]
    bounds = [_tile_bounds(q_pos, k_pos, k_valid, sq, block_q, block_k,
                           causal, window, prefix)] if skip else []
    if k_valid is None:
        k_valid = jnp.ones(k_pos.shape, bool)
    return bounds + [row(q_pos), row(k_pos), row(k_valid)]


def _mask_specs(block_q: int, block_k: int, index_q, index_k):
    """Block specs of `_mask_args`' rows, each block (1, seq-block), a
    shape the TPU tiling accepts. `index_q(*grid, *prefetched) -> (b,
    q-block)`, likewise `index_k`."""
    q_spec = pl.BlockSpec((1, 1, block_q),
                          lambda *ix: (index_q(*ix)[0], 0, index_q(*ix)[1]))
    k_spec = pl.BlockSpec((1, 1, block_k),
                          lambda *ix: (index_k(*ix)[0], 0, index_k(*ix)[1]))
    return [q_spec, k_spec, k_spec]


def _pallas_call(kernel, grid, in_specs, out_specs, out_shape, scratch,
                 interpret: bool, skip: bool):
    """pl.pallas_call over `grid`, its last axis sequential. A call that
    skips dead tiles has the `_tile_bounds` read into SMEM before the
    grid runs (its first operand), and its index maps take them last."""
    params = _params("parallel", "parallel", "parallel", "arbitrary")
    if skip:
        return pl.pallas_call(
            kernel, out_shape=out_shape, compiler_params=params,
            interpret=interpret,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )


# The pallas_call factories below are cached on their static configuration:
# call sites of one configuration (the layers of a model) then share one
# jitted call, and its kernel is traced once per process, not per site.


@functools.lru_cache(maxsize=None)
def _fwd_call(b: int, h: int, kh: int, hd: int, hb: int, block_q: int,
              block_k: int, nq: int, nk: int, dtype, causal: bool,
              window: int, prefix: int, masked: bool, interpret: bool):
    skip = masked and nk > 1
    kernel = functools.partial(_fwd_kernel, causal=causal, window=window,
                               prefix=prefix, masked=masked, skip=skip,
                               nq=nq, nk=nk, g=h // kh, hd=hd,
                               scale=hd ** -0.5)
    kv_block = _clamp(nq, nk, True) if skip else None
    q_spec, kv_spec, lse_spec = _head_block_specs(h, kh, hd, hb, block_q,
                                                  block_k, kv_block)
    specs = _mask_specs(
        block_q, block_k, lambda bi, hj, iq, ik, *_: (bi, iq),
        lambda bi, hj, iq, ik, *b: (bi, kv_block(bi, iq, ik, *b) if skip
                                    else ik)) if masked else []
    scratch = [] if nk == 1 else [
        pltpu.VMEM((hb, block_q, hd), jnp.float32),       # acc
        pltpu.VMEM((hb, block_q, 1), jnp.float32),        # m
        pltpu.VMEM((hb, block_q, 1), jnp.float32),        # l
    ]
    return _pallas_call(
        kernel, (b, h // hb, nq, nk), specs + [q_spec, kv_spec, kv_spec],
        [q_spec, lse_spec],
        [jax.ShapeDtypeStruct((b, nq * block_q, h * hd), dtype),
         jax.ShapeDtypeStruct((b, h, 1, nq * block_q), jnp.float32)],
        scratch, interpret, skip)


def flash_attention_fwd(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                        k_valid=None, prefix: int = 0, block_q: int = 512,
                        block_k: int = 512, return_lse: bool = False,
                        interpret: bool = False):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd] -> [B,Sq,H,hd] (+ LSE [B,H,Sq] f32).

    With a window, keys at positions below `prefix` stay visible. Sq/Sk
    need not divide the block sizes — inputs are padded to the block grid
    and outputs sliced back."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    hb, block_q, block_k = _plan(h, kh, hd, q.dtype.itemsize,
                                 min(block_q, sq), min(block_k, sk))
    q, k, v, q_pos, k_pos, k_valid = _pad_inputs(
        q, k, v, q_pos, k_pos, k_valid, block_q, block_k)
    masked = causal or window > 0 or k_valid is not None
    nq, nk = q.shape[1] // block_q, k.shape[1] // block_k
    args = _mask_args(q_pos, k_pos, k_valid, sq, block_q, block_k, nk > 1,
                      causal, window, prefix) if masked else []
    out, lse = _fwd_call(b, h, kh, hd, hb, block_q, block_k, nq, nk,
                         q.dtype, bool(causal), int(window), int(prefix),
                         masked, interpret)(*args, _slab(q), _slab(k),
                                            _slab(v))
    out = out.reshape(b, -1, h, hd)[:, :sq]
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


def _bwd_dq_kernel(*refs, causal: bool, window: int, prefix: int,
                   masked: bool, skip: bool, nq: int, nk: int, g: int,
                   hd: int, scale: float):
    mask, live, refs = _split_mask_refs(refs, masked, causal, window,
                                        prefix, (2, 3, nq, nk) if skip
                                        else None)
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,         # in
     dq_ref,                                              # out
     acc_ref) = refs                                      # scratch
    hb = q_ref.shape[2] // hd
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @_when(live)
    def _step():
        ok = mask()
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


def _bwd_dkv_kernel(*refs, causal: bool, window: int, prefix: int,
                    masked: bool, skip: bool, nq: int, nk: int, g: int,
                    hd: int, scale: float, with_dq: bool):
    mask, live, refs = _split_mask_refs(refs, masked, causal, window,
                                        prefix, (3, 2, nq, nk) if skip
                                        else None)
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

    @_when(live)
    def _step():
        ok = mask()
        for j in range(k_ref.shape[2] // hd):
            kj = _lanes(j, hd)
            k, v = k_ref[0, :, kj], v_ref[0, :, kj]            # [bk, hd]
            k32 = k.astype(jnp.float32)
            dk = dv = 0.0
            # the G query heads of this kv head, unrolled (G is a small
            # int)
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
                    dq_ref[0, :, qi] = (jax.lax.dot(ds, k32)
                                        * scale).astype(dq_ref.dtype)
            if nq == 1:     # scratch accumulators made it 7% slower here
                dk_ref[0, :, kj] = dk.astype(dk_ref.dtype)
                dv_ref[0, :, kj] = dv.astype(dv_ref.dtype)
            else:
                dk_acc[j] += dk
                dv_acc[j] += dv

    if nq == 1 and live is not None:
        @pl.when(jnp.logical_not(live))
        def _dead():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

    if nq > 1:
        @pl.when(iq == nq - 1)
        def _finalize():
            for j in range(k_ref.shape[2] // hd):
                dk_ref[0, :, _lanes(j, hd)] = dk_acc[j].astype(dk_ref.dtype)
                dv_ref[0, :, _lanes(j, hd)] = dv_acc[j].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _dkv_call(b: int, h: int, kh: int, hd: int, hb: int, block_q: int,
              block_k: int, nq: int, nk: int, dtypes, causal: bool,
              window: int, prefix: int, masked: bool, interpret: bool):
    """The dkv kernel's call (and dq's, with one kv block): grid (b,
    kv-head-block, kv-block, q-block), the G query heads of each kv head
    contiguous lanes of the q slab."""
    g, nkv = h // kh, hb // (h // kh)
    skip = masked and nk > 1
    # one kv block: each q block's dq is complete in the dkv step, which
    # saves the dq kernel's recompute (a fifth of the backward at
    # [256, 274, 12, 64] on a v5e)
    with_dq = nk == 1
    if skip:
        q_block = _clamp(nq, nk, False)
    else:
        def q_block(bi, ik, iq):
            return iq
    q_spec = pl.BlockSpec((1, block_q, hb * hd),
                          lambda bi, kj, ik, iq, *b:
                          (bi, q_block(bi, ik, iq, *b), kj))
    k_spec = pl.BlockSpec((1, block_k, nkv * hd),
                          lambda bi, kj, ik, iq, *_: (bi, ik, kj))
    lse_spec = pl.BlockSpec((1, hb, 1, block_q),
                            lambda bi, kj, ik, iq, *b:
                            (bi, kj, 0, q_block(bi, ik, iq, *b)))
    specs = _mask_specs(
        block_q, block_k,
        lambda bi, kj, ik, iq, *b: (bi, q_block(bi, ik, iq, *b)),
        lambda bi, kj, ik, iq, *_: (bi, ik)) if masked else []
    dq_shape = [jax.ShapeDtypeStruct((b, nq * block_q, h * hd), dtypes[0])] \
        if with_dq else []
    acc = [] if nq == 1 else [pltpu.VMEM((nkv, block_k, hd), jnp.float32)] * 2
    return _pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=window,
                          prefix=prefix, masked=masked, skip=skip, nq=nq,
                          nk=nk, scale=hd ** -0.5, g=g, hd=hd,
                          with_dq=with_dq),
        (b, kh // nkv, nk, nq),
        specs + [q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec],
        [q_spec] * with_dq + [k_spec, k_spec],
        dq_shape + [
            jax.ShapeDtypeStruct((b, nk * block_k, kh * hd), dtypes[1]),
            jax.ShapeDtypeStruct((b, nk * block_k, kh * hd), dtypes[2]),
        ],
        acc, interpret, skip)


@functools.lru_cache(maxsize=None)
def _dq_call(b: int, h: int, kh: int, hd: int, hb: int, block_q: int,
             block_k: int, nq: int, nk: int, dtype, causal: bool,
             window: int, prefix: int, masked: bool, interpret: bool):
    skip = masked and nk > 1
    kv_block = _clamp(nq, nk, True) if skip else None
    q_spec, kv_spec, lse_spec = _head_block_specs(h, kh, hd, hb, block_q,
                                                  block_k, kv_block)
    specs = _mask_specs(
        block_q, block_k, lambda bi, hj, iq, ik, *_: (bi, iq),
        lambda bi, hj, iq, ik, *b: (bi, kv_block(bi, iq, ik, *b) if skip
                                    else ik)) if masked else []
    return _pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=window,
                          prefix=prefix, masked=masked, skip=skip, nq=nq,
                          nk=nk, scale=hd ** -0.5, g=h // kh, hd=hd),
        (b, h // hb, nq, nk),
        specs + [q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        q_spec, jax.ShapeDtypeStruct((b, nq * block_q, h * hd), dtype),
        [pltpu.VMEM((hb, block_q, hd), jnp.float32)], interpret, skip)


def flash_attention_bwd(q, k, v, q_pos, k_pos, k_valid, out, lse, do, *,
                        causal=True, window=0, prefix: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """Blockwise VJP: (residuals, dO) -> (dq, dk, dv).

    Probabilities are recomputed from (q, k, lse) tile-by-tile; nothing
    [Sq, Sk]-shaped is ever live. `k_valid` may be None (every key
    valid)."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    itemsize = q.dtype.itemsize
    hb, block_q, block_k = _plan(h, kh, hd, itemsize, min(block_q, sq),
                                 min(block_k, sk), whole_groups=True)

    q_p, k_p, v_p, qp, kp, kv = _pad_inputs(q, k, v, q_pos, k_pos, k_valid,
                                            block_q, block_k)
    masked = causal or window > 0 or kv is not None
    pad_q = q_p.shape[1] - sq
    o_p, do_p = _pad_axis(out, 1, pad_q), _pad_axis(do, 1, pad_q)
    lse_p = _pad_axis(lse, 2, pad_q)[:, :, None, :]          # [B,H,1,Sq]
    nq, nk = q_p.shape[1] // block_q, k_p.shape[1] // block_k
    args = _mask_args(qp, kp, kv, sq, block_q, block_k, nk > 1, causal,
                      window, prefix) if masked else []
    slabs = [_slab(x) for x in (q_p, k_p, v_p, o_p, do_p)] + [lse_p]
    static = (bool(causal), int(window), int(prefix), masked, interpret)
    outs = _dkv_call(b, h, kh, hd, hb, block_q, block_k, nq, nk,
                     (q.dtype, k.dtype, v.dtype), *static)(*args, *slabs)
    dk, dv = outs[-2:]
    if nk == 1:
        dq = outs[0]
    else:
        hb, _, _ = _plan(h, kh, hd, itemsize, block_q, block_k)
        dq = _dq_call(b, h, kh, hd, hb, block_q, block_k, nq, nk, q.dtype,
                      *static)(*args, *slabs)

    def heads(x, n, s):
        return x.reshape(b, -1, n, hd)[:, :s]
    return heads(dq, h, sq), heads(dk, kh, sk), heads(dv, kh, sk)
