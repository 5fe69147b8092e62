"""Mamba selective scan as a Pallas TPU kernel — fused fwd AND bwd.

TPU adaptation: the CUDA Mamba kernel relies on warp-level parallel scans
in shared memory; the TPU analogue blocks d_inner across the parallel
grid axes and sweeps sequence CHUNKS along the sequential grid axis, with
the SSM state living in VMEM scratch across chunks
(revolving state). Within a chunk the recurrence is stepped by a
fori_loop on the VPU — d_state(16) x block_d lanes per step keep the
vector units busy while the state never leaves VMEM.

Checkpointed-recompute memory model (backward): the forward additionally
emits the chunk-boundary states ``h_ckpt`` (the state
*entering* each chunk — ``h_ckpt[:, 0]`` is h0). The backward sweeps the
chunk axis in REVERSE along the sequential grid axis; inside each chunk it
recomputes the per-step states from that chunk's checkpoint into a
``[chunk + 1, d_state, block_d]`` VMEM scratch, then runs the adjoint
recurrence backward through the chunk, carrying the state cotangent
lambda in VMEM across chunks. Nothing ``[B, S, di, ds]``-shaped ever
materializes in either direction: the residual footprint is the inputs
plus ``h_ckpt`` (S/chunk times smaller than the full state history), and
the live backward working set is one chunk of recomputed states.

Grid: (B, d_inner / block_d, S / chunk)   (last axis sequential on TPU)

Layout: the state is held TRANSPOSED, h^T [d_state, block_d], so d_inner
sits on the 128-wide lane axis and a timestep's x / dt row [1, block_d]
broadcasts over the d_state sublanes. Rows are read and written in
aligned groups of ``sub`` timesteps (8, or 16 for the packed bf16 tile),
stepped with a static unroll inside the group: a single-row access at a
dynamic offset is not provably tile-aligned, which the TPU compiler
refuses. B / C rows of a group are transposed once into [d_state, sub]
columns. The chunk-boundary checkpoints keep the transposed layout
(``h_ckpt [B, nchunks, ds, di]``); they are only ever read back by the
backward kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _group_size(chunk: int) -> int:
    """Timesteps per aligned row group: a whole packed bf16 tile (16 rows)
    where the chunk allows it, else an f32 tile (8 rows)."""
    assert chunk % 8 == 0, f"chunk {chunk} must be a multiple of 8"
    return 16 if chunk % 16 == 0 else 8


def _rows(ref, t0, sub):
    """[sub, n] f32 rows t0 .. t0+sub of a (1, chunk, n) block."""
    return ref[0, pl.ds(t0, sub), :].astype(jnp.float32)


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,   # inputs
            y_ref, hout_ref, hckpt_ref,                   # outputs
            h_ref,                                        # scratch [ds, bd]
            *, nchunks: int, chunk: int):
    ic = pl.program_id(2)
    sub = _group_size(chunk)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    # checkpoint the state ENTERING this chunk (bwd recomputes from here)
    hckpt_ref[0, 0] = h_ref[...]

    a_neg = -jnp.exp(a_ref[...].astype(jnp.float32))      # A^T [ds, bd]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (sub, a_neg.shape[1]), 0)

    def group(gi, h):
        t0 = pl.multiple_of(gi * sub, sub)
        xs, dts = _rows(x_ref, t0, sub), _rows(dt_ref, t0, sub)
        bs = _rows(b_ref, t0, sub).T                      # [ds, sub]
        cs = _rows(c_ref, t0, sub).T
        ys = jnp.zeros(xs.shape, jnp.float32)
        for j in range(sub):
            dtt = dts[j:j + 1]                            # [1, bd]
            a = jnp.exp(dtt * a_neg)                      # [ds, bd]
            h = a * h + bs[:, j:j + 1] * (dtt * xs[j:j + 1])
            yt = jnp.sum(h * cs[:, j:j + 1], axis=0, keepdims=True)
            ys = jnp.where(row_id == j, yt, ys)
        y_ref[0, pl.ds(t0, sub), :] = ys.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // sub, group, h_ref[...])
    h_ref[...] = h

    @pl.when(ic == nchunks - 1)
    def _final():
        hout_ref[0] = h


def fit_blocks(s: int, di: int, chunk: int = 256, block_d: int = 512):
    """(chunk, block_d) that tile a scan of ``s`` positions over ``di``
    channels with no padding, or None where none does.

    ``block_d`` is kept where it divides ``di``; else the largest multiple
    of 128 lanes up to 1024 that does (640 for hymba-1.5b's 3200). The
    chunk is the largest multiple of 16 rows up to ``chunk`` that divides
    ``s`` (192 for 4096 text positions after 128 meta tokens)."""
    if di % block_d:
        fits = [b for b in range(1024, 127, -128) if di % b == 0]
        if not fits:
            return None
        block_d = fits[0]
    fits = [c for c in range(min(chunk, s) // 16 * 16, 15, -16)
            if s % c == 0]
    return (fits[0], block_d) if fits else None


def _resolve_blocks(s, di, chunk, block_d):
    block_d = min(block_d, di)
    chunk = min(chunk, s)
    assert di % block_d == 0 and s % chunk == 0, (di, block_d, s, chunk)
    return chunk, block_d


def selective_scan_fwd(x, dt, b_in, c_in, a_log, h0=None, *,
                       chunk: int = 256, block_d: int = 512,
                       interpret: bool = False, return_ckpt: bool = False):
    """x, dt [B,S,di]; b_in, c_in [B,S,ds]; a_log [di,ds]; h0 [B,di,ds].

    Returns (y [B,S,di], h_final [B,di,ds]) — plus the chunk-boundary
    checkpoints h_ckpt [B, nchunks, ds, di] (transposed state layout) when
    ``return_ckpt`` (the backward's residual)."""
    bsz, s, di = x.shape
    ds = b_in.shape[-1]
    chunk, block_d = _resolve_blocks(s, di, chunk, block_d)
    nd, nc = di // block_d, s // chunk

    h0_t = (jnp.zeros((bsz, ds, di), jnp.float32) if h0 is None
            else h0.astype(jnp.float32).transpose(0, 2, 1))

    grid = (bsz, nd, nc)
    kernel = functools.partial(_kernel, nchunks=nc, chunk=chunk)
    seq = pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d))
    seq_state = pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0))
    state = pl.BlockSpec((1, ds, block_d), lambda b, d, c: (b, 0, d))
    y, h_final, h_ckpt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq, seq, seq_state, seq_state,
            pl.BlockSpec((ds, block_d), lambda b, d, c: (0, d)),
            state,
        ],
        out_specs=[
            seq, state,
            pl.BlockSpec((1, 1, ds, block_d), lambda b, d, c: (b, c, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, ds, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, ds, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, block_d), jnp.float32)],
        interpret=interpret,
    )(x, dt, b_in, c_in, a_log.T, h0_t)
    h_final = h_final.transpose(0, 2, 1)
    if return_ckpt:
        return y, h_final, h_ckpt
    return y, h_final


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, hk_ref, gy_ref, gh_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dh0_ref,
                hs_ref, g_ref,
                *, nchunks: int, chunk: int):
    """Adjoint of the chunked recurrence, chunks visited in REVERSE.

    For h_t = a_t h_{t-1} + (dt_t x_t) B_t, y_t = h_t . C_t the state
    cotangent obeys lambda_t = a_{t+1} lambda_{t+1} + gy_t C_t; the carry
    g = a_t lambda_t flows right-to-left across chunks in VMEM (and is the
    h0 cotangent once chunk 0 has been processed). All states are in the
    transposed [ds, bd] layout; ``hs_ref[t + 1]`` is the state after
    in-chunk step t and ``hs_ref[0]`` the state entering the chunk."""
    ic = pl.program_id(2)
    sub = _group_size(chunk)
    ngroups = chunk // sub

    a_neg = -jnp.exp(a_ref[...].astype(jnp.float32))      # A^T [ds, bd]
    ds_, bd = a_neg.shape
    row_id = jax.lax.broadcasted_iota(jnp.int32, (sub, bd), 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (ds_, sub), 1)

    # 1) recompute the in-chunk states from the boundary checkpoint
    hs_ref[0] = hk_ref[0, 0]

    def fwd_group(gi, h):
        t0 = pl.multiple_of(gi * sub, sub)
        xs, dts = _rows(x_ref, t0, sub), _rows(dt_ref, t0, sub)
        bs = _rows(b_ref, t0, sub).T
        for j in range(sub):
            dtt = dts[j:j + 1]
            h = (jnp.exp(dtt * a_neg) * h
                 + bs[:, j:j + 1] * (dtt * xs[j:j + 1]))
            hs_ref[t0 + j + 1] = h
        return h

    jax.lax.fori_loop(0, ngroups, fwd_group, hs_ref[0])

    @pl.when(ic == 0)
    def _init():
        g_ref[...] = gh_ref[0]                            # lambda from h_final
        da_ref[...] = jnp.zeros_like(da_ref)

    # 2) adjoint sweep, t = chunk-1 .. 0
    def bwd_group(i, carry):
        g, da = carry
        t0 = pl.multiple_of((ngroups - 1 - i) * sub, sub)
        xs, dts = _rows(x_ref, t0, sub), _rows(dt_ref, t0, sub)
        gys = _rows(gy_ref, t0, sub)
        bs = _rows(b_ref, t0, sub).T                      # [ds, sub]
        cs = _rows(c_ref, t0, sub).T
        dx8 = jnp.zeros(xs.shape, jnp.float32)
        ddt8 = jnp.zeros(xs.shape, jnp.float32)
        db8 = jnp.zeros(bs.shape, jnp.float32)
        dc8 = jnp.zeros(bs.shape, jnp.float32)
        for j in reversed(range(sub)):
            dtt, xt, gyt = dts[j:j + 1], xs[j:j + 1], gys[j:j + 1]
            ht = hs_ref[t0 + j + 1]
            hprev = hs_ref[t0 + j]
            lam = g + cs[:, j:j + 1] * gyt                # [ds, bd]
            a = jnp.exp(dtt * a_neg)
            sb = jnp.sum(lam * bs[:, j:j + 1], axis=0, keepdims=True)
            dadt = lam * hprev * a                        # d(a_t), times a_t
            dc8 = jnp.where(col_id == j, jnp.sum(ht * gyt, axis=1,
                                                 keepdims=True), dc8)
            db8 = jnp.where(col_id == j, jnp.sum(lam * (dtt * xt), axis=1,
                                                 keepdims=True), db8)
            dx8 = jnp.where(row_id == j, dtt * sb, dx8)
            ddt8 = jnp.where(
                row_id == j,
                xt * sb + jnp.sum(dadt * a_neg, axis=0, keepdims=True), ddt8)
            da = da + dadt * dtt * a_neg                  # dA_log = dA * A
            g = a * lam
        dx_ref[0, pl.ds(t0, sub), :] = dx8.astype(dx_ref.dtype)
        ddt_ref[0, pl.ds(t0, sub), :] = ddt8.astype(ddt_ref.dtype)
        db_ref[0, 0, pl.ds(t0, sub), :] = db8.T
        dc_ref[0, 0, pl.ds(t0, sub), :] = dc8.T
        return g, da

    g, da = jax.lax.fori_loop(
        0, ngroups, bwd_group,
        (g_ref[...], jnp.zeros((ds_, bd), jnp.float32)))
    g_ref[...] = g
    da_ref[0] += da

    @pl.when(ic == nchunks - 1)
    def _final():
        dh0_ref[0] = g                                    # = a_0 lambda_0


# The backward holds a chunk of recomputed states, [chunk + 1, ds, block_d]
# f32 (8.4 MB at 256 x 16 x 512), next to double-buffered row blocks.
_BWD_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def selective_scan_bwd(x, dt, b_in, c_in, a_log, h_ckpt, gy, gh, *,
                       chunk: int = 256, block_d: int = 512,
                       interpret: bool = False):
    """Fused backward. Returns (dx, ddt, dB, dC, dA_log, dh0); dx/ddt in
    the input dtypes, the rest f32 (caller casts). dB/dC are accumulated
    over d_inner blocks and dA_log over batch OUTSIDE the kernel — those
    partials are [B, nd, S, ds] / [B, di, ds], never [B, S, di, ds]."""
    bsz, s, di = x.shape
    ds = b_in.shape[-1]
    chunk, block_d = _resolve_blocks(s, di, chunk, block_d)
    nd, nc = di // block_d, s // chunk

    grid = (bsz, nd, nc)
    kernel = functools.partial(_bwd_kernel, nchunks=nc, chunk=chunk)
    rev = pl.BlockSpec((1, chunk, block_d),
                       lambda b, d, c: (b, nc - 1 - c, d))
    rev_state = pl.BlockSpec((1, chunk, ds),
                             lambda b, d, c: (b, nc - 1 - c, 0))
    state = pl.BlockSpec((1, ds, block_d), lambda b, d, c: (b, 0, d))
    dx, ddt, db_blk, dc_blk, da_blk, dh0 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            rev, rev, rev_state, rev_state,
            pl.BlockSpec((ds, block_d), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, 1, ds, block_d),
                         lambda b, d, c: (b, nc - 1 - c, 0, d)),
            rev,
            state,
        ],
        out_specs=[
            rev, rev,
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda b, d, c: (b, d, nc - 1 - c, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda b, d, c: (b, d, nc - 1 - c, 0)),
            state, state,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, s, di), dt.dtype),
            jax.ShapeDtypeStruct((bsz, nd, s, ds), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nd, s, ds), jnp.float32),
            jax.ShapeDtypeStruct((bsz, ds, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, ds, di), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, ds, block_d), jnp.float32),  # states
            pltpu.VMEM((ds, block_d), jnp.float32),            # lambda carry
        ],
        compiler_params=_BWD_PARAMS,
        interpret=interpret,
    )(x, dt, b_in, c_in, a_log.T, h_ckpt, gy,
      gh.astype(jnp.float32).transpose(0, 2, 1))
    db = db_blk.sum(axis=1)                                  # [B, S, ds]
    dc = dc_blk.sum(axis=1)
    da_log = da_blk.sum(axis=0).T                            # [di, ds]
    return dx, ddt, db, dc, da_log, dh0.transpose(0, 2, 1)
