"""Mamba1 (selective SSM) block — falcon-mamba / Hymba SSM branch.

Train/prefill path uses a chunked selective scan: jax.lax.scan over sequence
chunks carrying the SSM state, jax.lax.associative_scan within a chunk.
Discretized operands (a = exp(dt*A), bx = dt*B*x) are materialized only per
chunk, so activation memory is O(B * chunk * d_inner * d_state) instead of
O(B * S * d_inner * d_state). This mirrors the Pallas kernel's grid
structure (repro.kernels.selective_scan).

Decode path is the O(1)-per-token recurrence on a cached state — this is
what makes the 524k-context cells feasible for the SSM/hybrid archs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.obs import recorder as _rec


def init_mamba(key, cfg, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    di = cfg.ssm.expand * d
    ds = cfg.ssm.d_state
    dc = cfg.ssm.d_conv
    dtr = cfg.ssm.dt_rank or -(-d // 16)
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A
    a_init = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :],
                      (di, 1))
    dt_std = dtr ** -0.5
    return {
        "in_proj": layers.dense_init(ks[0], (d, 2 * di)),
        "conv_w": layers.dense_init(ks[1], (dc, di), in_axis_size=dc),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": layers.dense_init(ks[2], (di, dtr + 2 * ds)),
        "dt_proj": (jax.random.uniform(ks[3], (dtr, di), jnp.float32,
                                       -dt_std, dt_std)),
        "dt_bias": jnp.log(jnp.expm1(  # softplus^-1 of dt in [1e-3, 1e-1]
            jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32,
                                       np.log(1e-3), np.log(1e-1))))),
        "A_log": jnp.log(a_init),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": layers.dense_init(ks[5], (di, d), in_axis_size=di),
    }


# ---------------------------------------------------------------------------
# Chunked selective scan (pure jnp; the Pallas kernel mirrors this)


def _ssm_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def chunked_selective_scan(x, dt, b_in, c_in, a_log, h0=None, chunk=256):
    """Selective scan y_t = C_t . h_t,  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    x, dt: [B, S, di]; b_in, c_in: [B, S, ds]; a_log: [di, ds].
    Returns (y [B, S, di], h_final [B, di, ds]). All scan math in f32."""
    bsz, s, di = x.shape
    ds = b_in.shape[-1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))

    a_neg = -jnp.exp(a_log.astype(jnp.float32))            # [di, ds]

    def to_chunks(t):
        return t.reshape(bsz, nc, chunk, t.shape[-1]).transpose(1, 0, 2, 3)

    xs = (to_chunks(x), to_chunks(dt), to_chunks(b_in), to_chunks(c_in))
    h_init = (jnp.zeros((bsz, di, ds), jnp.float32)
              if h0 is None else h0.astype(jnp.float32))

    # Each chunk is checkpointed: its discretized operands and associative-
    # scan levels ([B, chunk, di, ds] f32 apiece) are recomputed in the
    # backward pass from the carried state, instead of being stashed for
    # every chunk ([nc, B, chunk, di, ds] — tens of GB at hymba width).
    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def step(h, blk):
        xc, dtc, bc, cc = (t.astype(jnp.float32) for t in blk)
        a = jnp.exp(dtc[..., None] * a_neg)                # [B, c, di, ds]
        bx = (dtc * xc)[..., None] * bc[:, :, None, :]     # [B, c, di, ds]
        cum_a, h_local = jax.lax.associative_scan(
            _ssm_combine, (a, bx), axis=1)
        h_all = cum_a * h[:, None] + h_local               # [B, c, di, ds]
        y = jnp.einsum("bcns,bcs->bcn", h_all, cc)
        return h_all[:, -1], y

    h_final, ys = jax.lax.scan(step, h_init, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, nc * chunk, di)
    return y[:, :s].astype(x.dtype), h_final


def selective_scan_step(x, dt, b_in, c_in, a_log, h):
    """Single decode step. x, dt: [B, di]; b_in, c_in: [B, ds]; h: [B, di, ds]."""
    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)
    a_neg = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt32[..., None] * a_neg)
    bx = (dt32 * x32)[..., None] * b_in.astype(jnp.float32)[:, None, :]
    h_new = a * h.astype(jnp.float32) + bx
    y = jnp.einsum("bns,bs->bn", h_new, c_in.astype(jnp.float32))
    return y.astype(x.dtype), h_new


def resolve_ssm_impl(impl, backend, s, d_inner, *, has_cache, devices=1,
                     chunk=256):
    """(core, chunk, block_d) of a multi-token scan, from what it can
    observe, as attention's `resolve_impl` does.

    "auto" runs the Pallas scan on a single TPU device (a Mosaic kernel
    cannot be partitioned over several) for a cache-free call whose
    positions and channels the kernel's blocks tile
    (`selective_scan.fit_blocks`), and the jnp scan otherwise. An explicit
    "pallas" keeps the kernel's default blocks where none fit. block_d is
    None for the jnp core."""
    from repro.kernels import selective_scan as kss
    if impl == "auto":
        fits = kss.fit_blocks(s, d_inner, chunk)
        if (backend == "tpu" and devices == 1 and not has_cache
                and fits is not None):
            return ("pallas",) + fits
        return "jnp", chunk, None
    if impl == "pallas":
        return ("pallas",) + (kss.fit_blocks(s, d_inner, chunk)
                              or (chunk, 512))
    return impl, chunk, None


# ---------------------------------------------------------------------------
# Cache


def init_mamba_cache(cfg, batch: int, d_model: Optional[int] = None,
                     layer_count: Optional[int] = None,
                     dtype=jnp.bfloat16):
    d = d_model or cfg.d_model
    di = cfg.ssm.expand * d
    lead = () if layer_count is None else (layer_count,)
    return {
        "h": jnp.zeros(lead + (batch, di, cfg.ssm.d_state), jnp.float32),
        "conv": jnp.zeros(lead + (batch, cfg.ssm.d_conv - 1, di), dtype),
    }


# ---------------------------------------------------------------------------
# Block application


def _causal_depthwise_conv(x, w, b):
    """x [B, S, di], w [dc, di] depthwise causal conv along S."""
    dc = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    out = sum(
        xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
        for i in range(dc))
    return out + b.astype(x.dtype)


def apply_mamba(params, x, cfg, cache=None, impl="auto", chunk=256,
                bwd_impl="fused"):
    """x [B, S, D] -> (y [B, S, D], new_cache)."""
    d = x.shape[-1]
    di = cfg.ssm.expand * d
    ds = cfg.ssm.d_state
    dtr = params["dt_proj"].shape[0]
    dtype = x.dtype

    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dtype))
    xin, z = xz[..., :di], xz[..., di:]

    if cache is None:
        xc = _causal_depthwise_conv(xin, params["conv_w"], params["conv_b"])
        new_conv = None
    else:
        hist = cache["conv"].astype(dtype)                 # [B, dc-1, di]
        full = jnp.concatenate([hist, xin], axis=1)
        xc = _causal_depthwise_conv(full, params["conv_w"],
                                    params["conv_b"])[:, hist.shape[1]:]
        new_conv = full[:, -(cfg.ssm.d_conv - 1):]

    xc = jax.nn.silu(xc)

    proj = jnp.einsum("bsn,ne->bse", xc, params["x_proj"].astype(dtype))
    dt_in, b_in, c_in = (proj[..., :dtr], proj[..., dtr:dtr + ds],
                         proj[..., dtr + ds:])
    dt = jnp.einsum("bsr,rn->bsn", dt_in, params["dt_proj"].astype(dtype))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"]).astype(dtype)

    if cache is None or xc.shape[1] > 1:
        # train / prefill: chunked scan (optionally carrying a prior state)
        h0 = cache["h"] if cache is not None else None
        impl, chunk, block_d = resolve_ssm_impl(
            impl, jax.default_backend(), xc.shape[1], di,
            has_cache=cache is not None, devices=jax.device_count(),
            chunk=chunk)
        _rec.get().event("ssm/impl", impl=impl, s=xc.shape[1], d_inner=di,
                         chunk=chunk, block_d=block_d)
        with jax.named_scope("ssm_scan"):
            if impl == "pallas":
                from repro.kernels import ops as kops
                y, h_final = kops.selective_scan(
                    xc, dt, b_in, c_in, params["A_log"], h0, chunk, block_d,
                    bwd_impl)
            else:
                y, h_final = chunked_selective_scan(
                    xc, dt, b_in, c_in, params["A_log"], h0=h0, chunk=chunk)
        new_cache = None if cache is None else \
            {"h": h_final, "conv": new_conv}
    else:
        y1, h_new = selective_scan_step(
            xc[:, 0], dt[:, 0], b_in[:, 0], c_in[:, 0],
            params["A_log"], cache["h"])
        y = y1[:, None]
        new_cache = {"h": h_new, "conv": new_conv}

    y = y + xc * params["D"].astype(dtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsn,nd->bsd", y, params["out_proj"].astype(dtype))
    return out, new_cache
