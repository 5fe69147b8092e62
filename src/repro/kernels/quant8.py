"""Fused int-quant-dequant Pallas kernel for the MPSL smashed-data links.

The uplink/downlink compression (core.compression) is pure elementwise +
row-reduction work; fusing scale computation, rounding and dequant into
one VMEM pass keeps it bandwidth-bound at one read + one write per
element instead of the four passes the unfused lowering takes.

Grid: (rows / block_rows,). Each step loads a [block_rows, d] tile,
computes per-row absmax scales on the VPU, quantizes and immediately
dequantizes (training-side straight-through value).

Stochastic rounding (unbiased: E[q] = x/scale) has two lowerings:
  * compiled TPU — the per-core hardware PRNG, seeded from a scalar
    input folded with the grid step (`pltpu.prng_seed`), generating one
    uint32 per element in-kernel: still one read + one write per element.
  * interpret mode (CPU) — the TPU PRNG primitives have no CPU lowering,
    so uniform offsets are generated OUTSIDE with the threaded
    `jax.random` key and streamed as a second input tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_det(y):
    return jnp.round(y)


def _kernel(x_ref, y_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax,
                        1e-12)
    q = jnp.clip(_round_det(x / scale), -qmax, qmax)
    y_ref[...] = (q * scale).astype(y_ref.dtype)


def _kernel_sr_threaded(x_ref, u_ref, y_ref, *, qmax: float):
    """Stochastic rounding with uniforms streamed in (interpret mode)."""
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax,
                        1e-12)
    q = jnp.floor(x / scale + u_ref[...].astype(jnp.float32))
    y_ref[...] = (jnp.clip(q, -qmax, qmax) * scale).astype(y_ref.dtype)


def _kernel_sr_tpu(seed_ref, x_ref, y_ref, *, qmax: float):
    """Stochastic rounding with the TPU hardware PRNG (compiled mode)."""
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax,
                        1e-12)
    # top 24 random bits as a non-negative int32 (the TPU has no
    # uint32 -> float32 convert), scaled to U[0, 1)
    bits = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.int32)
    u = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) * (2.0 ** -24)
    q = jnp.floor(x / scale + u)
    y_ref[...] = (jnp.clip(q, -qmax, qmax) * scale).astype(y_ref.dtype)


def quant_dequant_fwd(x, *, key=None, bits: int = 8, block_rows: int = 256,
                      interpret: bool = False):
    """x [..., d] -> int-precision x̂ with per-row symmetric scales.

    key=None rounds to nearest; with a key, stochastic rounding keeps the
    quantizer unbiased (the MPSL link requirement)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    xr = x.reshape(rows, d)
    # uniforms are drawn pre-padding so the stream matches the unfused
    # jnp lowering element-for-element (same key => same rounding)
    u = None
    if key is not None and interpret:
        u = jax.random.uniform(key, xr.shape, jnp.float32)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        if u is not None:
            u = jnp.pad(u, ((0, pad), (0, 0)))
    nr = xr.shape[0] // block_rows
    qmax = 2.0 ** (bits - 1) - 1

    spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct(xr.shape, x.dtype)

    if key is None:
        y = pl.pallas_call(
            functools.partial(_kernel, qmax=qmax),
            grid=(nr,), in_specs=[spec], out_specs=spec,
            out_shape=out_shape, interpret=interpret,
        )(xr)
    elif interpret:
        y = pl.pallas_call(
            functools.partial(_kernel_sr_threaded, qmax=qmax),
            grid=(nr,), in_specs=[spec, spec], out_specs=spec,
            out_shape=out_shape, interpret=True,
        )(xr, u)
    else:
        seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max,
                                  jnp.int32)
        y = pl.pallas_call(
            functools.partial(_kernel_sr_tpu, qmax=qmax),
            grid=(nr,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
            out_specs=spec,
            out_shape=out_shape,
        )(seed, xr)
    if pad:
        y = y[:rows]
    return y.reshape(orig_shape)
