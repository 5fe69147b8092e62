"""Jit'd public wrappers around the Pallas kernels.

Training-grade custom VJPs: flash attention, the fused softmax-xent and
the selective scan run Pallas kernels in BOTH directions (flash/CE
recompute probabilities blockwise from the forward's LSE residual; the
scan recomputes in-chunk states from per-chunk boundary checkpoints —
nothing [S, S]-, [T, V]- or [B, S, di, ds]-shaped is ever live).
Quant-dequant is straight-through.
On this CPU container kernels execute in interpret mode; on TPU
`interpret=False`.

The key-validity mask (or None: every key valid, and no mask work in the
kernels) is threaded through the VJP residuals, so forward and backward
always see the identical mask — including under `jax.jit` where the
mask is a traced array and could not ride along as a static nondiff
argument.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import quant8 as _q8
from repro.kernels import ref as _ref
from repro.kernels import selective_scan as _ss
from repro.kernels import softmax_xent as _sx

INTERPRET = jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention


def flash_attention(q, k, v, q_pos, k_pos, causal=True, window=0,
                    k_valid=None, block_q=512, block_k=512):
    """Fused attention with a fused blockwise backward (see _fa module)."""
    return _flash_attention(q, k, v, q_pos, k_pos, k_valid, bool(causal),
                            int(window), int(block_q), int(block_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_attention(q, k, v, q_pos, k_pos, k_valid, causal, window,
                     block_q, block_k):
    return _fa.flash_attention_fwd(q, k, v, q_pos, k_pos, causal=causal,
                                   window=window, k_valid=k_valid,
                                   block_q=block_q, block_k=block_k,
                                   interpret=INTERPRET)


def _fa_fwd(q, k, v, q_pos, k_pos, k_valid, causal, window, block_q,
            block_k):
    out, lse = _fa.flash_attention_fwd(q, k, v, q_pos, k_pos, causal=causal,
                                       window=window, k_valid=k_valid,
                                       block_q=block_q, block_k=block_k,
                                       return_lse=True, interpret=INTERPRET)
    # residuals carry the mask: fwd/bwd agree by construction
    return out, (q, k, v, q_pos, k_pos, k_valid, out, lse)


def _fa_bwd(causal, window, block_q, block_k, res, g):
    q, k, v, q_pos, k_pos, k_valid, out, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, q_pos, k_pos, k_valid, out, lse, g, causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        interpret=INTERPRET)
    return dq, dk, dv, None, None, None


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# fused per-token softmax cross-entropy (LM head)


def softmax_xent_tokens(h, w, labels, block_t=256, block_v=512):
    """Per-token CE loss [T] from h [T, D], w [D, V], labels [T].

    Online softmax over vocab tiles in both directions; logits are never
    materialized at [T, V]."""
    return _softmax_xent(h, w, labels, int(block_t), int(block_v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _softmax_xent(h, w, labels, block_t, block_v):
    loss, _ = _sx.softmax_xent_fwd(h, w, labels, block_t=block_t,
                                   block_v=block_v, interpret=INTERPRET)
    return loss


def _sx_fwd(h, w, labels, block_t, block_v):
    loss, lse = _sx.softmax_xent_fwd(h, w, labels, block_t=block_t,
                                     block_v=block_v, interpret=INTERPRET)
    return loss, (h, w, labels, lse)


def _sx_bwd(block_t, block_v, res, g):
    h, w, labels, lse = res
    dh, dw = _sx.softmax_xent_bwd(h, w, labels, lse, g, block_t=block_t,
                                  block_v=block_v, interpret=INTERPRET)
    return dh, dw, None


_softmax_xent.defvjp(_sx_fwd, _sx_bwd)


# ---------------------------------------------------------------------------
# selective scan


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def selective_scan(x, dt, b_in, c_in, a_log, h0=None, chunk=256,
                   block_d=512, bwd="fused"):
    """Fused chunked scan; a nonzero h0 seeds the kernel's VMEM state
    directly (no jnp [B,S,di,ds] propagation term).

    ``bwd`` selects the backward lowering (run.impls["ssm_bwd"]):
    "fused" sweeps chunks in reverse through the Pallas adjoint kernel,
    recomputing in-chunk states from the forward's boundary checkpoints;
    "recompute" is the legacy jax.vjp through the jnp reference (kept as
    the oracle / fallback)."""
    return _ss.selective_scan_fwd(x, dt, b_in, c_in, a_log, h0,
                                  chunk=chunk, block_d=block_d,
                                  interpret=INTERPRET)


def _ss_fwd(x, dt, b_in, c_in, a_log, h0, chunk, block_d, bwd):
    y, h_final, h_ckpt = _ss.selective_scan_fwd(
        x, dt, b_in, c_in, a_log, h0, chunk=chunk, block_d=block_d,
        return_ckpt=True, interpret=INTERPRET)
    return (y, h_final), (x, dt, b_in, c_in, a_log, h0, h_ckpt)


def _ss_bwd(chunk, block_d, bwd, res, g):
    x, dt, b_in, c_in, a_log, h0, h_ckpt = res
    gy, gh = g

    if bwd == "recompute":
        if h0 is None:
            def f(x, dt, b_in, c_in, a_log):
                return _ref.selective_scan_ref(x, dt, b_in, c_in, a_log)
            _, vjp = jax.vjp(f, x, dt, b_in, c_in, a_log)
            return vjp((gy, gh)) + (None,)

        def f(x, dt, b_in, c_in, a_log, h0):
            return _ref.selective_scan_ref(x, dt, b_in, c_in, a_log, h0)
        _, vjp = jax.vjp(f, x, dt, b_in, c_in, a_log, h0)
        return vjp((gy, gh))

    dx, ddt, db, dc, da_log, dh0 = _ss.selective_scan_bwd(
        x, dt, b_in, c_in, a_log, h_ckpt, gy, gh, chunk=chunk,
        block_d=block_d, interpret=INTERPRET)
    return (dx, ddt, db.astype(b_in.dtype), dc.astype(c_in.dtype),
            da_log.astype(a_log.dtype),
            None if h0 is None else dh0.astype(h0.dtype))


selective_scan.defvjp(_ss_fwd, _ss_bwd)


# ---------------------------------------------------------------------------
# quant-dequant (straight-through)


def quant_dequant(x, key=None, bits: int = 8):
    """Fused quant-dequant; stochastic rounding when a PRNG key is given.

    The cotangent is straight-through (identity)."""
    if key is None:
        return _quant_dequant_det(x, int(bits))
    return _quant_dequant_sr(x, key, int(bits))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _quant_dequant_det(x, bits):
    return _q8.quant_dequant_fwd(x, bits=bits, interpret=INTERPRET)


def _qd_fwd(x, bits):
    return _quant_dequant_det(x, bits), None


def _qd_bwd(_bits, _res, g):
    return (g,)


_quant_dequant_det.defvjp(_qd_fwd, _qd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _quant_dequant_sr(x, key, bits):
    return _q8.quant_dequant_fwd(x, key=key, bits=bits, interpret=INTERPRET)


def _qdsr_fwd(x, key, bits):
    return _quant_dequant_sr(x, key, bits), None


def _qdsr_bwd(_bits, _res, g):
    return g, None


_quant_dequant_sr.defvjp(_qdsr_fwd, _qdsr_bwd)
