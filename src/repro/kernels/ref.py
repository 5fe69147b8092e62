"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_attention_ref(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                        k_valid=None, prefix=0):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd] (GQA), absolute-position masking;
    with a window, keys at positions below `prefix` stay visible.

    Plain materialized-scores attention in f32."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    ok = jnp.ones((b, sq, k.shape[1]), bool)
    if causal:
        ok &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        ok &= (((q_pos[:, :, None] - k_pos[:, None, :]) < window)
               | (k_pos[:, None, :] < prefix))
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def selective_scan_ref(x, dt, b_in, c_in, a_log, h0=None):
    """Sequential reference of the Mamba recurrence, f32.

    x, dt [B,S,di]; b_in, c_in [B,S,ds]; a_log [di,ds].
    Returns (y [B,S,di], h_final [B,di,ds])."""
    bsz, s, di = x.shape
    ds = b_in.shape[-1]
    a_neg = -jnp.exp(a_log.astype(jnp.float32))
    h = jnp.zeros((bsz, di, ds), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    def step(h, t):
        xt = x[:, t].astype(jnp.float32)
        dtt = dt[:, t].astype(jnp.float32)
        bt = b_in[:, t].astype(jnp.float32)
        ct = c_in[:, t].astype(jnp.float32)
        a = jnp.exp(dtt[..., None] * a_neg)
        h = a * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.einsum("bns,bs->bn", h, ct)
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(s))
    return ys.transpose(1, 0, 2).astype(x.dtype), h


def softmax_xent_ref(h, w, labels):
    """Materialized-logits per-token CE (and LSE), f32.

    h [T, D], w [D, V], labels [T] -> (loss [T], lse [T])."""
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold, lse


def quant_dequant_ref(x, bits: int = 8):
    """Deterministic symmetric per-row (last-axis) int quant-dequant."""
    qmax = 2.0 ** (bits - 1) - 1
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / qmax,
                        1e-12)
    q = jnp.clip(jnp.round(x32 / scale), -qmax, qmax)
    return (q * scale).astype(x.dtype)
