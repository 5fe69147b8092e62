"""Attention: GQA with RoPE / M-RoPE / learned positions, bias + qk-norm
variants, causal / full / sliding-window masks, blockwise (flash-style)
training path and KV-cache decode path.

Three interchangeable implementations of the core softmax(QK^T)V:
  * naive      — materializes scores; the oracle, decode, and short
                 sequences where the flash kernel does not run.
  * blockwise  — online-softmax scan over KV blocks, pure jnp. This is the
                 memory-efficient path for long sequences off a single
                 TPU device and mirrors the structure of the Pallas flash
                 kernel.
  * pallas     — the TPU flash kernel (repro.kernels); CPU-validated in
                 interpret mode. "auto" runs it on a single TPU device
                 for every multi-token call with no cache, at any key
                 count and with a visible prefix, and for a causal call
                 over precomputed K/V (the second layer of a K/V-sharing
                 pair) (`resolve_impl`). Its grid visits only the tiles
                 the causal / window / prefix mask leaves live, so a
                 windowed layer's work grows with its window, not with
                 the sequence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.obs import recorder as _rec

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Params


def init_attention(key, cfg):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, h, hd), in_axis_size=d),
        "wk": layers.dense_init(ks[1], (d, k, hd), in_axis_size=d),
        "wv": layers.dense_init(ks[2], (d, k, hd), in_axis_size=d),
        "wo": layers.dense_init(ks[3], (h, hd, d), in_axis_size=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((k, hd), jnp.float32)
        p["bv"] = jnp.zeros((k, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((hd,), jnp.float32)}
    return p


# ---------------------------------------------------------------------------
# Masks


def _mask_bias(q_pos, k_pos, causal: bool, window: int, k_valid=None,
               prefix: int = 0):
    """Additive bias [B, Sq, Sk] from absolute positions.

    q_pos [B, Sq], k_pos [B, Sk]; window > 0 keeps keys with
    q_pos - k_pos < window, and also (prefix > 0) the keys at positions
    below `prefix` (Hymba's meta tokens). k_valid optionally marks
    populated KV slots."""
    ok = jnp.ones(q_pos.shape[:1] + (q_pos.shape[1], k_pos.shape[1]), bool)
    if causal:
        ok &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window and window > 0:
        near = (q_pos[:, :, None] - k_pos[:, None, :]) < window
        if prefix:
            near |= k_pos[:, None, :] < prefix
        ok &= near
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core implementations


def _naive_attention(q, k, v, bias):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd], bias [B,Sq,Sk] -> [B,Sq,H,hd]."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5) + bias[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, sq, h, hd)


def _blockwise_attention(q, k, v, q_pos, k_pos, causal, window,
                         k_valid=None, block: int = 1024, prefix: int = 0):
    """Online-softmax scan over KV blocks. Memory O(Sq * block)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    sk = k.shape[1]
    nb = -(-sk // block)
    pad = nb * block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        valid = jnp.pad(
            k_valid if k_valid is not None
            else jnp.ones((b, sk), bool), ((0, 0), (0, pad)))
    else:
        valid = k_valid if k_valid is not None else jnp.ones((b, sk), bool)

    qg = (q * (hd ** -0.5)).reshape(b, sq, kh, g, hd)
    # [nb, B, block, ...] scan layout
    kb = k.reshape(b, nb, block, kh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block, kh, hd).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(b, nb, block).transpose(1, 0, 2)
    mb = valid.reshape(b, nb, block).transpose(1, 0, 2)

    acc0 = jnp.zeros((b, sq, kh, g, hd), jnp.float32)
    m0 = jnp.full((b, sq, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kh, g), jnp.float32)

    # The KV-block step is itself checkpointed: the block's scores /
    # probabilities are recomputed during the backward pass instead of
    # being stashed per block (this is precisely what the Pallas flash
    # kernel does on TPU; without it the residuals are O(Sq*Sk)).
    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def step(carry, blk):
        acc, m, l = carry
        kc, vc, pc, vm = blk
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kc,
                       preferred_element_type=jnp.float32)
        bias = _mask_bias(q_pos, pc, causal, window, vm, prefix)
        s = s + bias[:, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    (acc, _, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, pb, mb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Cache


def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
               layer_count: Optional[int] = None):
    """KV cache for `layer_count` stacked layers (or one layer if None)."""
    k, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    lead = () if layer_count is None else (layer_count,)
    return {
        "k": jnp.zeros(lead + (batch, cache_len, k, hd), dtype),
        "v": jnp.zeros(lead + (batch, cache_len, k, hd), dtype),
        "pos": jnp.full(lead + (batch, cache_len), -1, jnp.int32),
        "index": jnp.zeros(lead, jnp.int32),
    }


def _cache_insert(cache, k_new, v_new, positions):
    """Insert Sq new KV entries.

    Ring-buffered for window caches: the write offset is index % cache_len.
    Decode writes Sq == 1 (never straddles); prefill (Sq > 1) starts at
    index 0 — when the new sequence exceeds a window cache, only the last
    cache_len entries are kept (static-shape tail slice)."""
    cache_len = cache["k"].shape[1]
    sq = k_new.shape[1]
    if sq >= cache_len and sq > 1:            # prefill into a window cache
        k_new = k_new[:, -cache_len:]
        v_new = v_new[:, -cache_len:]
        positions = positions[:, -cache_len:]
        idx = jnp.zeros((), jnp.int32)
    else:
        idx = cache["index"] % cache_len

    def ins(buf, new):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), idx, axis=1)

    return {
        "k": ins(cache["k"], k_new),
        "v": ins(cache["v"], v_new),
        "pos": ins(cache["pos"], positions),
        "index": cache["index"] + sq,
    }


# ---------------------------------------------------------------------------
# Public entry


def resolve_impl(impl, backend, sq, sk, *, has_cache, has_precomputed_kv,
                 devices=1, causal=False):
    """The core implementation a call runs, from what it can observe.

    An explicit impl is kept, except that single-token decode never scans
    KV blocks: its scores are [B, H, 1, Sk], cheap to materialize, and a
    seq-sharded cache is not resharded into blocks. "auto" picks the
    Pallas flash kernel on a single TPU device (a Mosaic kernel cannot be
    partitioned over several) for a multi-token call with no cache, at
    any key count, with or without a visible prefix; precomputed K/V
    qualify when the call is causal (the sequence's own K/V, as the
    second layer of a K/V-sharing pair passes them), not when it is
    cross-attention over encoder K/V. Off that path, blockwise above 2048
    keys and naive otherwise."""
    if impl == "blockwise" and sq == 1:
        return "naive"
    if impl != "auto":
        return impl
    if (backend == "tpu" and devices == 1 and sq > 1 and not has_cache
            and (causal or not has_precomputed_kv)):
        return "pallas"
    if sq > 1 and sk > 2048:
        return "blockwise"
    return "naive"


def apply_attention(params, x, cfg, *, positions, causal=True, window=0,
                    cache=None, impl="auto", cos_sin=None, block=1024,
                    kv_x=None, kv_positions=None, precomputed_kv=None,
                    use_rope=None, prefix=0, return_kv=False):
    """x [B, S, D] -> (out [B, S, D], new_cache), and with `return_kv` the
    K/V attended, as {'k', 'v', 'pos'} (after RoPE).

    positions: [B, S] absolute positions (or [B, 3, S] for M-RoPE).
    cache: None for train/prefill-without-cache, else KV cache dict.
    kv_x / kv_positions: cross-attention source (keys/values from encoder).
    precomputed_kv: {'k','v','pos'} — decode-time cross-attention KV, or
      (Hymba) the K/V that the first layer of a sharing pair computed.
    prefix: with a window, keys at positions below it stay visible.
    """
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"]["scale"])

    if positions.ndim == 3:            # M-RoPE grids [B, 3, S]
        flat_pos = positions[:, 0]
    else:
        flat_pos = positions

    if precomputed_kv is None:
        src = x if kv_x is None else kv_x.astype(x.dtype)
        k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(x.dtype))
        if cfg.qkv_bias:
            k = k + params["bk"].astype(x.dtype)
            v = v + params["bv"].astype(x.dtype)
        if cfg.qk_norm:
            k = layers.rms_norm(k, params["k_norm"]["scale"])
    else:
        k = v = None

    rope_on = (cfg.pos_embed in ("rope", "mrope")) if use_rope is None \
        else use_rope
    if rope_on:
        if cos_sin is None:
            if cfg.pos_embed == "mrope":
                pos3 = positions if positions.ndim == 3 else \
                    jnp.broadcast_to(positions[:, None, :],
                                     (positions.shape[0], 3, positions.shape[1]))
                cos, sin = layers.mrope_cos_sin(
                    pos3, hd, cfg.rope_theta, cfg.mrope_sections)
            else:
                cos, sin = layers.rope_cos_sin(flat_pos, hd, cfg.rope_theta)
        else:
            cos, sin = cos_sin
        q = layers.apply_rope(q, cos, sin)
        if k is not None and kv_x is None:
            k = layers.apply_rope(k, cos, sin)

    if precomputed_kv is not None:
        k_all = precomputed_kv["k"].astype(x.dtype)
        v_all = precomputed_kv["v"].astype(x.dtype)
        k_pos, k_valid = precomputed_kv["pos"], None
    elif cache is not None and q.shape[1] > 1:
        # PREFILL: attend over the full fresh sequence (an empty/stale ring
        # cache cannot serve early queries' windows), then write the cache.
        cache = _cache_insert(cache, k, v, flat_pos)
        k_all, v_all, k_pos, k_valid = k, v, flat_pos, None
    elif cache is not None:
        cache = _cache_insert(cache, k, v, flat_pos)
        k_all, v_all = cache["k"].astype(x.dtype), cache["v"].astype(x.dtype)
        k_pos, k_valid = cache["pos"], cache["pos"] >= 0
    elif kv_x is not None:
        k_all, v_all = k, v
        k_pos = kv_positions if kv_positions is not None else \
            layers.positions_from_shape(kv_x.shape[0], kv_x.shape[1])
        k_valid = None
    else:
        k_all, v_all, k_pos, k_valid = k, v, flat_pos, None

    sk = k_all.shape[1]
    impl = resolve_impl(impl, jax.default_backend(), q.shape[1], sk,
                        has_cache=cache is not None,
                        has_precomputed_kv=precomputed_kv is not None,
                        devices=jax.device_count(), causal=causal)
    tiles = {}
    if impl == "pallas":
        from repro.kernels import flash_attention as _fa
        tiles = dict(zip(("tiles", "live_tiles"), _fa.tile_counts(
            q.shape[1], sk, h, kh, hd, q.dtype.itemsize, causal=causal,
            window=window, prefix=prefix)))
    _rec.get().event("attn/impl", impl=impl, sq=q.shape[1], sk=sk,
                     causal=causal, prefix=prefix, **tiles)

    if impl == "naive":
        bias = _mask_bias(flat_pos, k_pos, causal, window, k_valid, prefix)
        out = _naive_attention(q, k_all, v_all, bias)
    elif impl == "blockwise":
        out = _blockwise_attention(q, k_all, v_all, flat_pos, k_pos,
                                   causal, window, k_valid, block=block,
                                   prefix=prefix)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k_all, v_all, flat_pos, k_pos,
                                   causal=causal, window=window,
                                   k_valid=k_valid, prefix=prefix)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if return_kv:
        return y, cache, {"k": k_all, "v": v_all, "pos": k_pos}
    return y, cache


def compute_cross_kv(params, enc_out, cfg, positions=None):
    """Precompute cross-attention K/V from encoder output (decode path)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + params["bk"].astype(enc_out.dtype)
        v = v + params["bv"].astype(enc_out.dtype)
    if cfg.qk_norm:
        k = layers.rms_norm(k, params["k_norm"]["scale"])
    if positions is None:
        positions = layers.positions_from_shape(enc_out.shape[0],
                                                enc_out.shape[1])
    return {"k": k, "v": v, "pos": positions}
