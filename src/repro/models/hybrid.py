"""Hymba-style hybrid block: attention and Mamba heads run in PARALLEL over
the same normed input; branch outputs are per-branch RMSNormed and averaged
(adaptation of Hymba Sec. 2; the paper's learnable per-branch beta scalars
are included). Sliding-window attention on local layers, full attention on
cfg.global_layers.

Two more mechanisms of the published model are ModelConfig fields, both
off by default (the registry's hymba-1.5b still has them off):

  * `meta_tokens` — learned tokens prepended to every sequence at the
    trunk's input (`core/mpsl.py`); local layers keep them visible beside
    their window (`prefix`), global layers see them through the causal
    mask;
  * `kv_share_groups` — pairs of consecutive local layers that share one
    K/V: the second layer has no wk/wv and attends with the first one's K
    and V (`shared_kv`), which carry RoPE at the same positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba


def init_hybrid(key, cfg, own_kv: bool = True):
    """The mixer's parameters; `own_kv=False` for the second layer of a
    K/V-sharing pair, which has no K/V projections."""
    ka, km, kn = jax.random.split(key, 3)
    attn = attention.init_attention(ka, cfg)
    if not own_kv:
        for name in ("wk", "wv", "bk", "bv", "k_norm"):
            attn.pop(name, None)
    return {
        "attn": attn,
        "ssm": mamba.init_mamba(km, cfg),
        "attn_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        "ssm_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        "beta_attn": jnp.ones((), jnp.float32),
        "beta_ssm": jnp.ones((), jnp.float32),
    }


def apply_hybrid(params, x, cfg, *, positions, is_global, cache=None,
                 shared_kv=None):
    """x [B, S, D] -> (y, new_cache, kv). cache = {'kv': ..., 'ssm': ...}.

    is_global: static bool — full attention vs sliding window.
    shared_kv: the K/V ({'k', 'v', 'pos'}) of the first layer of a sharing
    pair, for the second; kv is the K/V this layer attended with."""
    window = 0 if is_global else cfg.sliding_window
    kv_cache = cache["kv"] if cache is not None else None
    ssm_cache = cache["ssm"] if cache is not None else None

    with jax.named_scope("attention"):
        a_out, kv_new, kv = attention.apply_attention(
            params["attn"], x, cfg, positions=positions, causal=True,
            window=window, cache=kv_cache, precomputed_kv=shared_kv,
            prefix=cfg.meta_tokens if window else 0, return_kv=True)
    with jax.named_scope("ssm"):
        s_out, ssm_new = mamba.apply_mamba(params["ssm"], x, cfg,
                                           cache=ssm_cache)

    a_out = layers.rms_norm(a_out, params["attn_norm"]["scale"])
    s_out = layers.rms_norm(s_out, params["ssm_norm"]["scale"])
    y = 0.5 * (a_out * params["beta_attn"].astype(a_out.dtype)
               + s_out * params["beta_ssm"].astype(s_out.dtype))

    new_cache = None
    if cache is not None:
        new_cache = {"kv": kv_new, "ssm": ssm_new}
    return y, new_cache, kv
