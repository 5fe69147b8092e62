"""Hymba-style hybrid block: attention and Mamba heads run in PARALLEL over
the same normed input; branch outputs are per-branch RMSNormed and averaged
(adaptation of Hymba Sec. 2; the paper's learnable per-branch beta scalars
are included). Sliding-window attention on local layers, full attention on
cfg.global_layers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba


def init_hybrid(key, cfg):
    ka, km, kn = jax.random.split(key, 3)
    return {
        "attn": attention.init_attention(ka, cfg),
        "ssm": mamba.init_mamba(km, cfg),
        "attn_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        "ssm_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        "beta_attn": jnp.ones((), jnp.float32),
        "beta_ssm": jnp.ones((), jnp.float32),
    }


def apply_hybrid(params, x, cfg, *, positions, is_global, cache=None,
                 impl="auto", ssm_impl="jnp", ssm_bwd="fused",
                 seq_shard=False):
    """x [B, S, D] -> (y, new_cache). cache = {'kv': ..., 'ssm': ...}.

    is_global: static bool — full attention vs sliding window."""
    window = 0 if is_global else cfg.sliding_window
    kv_cache = cache["kv"] if cache is not None else None
    ssm_cache = cache["ssm"] if cache is not None else None

    with jax.named_scope("attention"):
        a_out, kv_new = attention.apply_attention(
            params["attn"], x, cfg, positions=positions, causal=True,
            window=window, cache=kv_cache, impl=impl, seq_shard=seq_shard)
    with jax.named_scope("ssm"):
        s_out, ssm_new = mamba.apply_mamba(
            params["ssm"], x, cfg, cache=ssm_cache, impl=ssm_impl,
            bwd_impl=ssm_bwd)

    a_out = layers.rms_norm(a_out, params["attn_norm"]["scale"])
    s_out = layers.rms_norm(s_out, params["ssm_norm"]["scale"])
    y = 0.5 * (a_out * params["beta_attn"].astype(a_out.dtype)
               + s_out * params["beta_ssm"].astype(s_out.dtype))

    new_cache = None
    if cache is not None:
        new_cache = {"kv": kv_new, "ssm": ssm_new}
    return y, new_cache
