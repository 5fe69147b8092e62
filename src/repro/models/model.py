"""Model assembly: init + forward for every assigned architecture family.

The transformer body is represented as a list of SEGMENTS — runs of
consecutive layers with identical static structure — each stored as a
stacked pytree (leading layer axis) and executed with jax.lax.scan.
Homogeneous archs have one segment; Hymba splits at its global-attention
layers and scans a K/V-sharing pair of layers as one step; Whisper has
separate encoder and decoder stacks. Scan-over-layers keeps compile time
flat in depth (94-layer qwen3 compiles like 2 layers) and jax.checkpoint
around the scanned step gives per-block remat.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, hybrid, layers, mamba, mlp, moe
from repro.parallel import sharding


@dataclasses.dataclass(frozen=True)
class BlockKind:
    family: str                 # dense | moe | ssm | hybrid | vit | enc | dec
    is_global: bool = True      # full vs sliding-window attention
    causal: bool = True
    cross: bool = False         # cross-attention (whisper decoder)
    kv_pair: bool = False       # two hybrid layers, the second attending
                                # with the first's K/V (one scan step)

    @property
    def has_attn(self) -> bool:
        return self.family in ("dense", "moe", "vit", "enc", "dec")

    @property
    def has_mlp(self) -> bool:
        return self.family != "ssm"


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: BlockKind
    count: int                  # layers

    @property
    def steps(self) -> int:
        """Scan steps: one per layer, or per K/V-sharing pair."""
        return self.count // 2 if self.kind.kv_pair else self.count


def stacked_layers(seg_params) -> int:
    """Layers in a stacked segment's params (a K/V-sharing pair's step
    holds two, under "first" and "second")."""
    n = jax.tree_util.tree_leaves(seg_params)[0].shape[0]
    return 2 * n if "first" in seg_params else n


def kv_share_pairs(cfg) -> List[int]:
    """First layers of cfg.kv_share_groups' pairs, checked: each group is
    one local layer, or two consecutive ones."""
    glb = set(cfg.global_layers)
    firsts = []
    for g in cfg.kv_share_groups:
        g = tuple(g)
        if len(g) not in (1, 2) or any(i in glb or not 0 <= i <
                                       cfg.num_layers for i in g):
            raise ValueError(f"a K/V-sharing group is one local layer or two "
                             f"consecutive ones: {g}")
        if len(g) == 2:
            if g[1] != g[0] + 1:
                raise ValueError(f"K/V-sharing pair {g} is not consecutive")
            firsts.append(g[0])
    return firsts


def body_segments(cfg) -> List[Segment]:
    """Static segment plan for the (decoder-side) body."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return [Segment(BlockKind("dense"), cfg.num_layers)]
    if fam == "moe":
        return [Segment(BlockKind("moe"), cfg.num_layers)]
    if fam == "ssm":
        return [Segment(BlockKind("ssm"), cfg.num_layers)]
    if fam == "hybrid":
        segs, i = [], 0
        glb = set(cfg.global_layers)
        pairs = set(kv_share_pairs(cfg))
        while i < cfg.num_layers:
            kind = BlockKind("hybrid", is_global=i in glb,
                             kv_pair=i in pairs)
            n = 2 if kind.kv_pair else 1
            if segs and segs[-1].kind == kind:
                segs[-1] = Segment(kind, segs[-1].count + n)
            else:
                segs.append(Segment(kind, n))
            i += n
        return segs
    if fam == "vit":
        return [Segment(BlockKind("vit", causal=False), cfg.num_layers)]
    if fam == "audio":
        return [Segment(BlockKind("dec", cross=True), cfg.num_layers)]
    raise ValueError(f"unknown family {fam!r}")


def encoder_segments(cfg) -> List[Segment]:
    if cfg.encoder_layers:
        return [Segment(BlockKind("enc", causal=False), cfg.encoder_layers)]
    return []


# ---------------------------------------------------------------------------
# Block init / apply


def init_block(key, cfg, kind: BlockKind, own_kv: bool = True):
    if kind.kv_pair:
        one = dataclasses.replace(kind, kv_pair=False)
        k1, k2 = jax.random.split(key)
        return {"first": init_block(k1, cfg, one),
                "second": init_block(k2, cfg, one, own_kv=False)}
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": layers.init_norm(cfg.norm, cfg.d_model)}
    if kind.family == "ssm":
        p["ssm"] = mamba.init_mamba(ks[0], cfg)
        return p
    if kind.family == "hybrid":
        p["mix"] = hybrid.init_hybrid(ks[0], cfg, own_kv)
    else:
        p["attn"] = attention.init_attention(ks[0], cfg)
    if kind.cross:
        p["norm_cross"] = layers.init_norm(cfg.norm, cfg.d_model)
        p["cross"] = attention.init_attention(ks[1], cfg)
    p["norm2"] = layers.init_norm(cfg.norm, cfg.d_model)
    if cfg.moe and kind.family == "moe":
        p["moe"] = moe.init_moe(ks[2], cfg)
    else:
        p["mlp"] = mlp.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.activation)
    return p


def apply_block(params, x, cfg, kind: BlockKind, *, positions, cache=None,
                enc_out=None, cross_kv=None):
    """One transformer block, or a K/V-sharing pair of hybrid blocks.
    Returns (x, new_cache, aux_loss)."""
    if kind.kv_pair:
        if cache is not None:
            raise NotImplementedError("decoding through a K/V-sharing pair")
        one = dataclasses.replace(kind, kv_pair=False)
        x, _, a1, kv = _apply_block(params["first"], x, cfg, one,
                                    positions=positions)
        x, _, a2, _ = _apply_block(params["second"], x, cfg, one,
                                   positions=positions, shared_kv=kv)
        return x, None, a1 + a2
    return _apply_block(params, x, cfg, kind, positions=positions,
                        cache=cache, enc_out=enc_out,
                        cross_kv=cross_kv)[:3]


def _apply_block(params, x, cfg, kind: BlockKind, *, positions, cache=None,
                 enc_out=None, cross_kv=None, shared_kv=None):
    """One block: (x, new_cache, aux_loss, the K/V a hybrid block attended
    with, or None)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(x, params["norm1"], cfg.norm)

    new_cache = kv = None
    if kind.family == "ssm":
        with jax.named_scope("ssm"):
            out, new_cache = mamba.apply_mamba(params["ssm"], h, cfg,
                                               cache=cache)
        return x + out, new_cache, aux, None
    if kind.family == "hybrid":
        out, new_cache, kv = hybrid.apply_hybrid(
            params["mix"], h, cfg, positions=positions,
            is_global=kind.is_global, cache=cache, shared_kv=shared_kv)
        x = x + out
    else:
        window = 0 if kind.is_global else cfg.sliding_window
        with jax.named_scope("attention"):
            out, new_cache = attention.apply_attention(
                params["attn"], h, cfg, positions=positions,
                causal=kind.causal, window=window, cache=cache)
        x = x + out

    if kind.cross:
        h = layers.apply_norm(x, params["norm_cross"], cfg.norm)
        if cross_kv is not None:
            out, _ = attention.apply_attention(
                params["cross"], h, cfg, positions=positions, causal=False,
                precomputed_kv=cross_kv, use_rope=False)
        else:
            out, _ = attention.apply_attention(
                params["cross"], h, cfg, positions=positions, causal=False,
                kv_x=enc_out, use_rope=False)
        x = x + out

    h = layers.apply_norm(x, params["norm2"], cfg.norm)
    if "moe" in params:
        out, aux = moe.apply_moe(params["moe"], h, cfg)
    else:
        out = mlp.apply_mlp(params["mlp"], h, cfg.activation)
    x = x + out
    x = sharding.shard_act(x, ("batch", None, None))
    return x, new_cache, aux, kv


# ---------------------------------------------------------------------------
# Segment init / scan


def init_segment(key, cfg, seg: Segment):
    keys = jax.random.split(key, seg.steps)
    return jax.vmap(lambda k: init_block(k, cfg, seg.kind))(keys)


def init_segment_cache(cfg, seg: Segment, batch: int, cache_len: int,
                       dtype=jnp.bfloat16):
    kind = seg.kind
    if kind.kv_pair:
        raise NotImplementedError("a cache for a K/V-sharing pair")
    if kind.family == "ssm":
        return mamba.init_mamba_cache(cfg, batch, layer_count=seg.count,
                                      dtype=dtype)
    if kind.family == "hybrid":
        win = cache_len if kind.is_global else \
            min(cfg.sliding_window, cache_len)
        return {
            "kv": attention.init_cache(cfg, batch, win, dtype, seg.count),
            "ssm": mamba.init_mamba_cache(cfg, batch, layer_count=seg.count,
                                          dtype=dtype),
        }
    return attention.init_cache(cfg, batch, cache_len, dtype, seg.count)


def apply_segment(params, x, cfg, seg: Segment, *, positions, cache=None,
                  enc_out=None, cross_kv=None, remat=True):
    """Scan a stacked segment. Returns (x, new_cache, aux_sum).

    Train path (no cache): layer params are scan xs. Serve path: the
    stacked cache is a scan CARRY updated in place with dynamic-update-
    slice on the layer dim — the while loop then aliases the buffer
    instead of allocating a second stacked cache as scan outputs would."""
    if cache is None:
        def step(carry, xs):
            h, aux = carry
            lp, ckv = xs
            y, _, a = apply_block(lp, h, cfg, seg.kind, positions=positions,
                                  enc_out=enc_out, cross_kv=ckv)
            return (y, aux + a), None

        if remat:
            step = jax.checkpoint(
                step, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), _ = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.float32)), (params, cross_kv))
        return x, None, aux

    tmap = jax.tree_util.tree_map

    def step_cached(carry, xs):
        h, aux, c, i = carry
        lp, ckv = xs
        lc = tmap(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), c)
        y, nc, a = apply_block(lp, h, cfg, seg.kind, positions=positions,
                               cache=lc, enc_out=enc_out, cross_kv=ckv)
        c = tmap(lambda buf, new: jax.lax.dynamic_update_index_in_dim(
            buf, new.astype(buf.dtype), i, 0), c, nc)
        return (y, aux + a, c, i + 1), None

    (x, aux, new_cache, _), _ = jax.lax.scan(
        step_cached,
        (x, jnp.zeros((), jnp.float32), cache, jnp.zeros((), jnp.int32)),
        (params, cross_kv))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model init


def init_lm(key, cfg):
    """Full model params: embed + body segments (+ encoder) + final norm + head."""
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    embed: Dict[str, Any] = {
        "table": layers.dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                   in_axis_size=cfg.d_model)}
    if cfg.pos_embed == "learned":
        embed["pos"] = layers.dense_init(
            ks[1], (cfg.max_seq, cfg.d_model), in_axis_size=cfg.d_model)
    params["embed"] = embed

    segs = body_segments(cfg)
    seg_keys = jax.random.split(ks[2], len(segs))
    params["segments"] = [init_segment(k, cfg, s)
                          for k, s in zip(seg_keys, segs)]
    params["final_norm"] = layers.init_norm(cfg.norm, cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(
            ks[3], (cfg.d_model, cfg.vocab_size))

    enc_segs = encoder_segments(cfg)
    if cfg.meta_tokens:
        params["meta_tokens"] = layers.dense_init(
            ks[6], (cfg.meta_tokens, cfg.d_model), in_axis_size=cfg.d_model)

    if enc_segs:
        ek = jax.random.split(ks[4], len(enc_segs))
        params["encoder"] = {
            "segments": [init_segment(k, cfg, s)
                         for k, s in zip(ek, enc_segs)],
            "norm": layers.init_norm(cfg.norm, cfg.d_model),
            "pos": layers.dense_init(ks[5], (cfg.encoder_seq, cfg.d_model),
                                     in_axis_size=cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# Forward passes


def embed_tokens(params, tokens, cfg, positions=None, dtype=jnp.bfloat16):
    """Token ids [B, S] -> embeddings [B, S, D]."""
    h = params["embed"]["table"].astype(dtype)[tokens]
    if cfg.pos_embed == "learned":
        pos = positions if positions is not None else \
            layers.positions_from_shape(tokens.shape[0], tokens.shape[1])
        h = h + params["embed"]["pos"].astype(dtype)[pos]
    return sharding.shard_act(h, ("batch", None, None))


def run_encoder(params, frame_embeds, cfg, remat=True):
    """Whisper encoder over precomputed (stub) frame embeddings."""
    enc = params["encoder"]
    h = frame_embeds + enc["pos"].astype(frame_embeds.dtype)[None]
    positions = layers.positions_from_shape(h.shape[0], h.shape[1])
    for seg_params, seg in zip(enc["segments"], encoder_segments(cfg)):
        h, _, _ = apply_segment(seg_params, h, cfg, seg, positions=positions,
                                remat=remat)
    return layers.apply_norm(h, enc["norm"], cfg.norm)


def forward_body(params, h, cfg, *, positions, cache=None, enc_out=None,
                 cross_kv=None, remat=True):
    """Embeddings -> final hidden states. Returns (h, new_caches, aux).

    Meta tokens are prepended by the MPSL LM loss only."""
    if cfg.meta_tokens:
        raise NotImplementedError("forward_body with meta tokens")
    segs = body_segments(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Optional[List[Any]] = [] if cache is not None else None
    for i, (seg_params, seg) in enumerate(zip(params["segments"], segs)):
        seg_cache = cache[i] if cache is not None else None
        seg_ckv = cross_kv[i] if cross_kv is not None else None
        h, nc, aux = apply_segment(
            seg_params, h, cfg, seg, positions=positions, cache=seg_cache,
            enc_out=enc_out, cross_kv=seg_ckv, remat=remat)
        if new_caches is not None:
            new_caches.append(nc)
        aux_total = aux_total + aux
    h = layers.apply_norm(h, params["final_norm"], cfg.norm)
    return h, new_caches, aux_total


def lm_logits(params, h, cfg):
    # Tied archs may carry an explicitly trained head (MPSL fine-tuning
    # keeps the embedding frozen client-side but trains the tail copy).
    if "lm_head" in params:
        w = params["lm_head"]
    else:
        w = params["embed"]["table"].T
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))
    return sharding.shard_act(logits, ("batch", None, "model"))


def init_body_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16):
    return [init_segment_cache(cfg, seg, batch, cache_len, dtype)
            for seg in body_segments(cfg)]


def compute_cross_kv_stacked(params, enc_out, cfg):
    """Per-decoder-layer cross K/V, stacked along the layer axis."""
    out = []
    for seg_params, seg in zip(params["segments"], body_segments(cfg)):
        if not seg.kind.cross:
            out.append(None)
            continue
        ckv = jax.vmap(
            lambda p: attention.compute_cross_kv(p["cross"], enc_out, cfg)
        )(seg_params)
        out.append(ckv)
    return out


# ---------------------------------------------------------------------------
# Analytic parameter counts


def _attn_params(cfg) -> int:
    d, h, k, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                   cfg.resolved_head_dim)
    n = d * h * hd + 2 * d * k * hd + h * hd * d
    if cfg.qkv_bias:
        n += (h + 2 * k) * hd
    if cfg.qk_norm:
        n += 2 * hd
    return n


def _mlp_params(d, f, activation) -> int:
    return d * f * (3 if layers.gated_activation(activation) else 2)


def _mamba_params(cfg) -> int:
    d = cfg.d_model
    di, ds, dc = cfg.d_inner, cfg.ssm.d_state, cfg.ssm.d_conv
    dtr = cfg.dt_rank
    return (d * 2 * di + dc * di + di + di * (dtr + 2 * ds)
            + dtr * di + di + di * ds + di + di * d)


def _norm_params(cfg) -> int:
    return cfg.d_model * (2 if cfg.norm == "layernorm" else 1)


def _kv_params(cfg) -> int:
    d, k, hd = cfg.d_model, cfg.num_kv_heads, cfg.resolved_head_dim
    return 2 * d * k * hd + (2 * k * hd if cfg.qkv_bias else 0) + \
        (hd if cfg.qk_norm else 0)


def _block_params(cfg, kind: BlockKind) -> int:
    """Parameters of one layer (a K/V-sharing pair's mean, as its second
    layer has no K/V projections)."""
    if kind.kv_pair:
        one = dataclasses.replace(kind, kv_pair=False)
        return _block_params(cfg, one) - _kv_params(cfg) // 2
    n = _norm_params(cfg)
    if kind.family == "ssm":
        return n + _mamba_params(cfg)
    if kind.family == "hybrid":
        n += _attn_params(cfg) + _mamba_params(cfg) + 2 * cfg.d_model + 2
    else:
        n += _attn_params(cfg)
    if kind.cross:
        n += _norm_params(cfg) + _attn_params(cfg)
    n += _norm_params(cfg)
    if cfg.moe and kind.family == "moe":
        m = cfg.moe
        gated = 3 if layers.gated_activation(cfg.activation) else 2
        n += cfg.d_model * m.num_experts
        n += m.num_experts * cfg.d_model * m.d_ff_expert * gated
        if m.num_shared_experts:
            n += _mlp_params(cfg.d_model, m.d_ff_shared, cfg.activation)
            n += cfg.d_model
    else:
        n += _mlp_params(cfg.d_model, cfg.d_ff, cfg.activation)
    return n


def count_params_analytic(cfg, trainable_blocks: Optional[int] = None) -> int:
    """Total params, or params of the last `trainable_blocks` blocks only."""
    per_block = [(_block_params(cfg, seg.kind), seg.count)
                 for seg in body_segments(cfg)]
    if trainable_blocks is not None and trainable_blocks >= 0:
        want = min(trainable_blocks, cfg.num_layers)
        total, seen = 0, 0
        for n, count in reversed(per_block):
            take = min(count, want - seen)
            total += n * take
            seen += take
            if seen >= want:
                break
        return total
    total = sum(n * c for n, c in per_block)
    total += cfg.vocab_size * cfg.d_model           # embed
    if cfg.pos_embed == "learned":
        total += cfg.max_seq * cfg.d_model
    total += _norm_params(cfg) + cfg.meta_tokens * cfg.d_model
    if not cfg.tie_embeddings:
        total += cfg.d_model * cfg.vocab_size
    if cfg.encoder_layers:
        total += cfg.encoder_layers * (
            _block_params(cfg, BlockKind("enc", causal=False)))
        total += _norm_params(cfg) + cfg.encoder_seq * cfg.d_model
    return total
