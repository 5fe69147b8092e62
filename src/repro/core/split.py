"""The MPSL three-way split  W = [W_h ; W_b ; W_t]  (paper Sec. 3.1).

Parameters are partitioned into three top-level trees:

  client  — W_h: per-client lightweight tokenizer heads, STACKED along a
            leading client axis [N, ...]; never synchronized during
            training (paper Sec. 3.3: only a post-training FedAvg).
            For LM archs this is a low-rank tokenizer adapter on top of a
            frozen embedding table (DESIGN.md Sec. 2); for the paper's own
            ViT/Meta-Transformer configs it is the modality tokenizers.
  server  — W_b (the fine-tuned suffix of the unified encoder) + W_t
            (task head / LM head): shared, one copy, single backward pass.
  frozen  — pretrained weights that receive no updates but are still on
            the activation/gradient path (embedding table, the non-fine-
            tuned encoder prefix, whisper's encoder): stored in bf16 with
            no optimizer state.

The body boundary follows the paper's "fine-tune the last k blocks"
protocol; stacked scan segments are sliced at the boundary so the frozen
prefix and trainable suffix remain scannable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, model as M, tokenizers as tok
from repro.obs import comm as obs_comm


@dataclasses.dataclass(frozen=True)
class SplitPlan:
    cfg: Any
    mpsl: Any
    trainable_blocks: int
    segments_frozen: Tuple[M.Segment, ...]
    segments_train: Tuple[M.Segment, ...]

    @property
    def boundary(self) -> int:
        return self.cfg.num_layers - self.trainable_blocks


def resolve_trainable_blocks(cfg, mpsl) -> int:
    k = mpsl.trainable_blocks
    return cfg.num_layers if k < 0 else min(k, cfg.num_layers)


def split_segments(segs: List[M.Segment], boundary: int):
    """Split a Segment list at a layer boundary (counted from layer 0); a
    K/V-sharing pair stays on one side."""
    frozen, train, seen = [], [], 0
    for seg in segs:
        if seen + seg.count <= boundary:
            frozen.append(seg)
        elif seen >= boundary:
            train.append(seg)
        else:
            cut = boundary - seen
            if seg.kind.kv_pair and cut % 2:
                raise ValueError(f"the trainable boundary at layer "
                                 f"{boundary} splits a K/V-sharing pair")
            frozen.append(M.Segment(seg.kind, cut))
            train.append(M.Segment(seg.kind, seg.count - cut))
        seen += seg.count
    return frozen, train


def make_split_plan(cfg, mpsl) -> SplitPlan:
    k = resolve_trainable_blocks(cfg, mpsl)
    fsegs, tsegs = split_segments(M.body_segments(cfg), cfg.num_layers - k)
    return SplitPlan(cfg, mpsl, k, tuple(fsegs), tuple(tsegs))


def _slice_stacked(seg_params_list, segs: List[M.Segment], boundary: int):
    """Slice stacked segment params at the layer boundary."""
    frozen, train, seen = [], [], 0
    for sp, seg in zip(seg_params_list, segs):
        if seen + seg.count <= boundary:
            frozen.append(sp)
        elif seen >= boundary:
            train.append(sp)
        else:
            cut = M.Segment(seg.kind, boundary - seen).steps
            frozen.append(jax.tree_util.tree_map(lambda a: a[:cut], sp))
            train.append(jax.tree_util.tree_map(lambda a: a[cut:], sp))
        seen += seg.count
    return frozen, train


# ---------------------------------------------------------------------------
# Client heads


def init_client_adapters(key, cfg, mpsl):
    """Low-rank per-client tokenizer adapter: h + (h @ a_n) @ b_n.

    a ~ N(0, 1/D), b = 0 (LoRA-style: identity at init). Stacked [N, ...]."""
    n, r, d = mpsl.n_clients, mpsl.head_adapter_rank, cfg.d_model
    ka, _ = jax.random.split(key)
    return {
        "a": layers.dense_init(ka, (n, d, r), in_axis_size=d),
        "b": jnp.zeros((n, r, d), jnp.float32),
    }


def apply_client_adapter(adapter, h):
    """h [N, ..., D] with per-client low-rank delta (vmapped over N)."""
    a = adapter["a"].astype(h.dtype)
    b = adapter["b"].astype(h.dtype)
    delta = jnp.einsum("n...d,ndr->n...r", h, a)
    return h + jnp.einsum("n...r,nrd->n...d", delta, b)


def init_client_tokenizers(key, cfg, mpsl, modalities):
    """Paper-mode client heads: per-client Meta-Transformer tokenizers."""
    n = mpsl.n_clients
    keys = jax.random.split(key, n)
    out = {}
    for m in modalities:
        spec = tok.MODALITIES[m]
        out[m] = jax.vmap(
            lambda k: tok.init_tokenizer(k, spec, cfg.d_model))(keys)
    return out


# ---------------------------------------------------------------------------
# MPSL parameter trees


def init_mpsl_lm(key, cfg, run):
    """MPSL split parameters for an LM-family arch."""
    mpsl = run.mpsl
    plan = make_split_plan(cfg, mpsl)
    k0, k1, k2 = jax.random.split(key, 3)
    base = M.init_lm(k0, cfg)

    fseg_p, tseg_p = _slice_stacked(
        base["segments"], M.body_segments(cfg), plan.boundary)

    frozen: Dict[str, Any] = {"embed": base["embed"], "segments": fseg_p}
    if "meta_tokens" in base:          # the server's, frozen with the trunk
        frozen["meta_tokens"] = base["meta_tokens"]
    if "encoder" in base:
        frozen["encoder"] = base["encoder"]
    frozen = layers.cast_tree(frozen, jnp.dtype(run.frozen_dtype))

    server: Dict[str, Any] = {
        "segments": tseg_p,
        "final_norm": base["final_norm"],
    }
    if not cfg.tie_embeddings:
        server["lm_head"] = base["lm_head"]
    else:
        # tail must stay trainable+shared even with tied embeddings; keep a
        # trainable copy (the frozen table is the client-side tokenizer).
        server["lm_head"] = base["embed"]["table"].T.copy()

    client = {"adapter": init_client_adapters(k1, cfg, mpsl)}
    # one-time link: each client ships its head for the post-training
    # FedAvg (paper Sec. 3.3) — accounted per client from the real tree
    obs_comm.record_param_link("aggregation.client_head", client,
                               direction="uplink", per_step=False)
    params = {"client": client, "server": server}
    return params, frozen, plan


def init_mpsl_vit(key, cfg, run, modalities=("vision", "text"),
                  n_classes: int = 10, retrieval: bool = False):
    """MPSL split parameters for the paper's Meta-Transformer setup."""
    mpsl = run.mpsl
    plan = make_split_plan(cfg, mpsl)
    ks = jax.random.split(key, 6)

    segs = M.body_segments(cfg)
    seg_keys = jax.random.split(ks[0], len(segs))
    seg_p = [M.init_segment(k, cfg, s) for k, s in zip(seg_keys, segs)]
    fseg_p, tseg_p = _slice_stacked(seg_p, segs, plan.boundary)

    frozen = layers.cast_tree({"segments": fseg_p},
                              jnp.dtype(run.frozen_dtype))
    server: Dict[str, Any] = {
        "segments": tseg_p,
        "final_norm": layers.init_norm(cfg.norm, cfg.d_model),
    }
    if retrieval:
        server["proj_a"] = layers.dense_init(ks[1], (cfg.d_model, 512))
        server["proj_b"] = layers.dense_init(ks[2], (cfg.d_model, 512))
        server["logit_scale"] = jnp.asarray(2.659, jnp.float32)  # ln(1/0.07)
    else:
        server["task_head"] = {
            "w": layers.dense_init(ks[3], (cfg.d_model, n_classes)),
            "b": jnp.zeros((n_classes,), jnp.float32),
        }
    client = {"tokenizers": init_client_tokenizers(ks[4], cfg, mpsl,
                                                   modalities)}
    obs_comm.record_param_link("aggregation.client_head", client,
                               direction="uplink", per_step=False)
    params = {"client": client, "server": server}
    return params, frozen, plan


# ---------------------------------------------------------------------------
# Post-training model construction (paper Sec. 3.3)


def assemble_full_params(params, frozen, plan, client_head=None):
    """[F_C ; F_S] — rebuild an init_lm-style tree from the split trees.

    client_head: per-client index (personalization) or None for the FedAvg
    aggregate of client heads (used for FL-comparable evaluation)."""
    cfg = plan.cfg
    segs = M.body_segments(cfg)
    fseg_p = [layers.cast_tree(p, jnp.float32) for p in frozen["segments"]]
    tseg_p = params["server"]["segments"]

    merged, fi, ti, seen = [], 0, 0, 0
    for seg in segs:
        take = []
        remaining = seg.count
        while remaining:
            if seen < plan.boundary:
                src = fseg_p[fi]
                n = M.stacked_layers(src)
                take.append(src)
                fi += 1
                seen += n
                remaining -= n
            else:
                src = tseg_p[ti]
                n = M.stacked_layers(src)
                take.append(src)
                ti += 1
                seen += n
                remaining -= n
        merged.append(take[0] if len(take) == 1 else jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *take))

    out = {"segments": merged,
           "final_norm": params["server"]["final_norm"]}
    if "embed" in frozen:
        out["embed"] = layers.cast_tree(frozen["embed"], jnp.float32)
    if "meta_tokens" in frozen:
        out["meta_tokens"] = frozen["meta_tokens"].astype(jnp.float32)
    if "encoder" in frozen:
        out["encoder"] = layers.cast_tree(frozen["encoder"], jnp.float32)
    if "lm_head" in params["server"]:
        out["lm_head"] = params["server"]["lm_head"]
    return out
