"""Plain float32 reference of the MPSL image-text classification step on a
Meta-Transformer ViT trunk (arXiv:2307.10802 on ViT-B/16, arXiv:2010.11929).

Per client n (its own tokenizers, never shared):
  vision  224x224x3 image -> 196 patches of 16x16x3, each flattened
          (row, column, channel) and projected by the client's [768, D]
          patch weight plus bias; a learned cls token is prepended and
          learned positions added: 197 tokens.
  text    77 CLIP ids -> the client's frozen [49408, D] table (no
          gradient) plus learned positions: 77 tokens.
Early fusion concatenates text then vision (274 tokens) and the server
encodes all clients' samples as one batch: L pre-LayerNorm blocks (the
first L - k frozen, the last k trained) of full bidirectional multi-head
attention with q/k/v biases and no output bias, and a GELU MLP without
biases; a final LayerNorm, the mean over tokens, and a linear head. Each
client's loss is its samples' mean cross-entropy; the step's loss weights
clients by their share of the participating samples.

Departures of the program from ViT-B/16, followed here so that the
comparison is of arithmetic, not of architecture: the tanh GELU, and no
MLP or attention-output biases.

The weights are laid out as the program stores them: per-client leaves
stacked on a leading client axis, each run of layers stacked on a leading
layer axis, the frozen layers in their storage dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C


def weight_specs(cfg, mix):
    """{path: (shape, kind, fan_in, dtype)} for the trainable tree and the
    frozen tree, in the program's layout."""
    n, d, h, hd, f = (mix["n_clients"], cfg["d_model"], cfg["num_heads"],
                      cfg["head_dim"], cfg["d_ff"])
    tk = cfg["tokenizers"]
    train, frozen = {}, {}
    for m in mix["modalities"]:
        base = f"client/tokenizers/{m}/"
        if m == "text":
            train[base + "embed"] = ((n, tk["text"]["vocab_size"], d), "w", d)
            train[base + "pos"] = ((n, tk["text"]["tokens"], d), "w", d)
        else:
            p = tk[m]["patch"]
            chans = tk[m]["image"][2] if m == "vision" else 1
            train[base + "proj"] = ((n, p * p * chans, d), "w", p * p * chans)
            train[base + "proj_b"] = ((n, d), "b", 1)
            train[base + "cls"] = ((n, 1, d), "w", d)
            train[base + "pos"] = ((n, tk[m]["tokens"], d), "w", d)
    k = cfg["mpsl"]["trainable_blocks"]
    frozen_runs, train_runs = C.layer_runs(["vit"] * cfg["num_layers"],
                                           cfg["num_layers"] - k)
    for tree, prefix, runs in ((frozen, "segments", frozen_runs),
                               (train, "server/segments", train_runs)):
        for i, (_, count) in enumerate(runs):
            s = f"{prefix}/{i}/"
            for norm in ("norm1", "norm2"):
                tree[s + norm + "/scale"] = ((count, d), "ln_scale", 1)
                tree[s + norm + "/bias"] = ((count, d), "b", 1)
            for w in ("wq", "wk", "wv"):
                tree[s + "attn/" + w] = ((count, d, h, hd), "w", d)
                tree[s + "attn/b" + w[1]] = ((count, h, hd), "b", 1)
            tree[s + "attn/wo"] = ((count, h, hd, d), "w", h * hd)
            tree[s + "mlp/wi"] = ((count, d, f), "w", d)
            tree[s + "mlp/wo"] = ((count, f, d), "w", f)
    train["server/final_norm/scale"] = ((d,), "ln_scale", 1)
    train["server/final_norm/bias"] = ((d,), "b", 1)
    train["server/task_head/w"] = ((d, mix["n_classes"]), "w", d)
    train["server/task_head/b"] = ((mix["n_classes"],), "b", 1)
    tdt, fdt = jnp.dtype(cfg["param_dtype"]), jnp.dtype(cfg["frozen_dtype"])
    return ({p: s + (tdt,) for p, s in train.items()},
            {p: s + (fdt,) for p, s in frozen.items()})


def init_weights(cfg, mix, key):
    """(trainable, frozen) trees drawn from `key`, in their stored dtypes."""
    t_specs, f_specs = weight_specs(cfg, mix)
    return (C.init_tree(t_specs, jax.random.fold_in(key, 1)),
            C.init_tree(f_specs, jax.random.fold_in(key, 2)))


# ---------------------------------------------------------------------------
# forward


def _patchify(x, p):
    """[B, H, W, C] -> [B, (H/p)(W/p), p*p*C], patches in row-major order,
    each flattened (row, column, channel)."""
    if x.ndim == 3:
        x = x[..., None]
    b, hh, ww, c = x.shape
    x = x.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def _tokenize(tk, batch, n, cfg, mix, mm):
    """Client n's tokens, early-fused: [Bn, T, D]."""
    parts = {}
    for m in mix["modalities"]:
        p = jax.tree_util.tree_map(lambda a: a[n], tk[m])
        x = batch[m][n]
        if m == "text":
            emb = jax.lax.stop_gradient(p["embed"])             # [V, D]
            parts[m] = emb[x] + p["pos"]
        else:
            pt = _patchify(x.astype(jnp.float32),
                           cfg["tokenizers"][m]["patch"])
            tok = mm("bpk,kd->bpd", pt, p["proj"]) + p["proj_b"]
            cls = jnp.broadcast_to(p["cls"], (tok.shape[0], 1, tok.shape[-1]))
            parts[m] = jnp.concatenate([cls, tok], axis=1) + p["pos"]
    return jnp.concatenate([parts[m] for m in sorted(parts)], axis=1)


def _block(lp, x, cfg, mm):
    """One pre-LN ViT block on x [B, T, D]."""
    eps, hd = cfg["norm_eps"], cfg["head_dim"]
    lp = C.f32(lp)
    a = lp["attn"]
    h = C.layer_norm(x, lp["norm1"]["scale"], lp["norm1"]["bias"], eps)
    q = mm("btd,dhk->bthk", h, a["wq"]) + a["bq"]
    k = mm("btd,dhk->bthk", h, a["wk"]) + a["bk"]
    v = mm("btd,dhk->bthk", h, a["wv"]) + a["bv"]
    s = mm("bqhk,bshk->bhqs", q, k) / np.sqrt(hd)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bthk,hkd->btd", o, a["wo"])
    h = C.layer_norm(x, lp["norm2"]["scale"], lp["norm2"]["bias"], eps)
    h = C.gelu_tanh(mm("btd,df->btf", h, lp["mlp"]["wi"]))
    return x + mm("btf,fd->btd", h, lp["mlp"]["wo"])


def _run_segments(x, segments, cfg, mm):
    body = jax.checkpoint(lambda x, lp: (_block(lp, x, cfg, mm), None))
    for seg in segments:
        x, _ = jax.lax.scan(body, x, seg)
    return x


def client_loss(params, frozen, batch, n, cfg, mix, precision="float32"):
    """Client n's share of the step's loss L_S = sum_n w_n L_n, for float32
    `params`; w_n is n's share of the participating clients."""
    mm = C.make_mm(precision)
    x = _tokenize(params["client"]["tokenizers"], batch, n, cfg, mix, mm)
    srv = params["server"]
    x = _run_segments(x, frozen["segments"] + srv["segments"], cfg, mm)
    x = C.layer_norm(x, srv["final_norm"]["scale"], srv["final_norm"]["bias"],
                     cfg["norm_eps"])
    emb = jnp.mean(x, axis=1)
    logits = mm("bd,dc->bc", emb, srv["task_head"]["w"]) + srv["task_head"]["b"]
    ce = jnp.mean(C.cross_entropy(logits, batch["labels"][n]))
    mask = batch["mask"].astype(jnp.float32)
    return mask[n] / jnp.maximum(jnp.sum(mask), 1.0) * ce


def readings(cfg, mix, key, batches, precision="float32"):
    """The reference's losses, first-gradient norms and change norms over
    `batches`, from the benchmark's weights for `key`; and those weights."""
    params, frozen = init_weights(cfg, mix, key)
    params = C.f32(params)
    fn = functools.partial(client_loss, cfg=cfg, mix=mix, precision=precision)
    with jax.default_matmul_precision("highest"):
        return C.readings(fn, params, frozen, batches, cfg["optimizer"],
                          mix["n_clients"])
