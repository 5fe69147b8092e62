"""Plain float32 reference of the MPSL language-model step on a Hymba-style
hybrid trunk (arXiv:2411.13676, nvidia/Hymba-1.5B-Base).

Per client n: token ids -> the frozen vocabulary table, then the client's
low-rank adapter h + (h a_n) b_n. The server runs every client's
sequences as one batch through L hybrid blocks (the first L - k frozen,
the last k trained), a final RMSNorm and the LM head, and each client's
loss is its mean next-token cross-entropy; the step's loss weights
clients by their share of the participating samples.

A hybrid block, on x [B, S, D]:
  h = RMSNorm(x)
  attention: q, k, v projections (GQA), RoPE on q and k, causal softmax
      attention over all earlier positions on the global layers and over
      the last `sliding_window` positions elsewhere, output projection;
  Mamba: in-projection to (x_in, z), causal depthwise conv + SiLU, x_proj
      to (dt, B, C), dt = softplus(dt W + b), the selective scan
      h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,  y_t = C_t h_t,
      one position after another, then y + D x, gated by SiLU(z), and
      the out-projection;
  x += (beta_a RMSNorm(attn) + beta_s RMSNorm(mamba)) / 2
  x += SwiGLU MLP of RMSNorm(x).

Departures of the program from the published model, followed here: no
meta tokens, no cross-layer KV sharing, and the branch combination above.
Attention is computed in blocks of queries and the scan in chunks of
positions, each recomputed in the backward pass, so that the reference
fits one chip at the published widths; neither changes the arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C

Q_BLOCK = 256        # attention queries per block (divides S)
SCAN_CHUNK = 256     # scan positions per recomputed chunk (divides S)
CE_CHUNK = 1024      # LM-head tokens per block


def layer_kinds(cfg):
    g = set(cfg["global_layers"])
    return ["global" if i in g else "local" for i in range(cfg["num_layers"])]


def segment_runs(cfg):
    k = cfg["mpsl"]["trainable_blocks"]
    return C.layer_runs(layer_kinds(cfg), cfg["num_layers"] - k)


def weight_specs(cfg, mix):
    n, d, h, kv, hd, f, v = (mix["n_clients"], cfg["d_model"],
                             cfg["num_heads"], cfg["num_kv_heads"],
                             cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"])
    ssm = cfg["ssm"]
    di, ds, dc, dtr = ssm["expand"] * d, ssm["d_state"], ssm["d_conv"], \
        ssm["dt_rank"]
    r = cfg["mpsl"]["head_adapter_rank"]
    train = {"client/adapter/a": ((n, d, r), "w", d),
             "client/adapter/b": ((n, r, d), "w", r)}
    frozen = {"embed/table": ((v, d), "w", d)}
    frozen_runs, train_runs = segment_runs(cfg)
    for tree, prefix, runs in ((frozen, "segments", frozen_runs),
                               (train, "server/segments", train_runs)):
        for i, (_, c) in enumerate(runs):
            s = f"{prefix}/{i}/"
            tree[s + "norm1/scale"] = ((c, d), "rms_scale", 1)
            tree[s + "norm2/scale"] = ((c, d), "rms_scale", 1)
            m = s + "mix/"
            tree[m + "attn/wq"] = ((c, d, h, hd), "w", d)
            tree[m + "attn/wk"] = ((c, d, kv, hd), "w", d)
            tree[m + "attn/wv"] = ((c, d, kv, hd), "w", d)
            tree[m + "attn/wo"] = ((c, h, hd, d), "w", h * hd)
            tree[m + "ssm/in_proj"] = ((c, d, 2 * di), "w", d)
            tree[m + "ssm/conv_w"] = ((c, dc, di), "w", dc)
            tree[m + "ssm/conv_b"] = ((c, di), "b", 1)
            tree[m + "ssm/x_proj"] = ((c, di, dtr + 2 * ds), "w", di)
            tree[m + "ssm/dt_proj"] = ((c, dtr, di), "uniform", dtr)
            tree[m + "ssm/dt_bias"] = ((c, di), "dt_bias", 1)
            tree[m + "ssm/A_log"] = ((c, di, ds), "a_log", 1)
            tree[m + "ssm/D"] = ((c, di), "one", 1)
            tree[m + "ssm/out_proj"] = ((c, di, d), "w", di)
            tree[m + "attn_norm/scale"] = ((c, d), "rms_scale", 1)
            tree[m + "ssm_norm/scale"] = ((c, d), "rms_scale", 1)
            tree[m + "beta_attn"] = ((c,), "beta", 1)
            tree[m + "beta_ssm"] = ((c,), "beta", 1)
            tree[s + "mlp/wi"] = ((c, d, f), "w", d)
            tree[s + "mlp/wg"] = ((c, d, f), "w", d)
            tree[s + "mlp/wo"] = ((c, f, d), "w", f)
    train["server/final_norm/scale"] = ((d,), "rms_scale", 1)
    train["server/lm_head"] = ((d, v), "w", d)
    tdt, fdt = jnp.dtype(cfg["param_dtype"]), jnp.dtype(cfg["frozen_dtype"])
    return ({p: s + (tdt,) for p, s in train.items()},
            {p: s + (fdt,) for p, s in frozen.items()})


def init_weights(cfg, mix, key):
    t_specs, f_specs = weight_specs(cfg, mix)
    return (C.init_tree(t_specs, jax.random.fold_in(key, 1)),
            C.init_tree(f_specs, jax.random.fold_in(key, 2)))


# ---------------------------------------------------------------------------
# forward


def _rope(x, theta):
    """x [B, S, H, hd], positions 0..S-1, halves rotated."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs          # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, h, cfg, window, mm):
    b, s, _ = h.shape
    nh, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _rope(mm("bsd,dhk->bshk", h, a["wq"]), cfg["rope_theta"])
    k = _rope(mm("bsd,dhk->bshk", h, a["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", h, a["wv"])
    rep = nh // kv                       # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(s)
    qblk = min(Q_BLOCK, s)
    nq = s // qblk
    qb = q.reshape(b, nq, qblk, nh, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        qi, i = args
        qpos = i * qblk + jnp.arange(qblk)
        sc = mm("bqhk,bshk->bhqs", qi, k) / np.sqrt(hd)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= (qpos[:, None] - kpos[None, :]) < window
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        return mm("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(one, (qb, jnp.arange(nq)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, hd)
    return mm("bshk,hkd->bsd", o, a["wo"])


def _selective_scan(x, dt, bm, cm, a_log):
    """x, dt [B, S, di]; bm, cm [B, S, ds]; one position at a time."""
    b, s, di = x.shape
    ds = bm.shape[-1]
    a = -jnp.exp(a_log)                                        # [di, ds]

    def pos(hs, t):
        xt, dtt, bt, ct = t
        hs = jnp.exp(dtt[..., None] * a) * hs \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return hs, jnp.sum(hs * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def chunk(hs, t):
        return jax.lax.scan(pos, hs, t)

    c = min(SCAN_CHUNK, s)

    def chunks(t):               # [B, S, k] -> [S/c, c, B, k]
        return t.transpose(1, 0, 2).reshape(s // c, c, b, t.shape[-1])

    h0 = jnp.zeros((b, di, ds), jnp.float32)
    _, y = jax.lax.scan(chunk, h0, tuple(map(chunks, (x, dt, bm, cm))))
    return y.reshape(s, b, di).transpose(1, 0, 2)


def _mamba(p, h, cfg, mm):
    ssm = cfg["ssm"]
    di = ssm["expand"] * cfg["d_model"]
    ds, dtr, dc = ssm["d_state"], ssm["dt_rank"], ssm["d_conv"]
    xz = mm("bsd,de->bse", h, p["in_proj"])
    xin, z = xz[..., :di], xz[..., di:]
    s = h.shape[1]
    xp = jnp.pad(xin, ((0, 0), (dc - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(dc)) + p["conv_b"]
    xc = C.silu(xc)
    proj = mm("bse,ef->bsf", xc, p["x_proj"])
    dt_in, bm, cm = (proj[..., :dtr], proj[..., dtr:dtr + ds],
                     proj[..., dtr + ds:])
    dt = C.softplus(mm("bsr,re->bse", dt_in, p["dt_proj"]) + p["dt_bias"])
    y = _selective_scan(xc, dt, bm, cm, p["A_log"])
    y = (y + xc * p["D"]) * C.silu(z)
    return mm("bse,ed->bsd", y, p["out_proj"])


def _block(lp, x, cfg, kind, mm):
    eps = cfg["norm_eps"]
    lp = C.f32(lp)
    mx = lp["mix"]
    h = C.rms_norm(x, lp["norm1"]["scale"], eps)
    window = 0 if kind == "global" else cfg["sliding_window"]
    att = _attention(mx["attn"], h, cfg, window, mm)
    mam = _mamba(mx["ssm"], h, cfg, mm)
    x = x + 0.5 * (C.rms_norm(att, mx["attn_norm"]["scale"], eps)
                   * mx["beta_attn"]
                   + C.rms_norm(mam, mx["ssm_norm"]["scale"], eps)
                   * mx["beta_ssm"])
    h = C.rms_norm(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    g = C.silu(mm("bsd,df->bsf", h, m["wg"])) * mm("bsd,df->bsf", h, m["wi"])
    return x + mm("bsf,fd->bsd", g, m["wo"])


def _head_loss(h, w, labels, mm):
    """Per-token CE of h [T, D] against labels [T], in blocks of tokens."""
    t = h.shape[0]
    nb = -(-t // CE_CHUNK)
    pad = nb * CE_CHUNK - t
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, CE_CHUNK, -1)
    lb = jnp.pad(labels, (0, pad)).reshape(nb, CE_CHUNK)

    @jax.checkpoint
    def one(args):
        hx, lx = args
        return C.cross_entropy(mm("cd,dv->cv", hx, w), lx)

    return jax.lax.map(one, (hb, lb)).reshape(-1)[:t]


def client_loss(params, frozen, batch, n, cfg, mix, precision="float32"):
    """Client n's share of the step's loss L_S = sum_n w_n L_n, for float32
    `params`; w_n is n's share of the participating clients."""
    mm = C.make_mm(precision)
    tokens = batch["tokens"][n]                                 # [Bn, S]
    ad = jax.tree_util.tree_map(lambda a: a[n], params["client"]["adapter"])
    x = frozen["embed"]["table"].astype(jnp.float32)[tokens]    # [Bn, S, D]
    x = x + mm("bsr,rd->bsd", mm("bsd,dr->bsr", x, ad["a"]), ad["b"])
    frozen_runs, train_runs = segment_runs(cfg)
    srv = params["server"]
    for seg, (kind, _) in zip(frozen["segments"] + srv["segments"],
                              frozen_runs + train_runs):
        body = jax.checkpoint(
            lambda x, lp, kind=kind: (_block(lp, x, cfg, kind, mm), None))
        x, _ = jax.lax.scan(body, x, seg)
    x = C.rms_norm(x, srv["final_norm"]["scale"], cfg["norm_eps"])
    h = x[:, :-1].reshape(-1, x.shape[-1])
    labels = batch["labels"][n][:, 1:].reshape(-1)
    ce = jnp.mean(_head_loss(h, srv["lm_head"], labels, mm))
    mask = batch["mask"].astype(jnp.float32)
    return mask[n] / jnp.maximum(jnp.sum(mask), 1.0) * ce


def readings(cfg, mix, key, batches, precision="float32"):
    params, frozen = init_weights(cfg, mix, key)
    params = C.f32(params)
    fn = functools.partial(client_loss, cfg=cfg, mix=mix, precision=precision)
    with jax.default_matmul_precision("highest"):
        return C.readings(fn, params, frozen, batches, cfg["optimizer"],
                          mix["n_clients"])
