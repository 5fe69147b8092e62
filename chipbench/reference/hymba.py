"""Plain float32 reference of the MPSL language-model step on Hymba-1.5B as
published (arXiv:2411.13676 Sec. 2, nvidia/Hymba-1.5B-Base): the hybrid
trunk with meta tokens and cross-layer KV sharing.

Per client n: token ids -> the frozen vocabulary table, then the client's
low-rank adapter h + (h a_n) b_n. The server prepends the M learned meta
tokens R [M, D] (frozen) to each sequence, X~ = [R; X], so the trunk runs
S' = M + S positions, 0..S'-1, through L hybrid blocks (the first L - k
frozen, the last k trained), a final RMSNorm and the LM head. The loss is
over the text only: trunk position M + t predicts token t + 1, each
client's loss is its mean next-token cross-entropy, and the step's loss
weights clients by their share of the participating samples.

A hybrid block, on x [B, S', D]:
  h = RMSNorm(x)
  attention: q, k, v projections (GQA), RoPE on q and k at positions
      0..S'-1, softmax attention over the keys the plain mask keeps:
      every earlier position on the global layers; on the others the last
      `sliding_window` positions and, beside them, the M meta keys
      (positions below M), which every query sees;
  Mamba: in-projection to (x_in, z), causal depthwise conv + SiLU, x_proj
      to (dt, B, C), dt = softplus(dt W + b), the selective scan
      h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,  y_t = C_t h_t,
      one position after another from a zero state (the meta tokens are
      the first positions, so they seed the state of the text), then
      y + D x, gated by SiLU(z), and the out-projection;
  x += (beta_a RMSNorm(attn) + beta_s RMSNorm(mamba)) / 2
  x += SwiGLU MLP of RMSNorm(x).

Cross-layer KV sharing: in each pair (i, i + 1) of `kv_share_groups`,
layer i + 1 has no K/V projections and attends with layer i's K and V
(RoPE applied, at the same positions); every other layer has its own.

Departure of the program from the published model, followed here: the
branch combination above. The published `config.json` is not in the
repository, so the pairs are the configuration file's `assumed` ones.
Attention is computed in blocks of queries and the scan in chunks of
positions, each recomputed in the backward pass, so that the reference
fits one chip at the published widths; neither changes the arithmetic.

Two controls leave out one mechanism each (`readings(meta=False)`: no
meta tokens; `readings(share_kv=False)`: the second layer of a pair
computes its own K and V from its input, with the first layer's
projections); at the cell's limits both read not correct.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C
from chipbench.reference.lm import _head_loss, _rope

Q_BLOCK = 128        # attention queries per block (the largest that divides S')
SCAN_CHUNK = 128     # scan positions per recomputed chunk (likewise)


def units(cfg):
    """[(kind, layers)] in layer order: a K/V-sharing pair is one unit of
    kind "pair" (2 layers); other layers are "global" or "local"."""
    glb = set(cfg["global_layers"])
    firsts = {g[0] for g in cfg.get("kv_share_groups", ()) if len(g) == 2}
    out, i = [], 0
    while i < cfg["num_layers"]:
        if i in firsts:
            out.append(("pair", 2))
            i += 2
        else:
            out.append(("global" if i in glb else "local", 1))
            i += 1
    return out


def segment_runs(cfg):
    """Runs of one unit kind, split at the frozen/trainable boundary:
    ([(kind, scan steps)] frozen, [(kind, scan steps)] trainable)."""
    boundary = cfg["num_layers"] - cfg["mpsl"]["trainable_blocks"]
    frozen, train, seen = [], [], 0
    for kind, n in units(cfg):
        side = frozen if seen < boundary else train
        if seen < boundary < seen + n:
            raise ValueError("the trainable boundary splits a K/V pair")
        if side and side[-1][0] == kind:
            side[-1] = (kind, side[-1][1] + 1)
        else:
            side.append((kind, 1))
        seen += n
    return frozen, train


def _block_specs(tree, s, c, cfg, own_kv):
    d, h, kv, hd, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    ssm = cfg["ssm"]
    di, ds, dc, dtr = ssm["expand"] * d, ssm["d_state"], ssm["d_conv"], \
        ssm["dt_rank"]
    tree[s + "norm1/scale"] = ((c, d), "rms_scale", 1)
    tree[s + "norm2/scale"] = ((c, d), "rms_scale", 1)
    m = s + "mix/"
    tree[m + "attn/wq"] = ((c, d, h, hd), "w", d)
    if own_kv:
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


def weight_specs(cfg, mix):
    n, d, v = mix["n_clients"], cfg["d_model"], cfg["vocab_size"]
    r = cfg["mpsl"]["head_adapter_rank"]
    train = {"client/adapter/a": ((n, d, r), "w", d),
             "client/adapter/b": ((n, r, d), "w", r)}
    frozen = {"embed/table": ((v, d), "w", d)}
    if cfg["meta_tokens"]:
        frozen["meta_tokens"] = ((cfg["meta_tokens"], d), "w", d)
    frozen_runs, train_runs = segment_runs(cfg)
    for tree, prefix, runs in ((frozen, "segments", frozen_runs),
                               (train, "server/segments", train_runs)):
        for i, (kind, c) in enumerate(runs):
            s = f"{prefix}/{i}/"
            if kind == "pair":
                _block_specs(tree, s + "first/", c, cfg, True)
                _block_specs(tree, s + "second/", c, cfg, False)
            else:
                _block_specs(tree, s, c, cfg, True)
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


def visible(qpos, kpos, window, prefix):
    """The plain mask [Sq, Sk]: causal; with a window, the last `window`
    positions and every key below `prefix` (the meta tokens)."""
    ok = kpos[None, :] <= qpos[:, None]
    if window:
        ok &= ((qpos[:, None] - kpos[None, :]) < window) | \
            (kpos[None, :] < prefix)
    return ok


def _attention(a, h, cfg, window, prefix, mm, kv=None):
    """(output, (k, v)): k and v are `kv` where given (the first layer of
    a pair's, for the second), else this layer's own, with RoPE on k."""
    b, s, _ = h.shape
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _rope(mm("bsd,dhk->bshk", h, a["wq"]), cfg["rope_theta"])
    if kv is None:
        kv = (_rope(mm("bsd,dhk->bshk", h, a["wk"]), cfg["rope_theta"]),
              mm("bsd,dhk->bshk", h, a["wv"]))
    rep = nh // nkv                      # query head i reads kv head i // rep
    k = jnp.repeat(kv[0], rep, axis=2)
    v = jnp.repeat(kv[1], rep, axis=2)
    kpos = jnp.arange(s)
    qblk = math.gcd(s, Q_BLOCK)
    nq = s // qblk
    qb = q.reshape(b, nq, qblk, nh, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        qi, i = args
        qpos = i * qblk + jnp.arange(qblk)
        sc = mm("bqhk,bshk->bhqs", qi, k) / np.sqrt(hd)
        ok = visible(qpos, kpos, window, prefix)
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        return mm("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(one, (qb, jnp.arange(nq)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, hd)
    return mm("bshk,hkd->bsd", o, a["wo"]), kv


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

    c = math.gcd(s, SCAN_CHUNK)

    @jax.checkpoint
    def chunk(hs, t):
        return jax.lax.scan(pos, hs, t, unroll=min(8, c))

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


def _block(lp, x, cfg, window, prefix, mm, kv=None):
    """(x after the block, the K/V its attention used)."""
    eps = cfg["norm_eps"]
    mx = lp["mix"]
    h = C.rms_norm(x, lp["norm1"]["scale"], eps)
    att, kv = _attention(mx["attn"], h, cfg, window, prefix, mm, kv)
    mam = _mamba(mx["ssm"], h, cfg, mm)
    x = x + 0.5 * (C.rms_norm(att, mx["attn_norm"]["scale"], eps)
                   * mx["beta_attn"]
                   + C.rms_norm(mam, mx["ssm_norm"]["scale"], eps)
                   * mx["beta_ssm"])
    h = C.rms_norm(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    g = C.silu(mm("bsd,df->bsf", h, m["wg"])) * mm("bsd,df->bsf", h, m["wi"])
    return x + mm("bsf,fd->bsd", g, m["wo"]), kv


def _unit(lp, x, cfg, kind, prefix, mm, share_kv):
    lp = C.f32(lp)
    if kind != "pair":
        window = 0 if kind == "global" else cfg["sliding_window"]
        return _block(lp, x, cfg, window, prefix, mm)[0]
    window = cfg["sliding_window"]
    x, kv = _block(lp["first"], x, cfg, window, prefix, mm)
    second = lp["second"]
    if not share_kv:                 # the control: K/V of its own input
        kv = None
        second = dict(second, mix=dict(second["mix"], attn=dict(
            second["mix"]["attn"], wk=lp["first"]["mix"]["attn"]["wk"],
            wv=lp["first"]["mix"]["attn"]["wv"])))
    return _block(second, x, cfg, window, prefix, mm, kv)[0]


def client_loss(params, frozen, batch, n, cfg, mix, precision="float32",
                meta=True, share_kv=True):
    """Client n's share of the step's loss L_S = sum_n w_n L_n, for float32
    `params`; w_n is n's share of the participating clients."""
    mm = C.make_mm(precision)
    tokens = batch["tokens"][n]                                 # [Bn, S]
    ad = jax.tree_util.tree_map(lambda a: a[n], params["client"]["adapter"])
    x = frozen["embed"]["table"].astype(jnp.float32)[tokens]    # [Bn, S, D]
    x = x + mm("bsr,rd->bsd", mm("bsd,dr->bsr", x, ad["a"]), ad["b"])
    m = cfg["meta_tokens"] if meta else 0
    if m:
        r = frozen["meta_tokens"].astype(jnp.float32)
        x = jnp.concatenate([jnp.broadcast_to(r, (x.shape[0],) + r.shape), x],
                            axis=1)
    frozen_runs, train_runs = segment_runs(cfg)
    srv = params["server"]
    for seg, (kind, _) in zip(frozen["segments"] + srv["segments"],
                              frozen_runs + train_runs):
        body = jax.checkpoint(lambda x, lp, kind=kind: (
            _unit(lp, x, cfg, kind, m, mm, share_kv), None))
        x, _ = jax.lax.scan(body, x, seg)
    x = C.rms_norm(x, srv["final_norm"]["scale"], cfg["norm_eps"])
    h = x[:, m:-1].reshape(-1, x.shape[-1])
    labels = batch["labels"][n][:, 1:].reshape(-1)
    ce = jnp.mean(_head_loss(h, srv["lm_head"], labels, mm))
    mask = batch["mask"].astype(jnp.float32)
    return mask[n] / jnp.maximum(jnp.sum(mask), 1.0) * ce


def readings(cfg, mix, key, batches, precision="float32", *, meta=True,
             share_kv=True):
    params, frozen = init_weights(cfg, mix, key)
    params = C.f32(params)
    fn = functools.partial(client_loss, cfg=cfg, mix=mix, precision=precision,
                           meta=meta, share_kv=share_kv)
    with jax.default_matmul_precision("highest"):
        return C.readings(fn, params, frozen, batches, cfg["optimizer"],
                          mix["n_clients"])
