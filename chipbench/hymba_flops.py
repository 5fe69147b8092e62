"""Model FLOPs of one MPSL training step on Hymba-1.5B as published, counted
as `flops.py` counts them (2 FLOPs per multiply-add; trainable layers,
head and adapter 3x forward, frozen layers 2x), with what the published
model changes:

  * the trunk runs S' = meta_tokens + seq_len positions; the adapter runs
    over the S text positions and the head over the S - 1 that predict;
  * the second layer of a K/V-sharing pair has no K or V projection;
  * a local layer's query attends to the last `sliding_window` positions
    and, beside them, the meta keys (positions below meta_tokens) that
    have slid out of the window; a global layer's to every earlier one.
"""
from __future__ import annotations

import numpy as np


def mean_keys(seq: int, window: int = 0, prefix: int = 0) -> float:
    """Mean number of keys a causal query attends over `seq` positions:
    all earlier ones and itself, or (window) the last `window` of those
    plus the keys below `prefix` that lie before the window."""
    p = np.arange(seq)
    if not window:
        return float(np.mean(p + 1))
    near = np.minimum(p + 1, window)
    meta = np.clip(p - window + 1, 0, prefix)
    return float(np.mean(near + meta))


def step_flops(cfg, mix) -> float:
    d, h, kv, hd, f, v = (cfg["d_model"], cfg["num_heads"],
                          cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                          cfg["vocab_size"])
    m, s = cfg["meta_tokens"], mix["seq_len"]
    sp = m + s
    seqs = mix["n_clients"] * mix["batch_per_client"]
    ssm = cfg["ssm"]
    di, ds, dtr = ssm["expand"] * d, ssm["d_state"], ssm["dt_rank"]
    q_o = 2 * 2 * d * h * hd
    k_v = 2 * 2 * d * kv * hd
    mamba = 2 * (d * 2 * di + di * (dtr + 2 * ds) + dtr * di + di * d)
    mlp = 2 * 3 * d * f
    glob = set(cfg["global_layers"])
    shared = {g[1] for g in cfg["kv_share_groups"] if len(g) == 2}
    k = cfg["mpsl"]["trainable_blocks"]
    body = 0.0
    for i in range(cfg["num_layers"]):
        keys = mean_keys(sp) if i in glob else \
            mean_keys(sp, cfg["sliding_window"], m)
        layer = q_o + (0 if i in shared else k_v) + mamba + mlp \
            + 4 * keys * h * hd
        body += (3 if i >= cfg["num_layers"] - k else 2) * layer
    adapter = 3 * 2 * 2 * d * cfg["mpsl"]["head_adapter_rank"]
    head = 3 * 2 * d * v
    return seqs * (sp * body + s * adapter + (s - 1) * head)
