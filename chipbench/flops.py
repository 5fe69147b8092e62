"""Model FLOPs of one MPSL training step, from a configuration and a mix.

Counted: the matmuls the forward pass requires, at 2 FLOPs per
multiply-add, and attention's scores and weighted values at
4 * keys * heads * head_dim per query. Factors by part:

  trainable layers, head, client tokenizer or adapter   3x forward
      (the forward, and the backward to activations and to weights);
  frozen layers                                          2x forward
      (the forward, and the backward to activations, which the client
      heads below them need; no weight gradients).

Left out: recomputation under remat, embedding gathers, norms, element-
wise work, and the selective scan's recurrence (elementwise, about 1% of
a hybrid layer's matmul FLOPs at hymba-1.5b's widths).
"""
from __future__ import annotations


def mean_keys(seq: int, causal: bool, window: int = 0) -> float:
    """Mean number of keys a query attends: all of them, the earlier ones
    and itself, or (window) the last `window` of those."""
    if not causal:
        return float(seq)
    if not window:
        return (seq + 1) / 2.0
    full = min(window, seq)
    # positions p < window see p + 1 keys, later ones see `window`
    return (full * (full + 1) / 2.0 + (seq - full) * window) / seq


def vit_step(cfg, mix) -> float:
    d, h, hd, f = cfg["d_model"], cfg["num_heads"], cfg["head_dim"], \
        cfg["d_ff"]
    tk = cfg["tokenizers"]
    samples = mix["n_clients"] * mix["batch_per_client"]
    seq = sum(tk[m]["tokens"] for m in mix["modalities"])
    layer = 2 * (4 * d * h * hd + 2 * d * f) + 4 * mean_keys(seq, False) * h * hd
    k = cfg["mpsl"]["trainable_blocks"]
    body = (3 * k + 2 * (cfg["num_layers"] - k)) * layer * seq
    client = 0.0
    for m in mix["modalities"]:
        if m != "text":                    # text is a table lookup
            p = tk[m]["patch"]
            chans = tk[m]["image"][2] if m == "vision" else 1
            client += 3 * 2 * p * p * chans * d * (tk[m]["tokens"] - 1)
    head = 3 * 2 * d * mix["n_classes"]
    return samples * (body + client + head)


def lm_step(cfg, mix) -> float:
    d, h, kv, hd, f, v = (cfg["d_model"], cfg["num_heads"],
                          cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                          cfg["vocab_size"])
    seqs = mix["n_clients"] * mix["batch_per_client"]
    s = mix["seq_len"]
    proj = 2 * (2 * d * h * hd + 2 * d * kv * hd)
    ssm = cfg.get("ssm")
    if ssm:
        di, ds, dtr = ssm["expand"] * d, ssm["d_state"], ssm["dt_rank"]
        proj += 2 * (d * 2 * di + di * (dtr + 2 * ds) + dtr * di + di * d)
    mlp = 2 * 3 * d * f
    glob = set(cfg.get("global_layers", range(cfg["num_layers"])))
    k = cfg["mpsl"]["trainable_blocks"]
    body = 0.0
    for i in range(cfg["num_layers"]):
        window = 0 if i in glob else cfg.get("sliding_window", 0)
        layer = proj + mlp + 4 * mean_keys(s, True, window) * h * hd
        body += (3 if i >= cfg["num_layers"] - k else 2) * layer
    adapter = 3 * 2 * 2 * d * cfg["mpsl"]["head_adapter_rank"]
    head = 3 * 2 * d * v * (s - 1) / s    # the last position predicts nothing
    return seqs * s * (body + adapter + head)


STEP_FLOPS = {"vit": vit_step, "lm": lm_step}


def step_flops(cfg, mix) -> float:
    return STEP_FLOPS[cfg["family"]](cfg, mix)
