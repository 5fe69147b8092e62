"""HBM bytes that the selective scans of one MPSL step on a hybrid trunk
must move, counted from shapes: the same work whichever core runs.

Per layer, over B sequences of S' = meta_tokens + seq_len positions, with
d_inner channels and d_state states, each operand read or written once:

  forward   x and dt [B, S', di] and B and C [B, S', ds] in the compute
            dtype, A [di, ds] f32 in; y [B, S', di] and the chunk
            checkpoints [B, ceil(S' / 256), ds, di] f32 out;
  backward  x, dt, gy [B, S', di], B, C, A and the checkpoints in; dx and
            ddt [B, S', di], dB and dC [B, S', ds] f32 and dA f32 out.

Every layer runs the forward twice (the step's forward, and its
recomputation under remat) and the backward once: the frozen layers too,
since the clients' adapters below them take gradients. A lower bound on
the traffic, so the share of the roofline it gives cannot pass 100%.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

CHUNK = 256          # positions per checkpoint, the scan's largest chunk


def layer_bytes(b, s, di, ds, dtype="bfloat16"):
    """(forward, backward) bytes of one layer's scan."""
    e = jnp.dtype(dtype).itemsize
    seq, state = b * s * di, b * s * ds
    ckpt = b * math.ceil(s / CHUNK) * ds * di * 4
    a = di * ds * 4
    fwd = (2 * seq + 2 * state) * e + a + seq * e + ckpt
    bwd = (3 * seq + 2 * state) * e + a + ckpt \
        + 2 * seq * e + 2 * state * 4 + a
    return fwd, bwd


def step_bytes(cfg, mix) -> float:
    b = mix["n_clients"] * mix["batch_per_client"]
    s = cfg.get("meta_tokens", 0) + mix["seq_len"]
    ssm = cfg["ssm"]
    fwd, bwd = layer_bytes(b, s, ssm["expand"] * cfg["d_model"],
                           ssm["d_state"], cfg["compute_dtype"])
    return float(cfg["num_layers"] * (2 * fwd + bwd))
