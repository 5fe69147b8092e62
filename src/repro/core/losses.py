"""Losses: memory-efficient LM cross-entropy, classification CE, and the
ONE-PEACE-style symmetric contrastive loss the paper uses for retrieval.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def chunked_softmax_xent(h, w, labels, valid=None, chunk: int = 512,
                         impl: str = "jnp"):
    """Per-token CE without materializing full [T, V] f32 logits.

    h [T, D], w [D, V], labels [T] -> per-token loss [T].

    impl='jnp' (what the train step runs, and the oracle): `chunk`-token
    slices under jax.checkpoint so the backward recomputes each chunk's
    logits instead of saving them.
    impl='pallas': the fused online-softmax kernel (repro.kernels),
    vocab-tiled in both directions; only tests select it.
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        # w in the activation dtype, as the jnp path computes; the kernel's
        # default 512-wide vocab tile keeps the double-buffered [D, 512] w
        # and [chunk, D] h tiles inside VMEM at D = 3072
        losses = kops.softmax_xent_tokens(h, w.astype(h.dtype),
                                          labels.astype(jnp.int32),
                                          block_t=min(chunk, h.shape[0]))
        if valid is not None:
            losses = losses * valid.astype(jnp.float32)
        return losses
    t, d = h.shape
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))

    hc = h.reshape(n, chunk, d)
    lc = labels.reshape(n, chunk)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def one(args):
        hx, lx = args
        logits = jnp.einsum("cd,dv->cv", hx, w.astype(hx.dtype))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[:, None], axis=-1)[:, 0]
        return lse - gold

    losses = jax.lax.map(one, (hc, lc)).reshape(n * chunk)
    losses = losses[:t]
    if valid is not None:
        losses = losses * valid.astype(jnp.float32)
    return losses


def softmax_xent(logits, labels):
    """Plain CE for small output spaces (classification heads)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def contrastive_loss(emb_a, emb_b, temperature: float = 0.07):
    """Symmetric InfoNCE over the GLOBAL batch (paper Sec. 4: batch size
    drives modality alignment / feature collapse). emb_* [B, D]."""
    a = emb_a / jnp.linalg.norm(emb_a.astype(jnp.float32), axis=-1,
                                keepdims=True).clip(1e-6)
    b = emb_b / jnp.linalg.norm(emb_b.astype(jnp.float32), axis=-1,
                                keepdims=True).clip(1e-6)
    logits = (a @ b.T) / temperature
    labels = jnp.arange(a.shape[0])
    l_ab = softmax_xent(logits, labels)
    l_ba = softmax_xent(logits.T, labels)
    return 0.5 * (l_ab + l_ba)          # per-sample [B]


def recall_at_k(emb_a, emb_b, k: int = 1):
    """Retrieval metric: fraction of a->b matches ranked in top-k."""
    a = emb_a / jnp.linalg.norm(emb_a, axis=-1, keepdims=True).clip(1e-6)
    b = emb_b / jnp.linalg.norm(emb_b, axis=-1, keepdims=True).clip(1e-6)
    sims = a @ b.T
    gold = jnp.arange(a.shape[0])
    rank = jnp.sum(sims > jnp.take_along_axis(
        sims, gold[:, None], axis=-1), axis=-1)
    return jnp.mean(rank < k)
