"""The benchmark's traffic: pools of distinct training batches made from a
seed and a traffic mix's parameters.

Each generator follows the recipe of the program's synthetic datasets
(`repro.data.synthetic`), drawn in bulk from one numpy generator, so a
pool costs one vectorised draw rather than a Python loop per sample:

  multimodal_cls  per-class templates for each modality; a sample is its
                  class template (or, with probability `cross_noise`,
                  another class's) plus noise: Gaussian for images and
                  spectrograms, a `noise` share of replaced ids for text.
  lm_induction    uniform token streams in which each of `n_patterns`
                  trigger tokens is followed by its bound partner 90% of
                  the time, so in-context copying lowers the loss.

Every batch is a dict of numpy arrays shaped [clients, per-client batch,
...] plus the participation mask, as the MPSL step takes them. The same
(seed, mix, config) gives the same pool.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def _raw_shape(cfg, modality):
    tk = cfg["tokenizers"][modality]
    if modality == "vision":
        return tuple(tk["image"])
    if modality == "audio":
        return tuple(tk["mel"])
    return (tk["tokens"],)


def multimodal_cls(cfg, mix, seed):
    n, bn, c = mix["n_clients"], mix["batch_per_client"], mix["n_classes"]
    rng = rng_for(seed, 1)
    templates = {}
    for m in mix["modalities"]:
        if m == "text":
            vocab = cfg["tokenizers"]["text"]["vocab_size"]
            templates[m] = rng.integers(0, vocab, (c,) + _raw_shape(cfg, m),
                                        dtype=np.int32)
        else:
            templates[m] = rng.standard_normal(
                (c,) + _raw_shape(cfg, m), dtype=np.float32)
    pool = []
    for _ in range(mix["pool_batches"]):
        labels = rng.integers(0, c, (n, bn), dtype=np.int32)
        batch = {"labels": labels}
        for m in mix["modalities"]:
            swap = rng.random((n, bn)) < mix["cross_noise"]
            y = np.where(swap, rng.integers(0, c, (n, bn), dtype=np.int32),
                         labels)
            x = templates[m][y]
            if m == "text":
                vocab = cfg["tokenizers"]["text"]["vocab_size"]
                length = x.shape[-1]
                k = int(length * mix["noise"])
                # k distinct positions per sample: the first k of a random
                # permutation of the positions
                pos = np.argsort(rng.random((n, bn, length)), axis=-1)[..., :k]
                np.put_along_axis(
                    x, pos, rng.integers(0, vocab, (n, bn, k), dtype=np.int32),
                    axis=-1)
            else:
                x = x + np.float32(mix["noise"]) * rng.standard_normal(
                    x.shape, dtype=np.float32)
            batch[m] = x
        batch["mask"] = np.ones((n,), np.float32)
        pool.append(batch)
    return pool


def lm_induction(cfg, mix, seed):
    n, bn, s = mix["n_clients"], mix["batch_per_client"], mix["seq_len"]
    vocab = cfg["vocab_size"]
    rng = rng_for(seed, 2)
    pool = []
    for _ in range(mix["pool_batches"]):
        seq = rng.integers(0, vocab, (n * bn, s), dtype=np.int32)
        for row in seq:
            triggers = rng.integers(0, vocab, mix["n_patterns"])
            partners = rng.integers(0, vocab, mix["n_patterns"])
            bind = np.full(vocab, -1, np.int64)
            bind[triggers] = partners
            keep = rng.random(s) < 0.9
            # sequential, as a partner may itself be a trigger
            for j in range(s - 1):
                p = bind[row[j]]
                if p >= 0 and keep[j]:
                    row[j + 1] = p
        tokens = seq.reshape(n, bn, s)
        # labels are the tokens: the loss shifts them by one itself
        pool.append({"tokens": tokens, "labels": tokens.copy(),
                     "mask": np.ones((n,), np.float32)})
    return pool


GENERATORS = {"multimodal_cls": multimodal_cls, "lm_induction": lm_induction}


def make_pool(cfg, mix, seed):
    return GENERATORS[mix["generator"]](cfg, mix, seed)
