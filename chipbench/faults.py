"""Faults planted in the timed path, to show that the comparison catches
them. The benchmark's own runs use none of these; the tests and the
calibration do.

  state_unchanged  the step returns the state it was given;
  half_batch       half of each client's rows are left out (where a client
                   has one row, half of the clients) and the loss is the
                   mean over the rest.
"""
from __future__ import annotations

import jax.numpy as jnp


def halve(batch):
    """The first half of each client's rows, or with one row per client,
    the first half of the clients (the mask drops the others)."""
    if batch["labels"].shape[1] >= 2:
        return {k: v if k == "mask" else v[:, :v.shape[1] // 2]
                for k, v in batch.items()}
    n = batch["mask"].shape[0]
    keep = (jnp.arange(n) < n // 2).astype(batch["mask"].dtype)
    return dict(batch, mask=batch["mask"] * keep)


def state_unchanged(step):
    def faulty(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return faulty


def half_batch(loss_fn):
    def faulty(params, frozen, batch, rng):
        return loss_fn(params, frozen, halve(batch), rng)
    return faulty


FAULTS = {"state_unchanged": {"step": state_unchanged},
          "half_batch": {"loss": half_batch}}
