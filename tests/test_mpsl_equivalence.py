"""The paper's core mechanism, property-tested.

1. The aggregated single backward pass (server computes grad of
   L_S = sum w_n L_n once) produces EXACTLY the gradients of N separate
   per-client backward passes combined with the same weights — i.e. the
   Lyu-et-al aggregation the paper adopts loses nothing (hypothesis
   sweep over client counts, masks, seeds).
2. Client isolation: client i's head gradient does not depend on client
   j's data (no cross-client leakage through the shared body forward).
3. Dropped clients (mask=0) contribute exactly zero gradient.
"""
import dataclasses

import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config, reduced
from repro.core import mpsl, split


def _setup(n_clients, seed=0, arch="minitron-4b"):
    cfg = reduced(get_config(arch))
    mp = MPSLConfig(n_clients=n_clients, trainable_blocks=1,
                    head_adapter_rank=4)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32")
    key = jax.random.PRNGKey(seed)
    params, frozen, _ = split.init_mpsl_lm(key, cfg, run)
    loss_fn = mpsl.make_lm_loss(cfg, run)
    return cfg, params, frozen, loss_fn


def _batch(cfg, n, bn, s, seed, mask=None):
    key = jax.random.PRNGKey(seed + 100)
    return {
        "tokens": jax.random.randint(key, (n, bn, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                     (n, bn, s), 0, cfg.vocab_size),
        "mask": jnp.ones((n,), jnp.float32) if mask is None
        else jnp.asarray(mask, jnp.float32),
    }


@pytest.mark.slow
@hypothesis.settings(max_examples=6, deadline=None)
@hypothesis.given(
    n=st.sampled_from([2, 3, 4]),
    seed=st.integers(0, 10_000),
    drop=st.integers(0, 3),
)
def test_aggregated_equals_per_client(n, seed, drop):
    cfg, params, frozen, loss_fn = _setup(n, seed % 3)
    mask = np.ones(n)
    if drop < n and n > 1:
        mask[drop] = 0.0
    batch = _batch(cfg, n, 2, 12, seed, mask)
    rng = jax.random.PRNGKey(seed)
    g_agg = jax.grad(lambda p: loss_fn(p, frozen, batch, rng)[0])(params)
    g_pc, _, _ = mpsl._per_client_grads(loss_fn, params, frozen, batch, rng)
    for a, b in zip(jax.tree_util.tree_leaves(g_agg),
                    jax.tree_util.tree_leaves(g_pc)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-8
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


@pytest.mark.slow
def test_client_isolation():
    """Perturbing client 1's data must not change client 0's head grad."""
    n = 3
    cfg, params, frozen, loss_fn = _setup(n)
    rng = jax.random.PRNGKey(0)
    b1 = _batch(cfg, n, 2, 12, seed=0)
    b2 = {**b1, "tokens": b1["tokens"].at[1].set(
        (b1["tokens"][1] + 7) % cfg.vocab_size)}
    g1 = jax.grad(lambda p: loss_fn(p, frozen, b1, rng)[0])(params)
    g2 = jax.grad(lambda p: loss_fn(p, frozen, b2, rng)[0])(params)
    # NB: grads flow into 'b' at init (LoRA 'b'=0 makes d/d'a' zero)
    a1 = g1["client"]["adapter"]["b"]
    a2 = g2["client"]["adapter"]["b"]
    # client 1's adapter grad changes...
    assert float(jnp.max(jnp.abs(a1[1] - a2[1]))) > 0
    # ...but clients 0 and 2 are bitwise unaffected
    np.testing.assert_array_equal(np.asarray(a1[0]), np.asarray(a2[0]))
    np.testing.assert_array_equal(np.asarray(a1[2]), np.asarray(a2[2]))


@pytest.mark.slow
def test_dropped_client_gets_zero_grad():
    n = 3
    cfg, params, frozen, loss_fn = _setup(n)
    batch = _batch(cfg, n, 2, 12, seed=1, mask=[1.0, 0.0, 1.0])
    g = jax.grad(lambda p: loss_fn(p, frozen, batch,
                                   jax.random.PRNGKey(0))[0])(params)
    a = g["client"]["adapter"]["b"]
    assert float(jnp.max(jnp.abs(a[1]))) == 0.0
    assert float(jnp.max(jnp.abs(a[0]))) > 0.0


@pytest.mark.slow
def test_weight_renormalization_on_dropout():
    """With uniform data, dropping a client renormalizes w_n = 1/(N-1):
    the loss is the mean over participants, not scaled down."""
    n = 4
    cfg, params, frozen, loss_fn = _setup(n)
    batch = _batch(cfg, n, 2, 12, seed=2)
    # make all clients' data identical
    for k in ("tokens", "labels"):
        batch[k] = jnp.broadcast_to(batch[k][:1], batch[k].shape)
    rng = jax.random.PRNGKey(0)
    l_full, _ = loss_fn(params, frozen, batch, rng)
    l_drop, _ = loss_fn(params, frozen,
                        {**batch, "mask": jnp.array([1., 1., 0., 1.])}, rng)
    assert abs(float(l_full) - float(l_drop)) < 1e-5


@pytest.mark.slow
@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(mu=st.sampled_from([1, 2, 4]))
def test_microbatching_preserves_gradients(mu):
    """Grad accumulation over Bn splits == full-batch gradient."""
    from repro.optim import schedules
    n, bn, s = 2, 4, 12
    cfg, params, frozen, loss_fn = _setup(n)
    batch = _batch(cfg, n, bn, s, seed=3)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    mpsl=MPSLConfig(n_clients=n, trainable_blocks=1,
                                    head_adapter_rank=4),
                    compute_dtype="float32")
    state = mpsl.init_state(params, frozen)
    step = jax.jit(mpsl.make_train_step(loss_fn, run,
                                        schedules.constant(0.0),
                                        microbatches=mu))
    _, metrics = step(state, batch)
    # compare against mu=1 loss
    step1 = jax.jit(mpsl.make_train_step(loss_fn, run,
                                         schedules.constant(0.0)))
    _, metrics1 = step1(state, batch)
    assert abs(float(metrics["loss"]) - float(metrics1["loss"])) < 1e-4
