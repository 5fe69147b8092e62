"""Step-pipeline tests: prefetch determinism/resume, batch placement,
train-state donation aliasing, and the sync-free trainer loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config, reduced
from repro.core import mpsl, split
from repro.data import (ClientLoader, PrefetchLoader, SyntheticLM,
                        dirichlet_partition)
from repro.launch.train import make_lm_loader
from repro.optim import schedules
from repro.parallel import sharding
from repro.train import MetricsRing, Trainer, TrainerConfig


def _base_loader(seed=0, n=4, bn=2):
    ds = SyntheticLM(vocab_size=64, seq_len=32, size=512, seed=seed)
    shards = dirichlet_partition(ds.labels, n, alpha=0.1, seed=seed,
                                 min_per_client=bn)
    return ClientLoader(ds, shards, bn, seed=seed)


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Prefetch determinism / resume


def test_prefetch_depth_invariance():
    """Batches at step k are bitwise identical with depth 0 / 2 / 8."""
    ref = {k: _base_loader().batch(k) for k in (0, 3, 7)}
    for depth in (0, 2, 8):
        with PrefetchLoader(_base_loader(), depth=depth) as pf:
            for k in (0, 3, 7):
                # non-contiguous requests force mid-stream reseeds too
                _tree_equal(pf.batch(k), ref[k])


def test_prefetch_sequential_stream_matches():
    inner = _base_loader()
    with PrefetchLoader(_base_loader(), depth=3) as pf:
        for k in range(10):
            _tree_equal(pf.batch(k), inner.batch(k))


def test_prefetch_resume_consumes_failed_runs_batches():
    """Crash at step 5, resume at 5: the restarted prefetcher yields
    exactly the batches the failed run would have consumed."""
    inner = _base_loader()
    pf = PrefetchLoader(_base_loader(), depth=4)
    for k in range(5):
        pf.batch(k)
    pf.close()                                   # "crash"
    pf2 = PrefetchLoader(_base_loader(), depth=4)
    for k in range(5, 9):
        _tree_equal(pf2.batch(k), inner.batch(k))
    pf2.close()


def test_prefetch_propagates_producer_error():
    class Boom:
        def batch(self, step):
            if step == 2:
                raise RuntimeError("boom")
            return {"x": np.zeros(3)}

    pf = PrefetchLoader(Boom(), depth=2)
    pf.batch(0)
    pf.batch(1)
    with pytest.raises(RuntimeError, match="boom"):
        pf.batch(2)


def test_prefetch_placement_commits_to_device():
    pf = PrefetchLoader(_base_loader(), depth=2,
                        place_fn=sharding.place_batch)
    b = pf.batch(0)
    assert all(isinstance(v, jax.Array) for v in b.values())
    assert all(v.committed for v in b.values())
    pf.close()


# ---------------------------------------------------------------------------
# Donated train step


def _tiny_train(donate, n=2, bn=2, seq=24):
    cfg = reduced(get_config("minitron-4b"))
    mp = MPSLConfig(n_clients=n, trainable_blocks=1, head_adapter_rank=4)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32", learning_rate=1e-3)
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    state = mpsl.place_state(mpsl.init_state(params, frozen))
    loss_fn = mpsl.make_lm_loss(cfg, run)
    step_fn = mpsl.jit_train_step(
        mpsl.make_train_step(loss_fn, run, schedules.constant(1e-3)),
        donate=donate)
    loader = make_lm_loader(cfg, n, bn, seq, seed=0)
    batch = {k: jnp.asarray(v) for k, v in loader.batch(0).items()}
    return state, step_fn, batch


@pytest.mark.slow
def test_donated_step_aliases_state_buffers():
    """The lowered step aliases (at least) params + both Adam moments in
    place — no 2x param+opt peak allocation."""
    state, step_fn, batch = _tiny_train(donate=True)
    compiled = step_fn.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    if ma is None or not hasattr(ma, "alias_size_in_bytes"):
        pytest.skip("backend exposes no memory analysis")
    donatable = sum(
        np.prod(l.shape) * l.dtype.itemsize
        for tree in (state["params"], state["opt"]["mu"], state["opt"]["nu"])
        for l in jax.tree_util.tree_leaves(tree))
    assert ma.alias_size_in_bytes >= donatable


def test_donated_handle_raises_on_reuse():
    state, step_fn, batch = _tiny_train(donate=True)
    new_state, _ = step_fn(state, batch)
    with pytest.raises((RuntimeError, ValueError)):
        step_fn(state, batch)                    # old buffers are gone
    # ... but the returned state keeps working
    step_fn(new_state, batch)


@pytest.mark.slow
def test_undonated_step_allows_reuse():
    state, step_fn, batch = _tiny_train(donate=False)
    step_fn(state, batch)
    step_fn(state, batch)


@pytest.mark.slow
def test_donated_matches_undonated():
    state_a, step_a, batch = _tiny_train(donate=True)
    state_b, step_b, _ = _tiny_train(donate=False)
    out_a, _ = step_a(state_a, batch)
    out_b, _ = step_b(state_b, batch)
    for x, y in zip(jax.tree_util.tree_leaves(out_a["params"]),
                    jax.tree_util.tree_leaves(out_b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Sync-free trainer loop


def test_metrics_ring_keeps_latest():
    ring = MetricsRing(4)
    for s in range(1, 8):
        ring.push(s, {"loss": jnp.float32(s)})
    got = ring.read_latest()
    assert got["step"] == 7
    assert float(got["loss"]) == 7.0


def test_metrics_ring_wraparound_bounds_live_entries():
    """Wraparound keeps at most `size` entries alive (the memory bound
    that lets the host run ahead without holding every step's metrics),
    and they are exactly the most recent `size` steps."""
    ring = MetricsRing(4)
    for s in range(1, 10):
        ring.push(s, {"loss": jnp.float32(s)})
    live = [e for e in ring._slots if e is not None]
    assert len(live) == 4
    assert sorted(step for step, _ in live) == [6, 7, 8, 9]
    assert ring.read_latest()["step"] == 9


def test_metrics_ring_overflow_slot_collision():
    """Pushing a step `size` ahead of a live entry overwrites that slot
    (step % size collision): the old metrics are dropped, latest() still
    resolves by step number, and an empty ring reads as None."""
    ring = MetricsRing(4)
    ring.push(1, {"loss": jnp.float32(1.0)})
    ring.push(5, {"loss": jnp.float32(5.0)})   # 5 % 4 == 1: same slot
    live = [e for e in ring._slots if e is not None]
    assert len(live) == 1
    got = ring.read_latest()
    assert got["step"] == 5 and float(got["loss"]) == 5.0
    assert MetricsRing(2).latest() is None
    assert MetricsRing(2).read_latest() is None


def test_obs_enabled_leaves_step_jaxpr_unchanged(tmp_path):
    """Telemetry neutrality: the traced program of the jitted train step
    is bit-for-bit identical with the recorder disabled vs enabled — the
    obs hooks fire on the host at trace time and insert nothing into the
    computation."""
    from repro import obs
    from repro.core import mpsl as mpsl_mod

    cfg = reduced(get_config("minitron-4b"))
    mp = MPSLConfig(n_clients=2, trainable_blocks=1, head_adapter_rank=4,
                    compress_uplink=True, compress_downlink=True)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32", learning_rate=1e-3)
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    state = mpsl_mod.init_state(params, frozen)
    loss_fn = mpsl_mod.make_lm_loss(cfg, run)
    step = mpsl_mod.make_train_step(loss_fn, run, schedules.constant(1e-3))
    loader = make_lm_loader(cfg, 2, 2, 24, seed=0)
    batch = {k: jnp.asarray(v) for k, v in loader.batch(0).items()}

    assert not obs.get().enabled
    jaxpr_off = str(jax.make_jaxpr(step)(state, batch))
    with obs.enabled(str(tmp_path / "log.jsonl")):
        jaxpr_on = str(jax.make_jaxpr(step)(state, batch))
    assert jaxpr_on == jaxpr_off


@pytest.mark.slow
def test_trainer_overlapped_end_to_end():
    """Full pipeline: prefetch + donation + sync-free metrics, and the
    result reflects the LAST step, not the last logged step."""
    cfg = reduced(get_config("minitron-4b"))
    mp = MPSLConfig(n_clients=2, trainable_blocks=1, head_adapter_rank=4)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    compute_dtype="float32", learning_rate=1e-3)
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    state = mpsl.place_state(mpsl.init_state(params, frozen))
    loss_fn = mpsl.make_lm_loss(cfg, run)
    step_fn = mpsl.jit_train_step(
        mpsl.make_train_step(loss_fn, run, schedules.constant(1e-3)))
    loader = PrefetchLoader(make_lm_loader(cfg, 2, 2, 24, seed=0), depth=3,
                            place_fn=sharding.place_batch)
    t = Trainer(step_fn, state, loader,
                TrainerConfig(total_steps=7, log_every=100),
                log_fn=lambda s: None)
    out = t.run()
    loader.close()
    assert out["final_loss"] is not None
    assert out["steps_per_sec"] > 0
    assert 0.0 <= out["host_stall_frac"] <= 1.0
    # history closes on the final step even though log_every never fired
    assert t.metrics_history[-1]["step"] == 7
    assert out["final_loss"] == t.metrics_history[-1]["loss"]
    assert [s for s, _ in t.ring.entries_after(0)] == list(range(1, 8))
