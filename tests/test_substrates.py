"""Substrate unit + property tests: data partitioning, seekable loader,
checkpoint round-trips, optimizer, schedules."""
import os

import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.data import (ClientLoader, SyntheticLM, SyntheticMultimodal,
                        dirichlet_partition)
from repro.data.partition import partition_stats
from repro.optim import (adamw_init, adamw_update, apply_updates,
                         clip_by_global_norm, warmup_cosine)


# ---------------------------------------------------------------------------
# Dirichlet partition (paper: Dir(0.1) over classes)


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    n_clients=st.integers(2, 12),
    alpha=st.sampled_from([0.1, 1.0, 10.0]),
    seed=st.integers(0, 100),
)
def test_partition_is_exact_cover(n_clients, alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 7, 500)
    shards = dirichlet_partition(labels, n_clients, alpha, seed)
    allidx = np.concatenate(shards)
    assert len(allidx) == len(labels)
    assert len(np.unique(allidx)) == len(labels)      # disjoint + complete
    assert all(len(s) >= 1 for s in shards)


def test_partition_noniid_at_low_alpha():
    labels = np.random.default_rng(0).integers(0, 10, 4000)
    lo = dirichlet_partition(labels, 8, alpha=0.1, seed=1)
    hi = dirichlet_partition(labels, 8, alpha=100.0, seed=1)

    def skew(shards):
        h = partition_stats(shards, labels, 10).astype(float)
        h = h / np.maximum(h.sum(1, keepdims=True), 1)
        return float(np.mean(np.max(h, axis=1)))

    assert skew(lo) > skew(hi) + 0.2    # low alpha => concentrated classes


def test_partition_deterministic():
    labels = np.random.default_rng(0).integers(0, 5, 300)
    a = dirichlet_partition(labels, 4, 0.1, seed=7)
    b = dirichlet_partition(labels, 4, 0.1, seed=7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Seekable loader (restart reproducibility — the FT invariant)


def test_loader_step_indexed_reproducible():
    ds = SyntheticLM(vocab_size=64, seq_len=16, size=256)
    shards = dirichlet_partition(ds.labels, 4, seed=0, min_per_client=2)
    l1 = ClientLoader(ds, shards, batch_per_client=2, seed=3)
    l2 = ClientLoader(ds, shards, batch_per_client=2, seed=3)
    for step in (0, 5, 17):
        b1, b2 = l1.batch(step), l2.batch(step)
        for k in b1:
            np.testing.assert_array_equal(b1[k], b2[k])


def test_loader_dropout_mask_never_empty():
    ds = SyntheticLM(vocab_size=64, seq_len=16, size=256)
    shards = dirichlet_partition(ds.labels, 4, seed=0, min_per_client=2)
    loader = ClientLoader(ds, shards, 2, seed=0, drop_prob=0.99)
    for step in range(10):
        assert loader.batch(step)["mask"].sum() >= 1


def test_multimodal_dataset_shapes():
    ds = SyntheticMultimodal(modalities=("vision", "text"), n_classes=4,
                             size=64)
    b = ds.sample(np.arange(8))
    assert b["vision"].shape == (8, 224, 224, 3)
    assert b["text"].shape == (8, 77)
    assert b["labels"].shape == (8,)


# ---------------------------------------------------------------------------
# Checkpointing


def _state(key):
    return {
        "w": jax.random.normal(key, (4, 8)),
        "frozen_bf16": jax.random.normal(key, (3, 3)).astype(jnp.bfloat16),
        "nested": {"count": jnp.zeros((), jnp.int32)},
        "rng": jax.random.PRNGKey(7),
    }


def test_checkpoint_roundtrip(tmp_path):
    st0 = _state(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 5, st0)
    restored, manifest = restore_checkpoint(str(tmp_path), st0)
    assert manifest["step"] == 5
    np.testing.assert_array_equal(np.asarray(st0["w"]),
                                  np.asarray(restored["w"]))
    assert restored["frozen_bf16"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(st0["frozen_bf16"].astype(jnp.float32)),
        np.asarray(jnp.asarray(restored["frozen_bf16"]).astype(jnp.float32)))
    # restored rng key must be usable
    jax.random.fold_in(restored["rng"], 3)


def test_checkpoint_latest_and_gc(tmp_path):
    st0 = _state(jax.random.PRNGKey(1))
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, st0, keep=2)
    assert latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert len([k for k in kept if k.startswith("step_")]) == 2


def test_checkpoint_crash_consistency(tmp_path):
    """A stale .tmp dir (simulated crash) is ignored by restore."""
    st0 = _state(jax.random.PRNGKey(2))
    save_checkpoint(str(tmp_path), 1, st0)
    os.makedirs(tmp_path / "step_00000002.tmp")       # crashed write
    assert latest_step(str(tmp_path)) == 1


def test_async_checkpointer(tmp_path):
    st0 = _state(jax.random.PRNGKey(3))
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(7, st0)
    ck.wait()
    assert latest_step(str(tmp_path)) == 7


# ---------------------------------------------------------------------------
# Optimizer


def test_adamw_decreases_quadratic():
    w = jnp.array([3.0, -2.0])
    opt = adamw_init(w)
    for _ in range(200):
        g = 2 * w
        upd, opt = adamw_update(g, opt, w, lr=5e-2)
        w = apply_updates(w, upd)
    assert float(jnp.abs(w).max()) < 0.1


def test_clip_by_global_norm():
    g = {"a": jnp.ones((10,)) * 10.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5
    assert float(norm) > 1.0


def test_warmup_cosine_shape():
    sched = warmup_cosine(1e-3, 10, 100)
    assert float(sched(0)) == 0.0
    assert abs(float(sched(10)) - 1e-3) < 1e-9
    assert float(sched(100)) < float(sched(50))
    assert float(sched(100)) >= 1e-4 - 1e-9           # min_ratio floor
