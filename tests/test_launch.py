"""The training launcher's device handling: a host mesh over every
device, sharded initialisation, the fixed compile-cache location, and a
profiler window that fails loudly."""
import math

import jax
import numpy as np
import pytest

from repro.core import mpsl
from repro.launch import compile_cache, train
from repro.launch import mesh as mesh_lib
from repro.obs.spans import ProfileWindow
from repro.parallel import sharding

TINY = ["--arch", "hymba-1.5b", "--steps", "3", "--n-clients", "2",
        "--batch-per-client", "1", "--seq", "24", "--log-every", "1",
        "--prefetch", "1"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Point the compile cache at a test directory; restore JAX's setting
    (and drop its cache handle) afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    meta = jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield tmp_path / "jc"
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", meta)
    cc.reset_cache()


def test_compile_cache_follows_env(cache_dir):
    assert compile_cache.enable_compilation_cache() == str(cache_dir)
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)


def test_compile_cache_keys_on_op_names(cache_dir):
    """Programs that differ only in a named scope get cache entries of
    their own, so a traced run never reads another tree's op names."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = [getattr(jax.config, n) for n in names]

    def scaled(scope):
        def scaled_by_two(x):
            with jax.named_scope(scope):
                return x * 2.0
        return scaled_by_two

    try:
        for n in names:
            jax.config.update(n, 0)
        cc.reset_cache()
        compile_cache.enable_compilation_cache()
        for scope in ("client_head", "optimizer", "client_head"):
            jax.jit(scaled(scope)).lower(np.ones(4, np.float32)).compile()
    finally:
        for n, v in zip(names, before):
            jax.config.update(n, v)
    entries = [p for p in cache_dir.iterdir()
               if p.name.startswith("jit_scaled_by_two")]
    assert len(entries) == 2


def test_compile_cache_defaults_to_checkout(cache_dir, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compilation_cache()
    root = compile_cache.CHECKOUT
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


def test_launcher_trains_reduced_hybrid(cache_dir):
    result = train.train(train.parse_args(TINY))
    losses = [h["loss"] for h in result["history"]]
    assert len(losses) == 3 and all(math.isfinite(l) for l in losses)


def test_reduced_runs_float32_full_runs_bfloat16():
    _, run = train.make_run_config(train.parse_args(TINY))
    assert run.compute_dtype == "float32"
    _, run = train.make_run_config(train.parse_args(TINY + ["--full"]))
    assert run.compute_dtype == "bfloat16"


def test_sharded_init_matches_state_shardings():
    """init_train_state places every leaf with the sharding the train step
    expects, and equals the eager init value for value."""
    args = train.parse_args(TINY)
    cfg, run = train.make_run_config(args)
    mesh = mesh_lib.make_host_mesh()
    key = jax.random.PRNGKey(0)
    with sharding.use_mesh(mesh):
        state = train.init_train_state(key, cfg, run, mesh)
        want = mpsl.state_shardings(state, mesh)
    got = jax.tree_util.tree_map(lambda x: x.sharding, state)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.is_equivalent_to(b, 1), got, want,
        is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)))
    from repro.core import split
    params, frozen, _ = split.init_mpsl_lm(key, cfg, run)
    eager = mpsl.init_state(params, frozen, run.seed)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(eager)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stage", ["start", "stop"])
def test_profile_window_raises_when_trace_fails(tmp_path, monkeypatch,
                                                stage):
    def broken(*_a, **_k):
        raise OSError("profiler unavailable")

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        broken if stage == "start"
                        else lambda d: started.append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace", broken)
    win = ProfileWindow(str(tmp_path), start_step=1, num_steps=1)
    win.on_step(0)
    if stage == "start":
        with pytest.raises(RuntimeError, match="did not start"):
            win.on_step(1)
    else:
        win.on_step(1)
        assert started == [str(tmp_path)]
        with pytest.raises(RuntimeError, match="did not stop"):
            win.on_step(2)


def test_profile_window_inert_without_dir(monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *_: pytest.fail("profiler started"))
    win = ProfileWindow(None, start_step=0)
    for i in range(3):
        win.on_step(i)
    win.stop()
