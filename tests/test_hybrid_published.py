"""Hymba-1.5B's published mechanisms in the program, at a small size on the
CPU: meta tokens and cross-layer K/V sharing (ModelConfig fields, off by
default), the windowed mask with a visible prefix, and the selective
scan's core chosen from what a call can observe."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config, reduced
from repro.core import mpsl, split
from repro.kernels import ops, ref
from repro.kernels import selective_scan as kss
from repro.models import attention, mamba, model as M
from repro.optim import schedules

# Three bf16 steps of `_steps(_cfg())`: (loss, grad norm), as the program
# computed them before meta tokens and K/V sharing existed.
BEFORE = [(6.011420249938965, 1.800890326499939),
          (5.957364082336426, 1.8070038557052612),
          (6.018270492553711, 1.8282947540283203)]


def _cfg(**kw):
    return reduced(get_config("hymba-1.5b"), num_layers=5,
                   global_layers=(0, 4), sliding_window=8, **kw)


PUBLISHED = dict(meta_tokens=4, kv_share_groups=((1, 2),))


def _run(cfg):
    mp = MPSLConfig(n_clients=2, trainable_blocks=2, head_adapter_rank=4)
    return RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                     compute_dtype="bfloat16")


def _batch(cfg, rng):
    t = rng.integers(0, cfg.vocab_size, (2, 1, 32)).astype(np.int32)
    return {"tokens": t, "labels": t, "mask": np.ones((2,), np.float32)}


def _steps(cfg, n=3):
    run = _run(cfg)
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    state = mpsl.init_state(params, frozen)
    step = jax.jit(mpsl.make_train_step(mpsl.make_lm_loss(cfg, run), run,
                                        schedules.constant(3e-3)))
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        state, m = step(state, _batch(cfg, rng))
        out.append((float(m["loss"]), float(m["grad_norm"])))
    return out


def test_fields_off_give_the_same_losses_bit_for_bit():
    assert _steps(_cfg()) == BEFORE


def test_published_mechanisms_train():
    losses = _steps(_cfg(**PUBLISHED))
    assert all(np.isfinite(v) for pair in losses for v in pair)
    assert [l for l, _ in losses] != [l for l, _ in BEFORE]


def test_layout_of_meta_tokens_and_kv_pairs():
    cfg = _cfg(**PUBLISHED)
    segs = M.body_segments(cfg)
    assert [(s.kind.is_global, s.kind.kv_pair, s.count, s.steps)
            for s in segs] == [(True, False, 1, 1), (False, True, 2, 1),
                               (False, False, 1, 1), (True, False, 1, 1)]
    params, frozen, plan = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg,
                                              _run(cfg))
    assert frozen["meta_tokens"].shape == (4, cfg.d_model)
    pair = frozen["segments"][1]
    assert set(pair["first"]["mix"]["attn"]) == {"wq", "wk", "wv", "wo"}
    assert set(pair["second"]["mix"]["attn"]) == {"wq", "wo"}
    assert mpsl.len_from_params(frozen) == plan.boundary == 3
    full = M.init_lm(jax.random.PRNGKey(0), cfg)
    assert sum(a.size for a in jax.tree_util.tree_leaves(full)) == \
        M.count_params_analytic(cfg)


def test_kv_groups_are_checked_and_never_split():
    with pytest.raises(ValueError):
        M.body_segments(_cfg(kv_share_groups=((3, 4),)))   # 4 is global
    with pytest.raises(ValueError):
        M.body_segments(_cfg(kv_share_groups=((1, 3),)))
    segs = M.body_segments(_cfg(**PUBLISHED))
    with pytest.raises(ValueError):
        split.split_segments(segs, 2)
    f, t = split.split_segments(segs, 3)
    assert [s.count for s in f] == [1, 2] and [s.count for s in t] == [1, 1]


def test_events_name_each_core_and_leave_the_program_unchanged(tmp_path):
    """One `ssm/impl` and one `attn/impl` (with its visible prefix) per
    traced call site, one `hybrid/kv_share`; the lowered step is the same
    with the recorder on or off."""
    cfg = _cfg(**PUBLISHED)
    run = _run(cfg)
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    loss = mpsl.make_lm_loss(cfg, run)
    batch = _batch(cfg, np.random.default_rng(0))

    def lowered():
        fresh = jax.jit(lambda *a: loss(*a))      # traced anew each call
        return fresh.lower(params, frozen, batch,
                           jax.random.PRNGKey(1)).as_text(debug_info=False)
    off = lowered()
    log = tmp_path / "run.jsonl"
    with obs.enabled(str(log)):
        assert lowered() == off
    events = [r for r in map(json.loads, log.read_text().splitlines())
              if r.get("kind") == "event"]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e["fields"])
    seq = 4 + 32
    # global, pair (two layers), local, global: five call sites
    assert [f["prefix"] for f in by["attn/impl"]] == [0, 4, 4, 4, 0]
    assert all(f["sk"] == seq for f in by["attn/impl"])
    assert by["ssm/impl"] == [{"impl": "jnp", "s": seq, "d_inner": 128,
                               "chunk": 256, "block_d": None}] * 5
    assert by["hybrid/kv_share"] == [{"layers": [2], "from_layers": [1]}]


@pytest.mark.parametrize("window", [0, 16])
def test_prefix_mask_agrees_across_naive_blockwise_and_ref(window):
    key = jax.random.PRNGKey(3)
    b, s, h, kh, hd, prefix = 2, 80, 4, 2, 16, 8
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    def naive(prefix):
        bias = attention._mask_bias(pos, pos, True, window, None, prefix)
        return attention._naive_attention(q, k, v, bias)
    blockwise = attention._blockwise_attention(q, k, v, pos, pos, True,
                                               window, block=32,
                                               prefix=prefix)
    want = ref.flash_attention_ref(q, k, v, pos, pos, causal=True,
                                   window=window, prefix=prefix)
    for got in (naive(prefix), blockwise):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # the prefix keys reach queries past the window, and only there
    moved = np.abs(np.asarray(naive(prefix) - naive(0))).max(axis=(0, 2, 3))
    assert not moved[:window].any() if window else not moved.any()
    if window:
        assert moved[window:].all()


@pytest.mark.parametrize("impl,backend,s,di,cache,devices,want", [
    ("auto", "cpu", 4224, 3200, False, 1, ("jnp", 256, None)),
    ("auto", "tpu", 4224, 3200, False, 1, ("pallas", 192, 640)),
    ("auto", "tpu", 4096, 8192, False, 1, ("pallas", 256, 512)),
    ("auto", "tpu", 4224, 3200, False, 4, ("jnp", 256, None)),
    ("auto", "tpu", 4224, 3200, True, 1, ("jnp", 256, None)),
    ("auto", "tpu", 4100, 3200, False, 1, ("jnp", 256, None)),
    ("auto", "tpu", 4224, 100, False, 1, ("jnp", 256, None)),
    ("jnp", "tpu", 4224, 3200, False, 1, ("jnp", 256, None)),
    ("pallas", "cpu", 4224, 3200, False, 1, ("pallas", 192, 640)),
])
def test_resolve_ssm_impl(impl, backend, s, di, cache, devices, want):
    assert mamba.resolve_ssm_impl(impl, backend, s, di, has_cache=cache,
                                  devices=devices) == want


def test_pallas_scan_at_block_d_640_and_an_odd_length():
    """Interpret mode, two 640-lane blocks, 384 positions (two chunks of
    192): forward and the fused backward against the plain recurrence."""
    b, s, di, ds = 1, 384, 1280, 4
    chunk, block_d = kss.fit_blocks(s, di)
    assert (chunk, block_d) == (192, 640)
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (b, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, di))) * 0.1
    bi = jax.random.normal(jax.random.fold_in(key, 2), (b, s, ds))
    ci = jax.random.normal(jax.random.fold_in(key, 3), (b, s, ds))
    al = jnp.log(jnp.abs(jax.random.normal(jax.random.fold_in(key, 4),
                                           (di, ds))) + 0.5)
    out_k, vjp_k = jax.vjp(lambda *a: ops.selective_scan(
        *a, None, chunk, block_d), x, dt, bi, ci, al)
    out_r, vjp_r = jax.vjp(ref.selective_scan_ref, x, dt, bi, ci, al)
    cts = (jax.random.normal(jax.random.fold_in(key, 6), out_k[0].shape),
           jax.random.normal(jax.random.fold_in(key, 7), out_k[1].shape))
    for name, a, r in zip("y h_final dx ddt dB dC dA_log".split(),
                          list(out_k) + list(vjp_k(cts)),
                          list(out_r) + list(vjp_r(cts))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-4,
                                   rtol=1e-4, err_msg=name)
