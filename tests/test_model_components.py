"""Component-level model tests: attention impls agree, RoPE/M-RoPE
properties, MoE dense vs ragged dispatch, Mamba chunk invariance,
tokenizers."""
import dataclasses

import hypothesis
from hypothesis import strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, SSMConfig, get_config, reduced
from repro.models import attention, layers, mamba, moe, tokenizers as tok
from repro.models.model import BlockKind, apply_block, init_block


def _attn_cfg(**kw):
    base = reduced(get_config("minitron-4b"))
    return dataclasses.replace(base, **kw)


@pytest.mark.parametrize(
    "window", [0, pytest.param(16, marks=pytest.mark.slow)])
def test_blockwise_equals_naive(window):
    cfg = _attn_cfg()
    key = jax.random.PRNGKey(0)
    p = attention.init_attention(key, cfg)
    x = jax.random.normal(key, (2, 64, cfg.d_model)) * 0.5
    pos = layers.positions_from_shape(2, 64)
    o1, _ = attention.apply_attention(p, x, cfg, positions=pos, causal=True,
                                      window=window, impl="naive")
    o2, _ = attention.apply_attention(p, x, cfg, positions=pos, causal=True,
                                      window=window, impl="blockwise",
                                      block=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_pallas_impl_matches_naive():
    cfg = _attn_cfg()
    key = jax.random.PRNGKey(1)
    p = attention.init_attention(key, cfg)
    x = jax.random.normal(key, (1, 128, cfg.d_model)) * 0.5
    pos = layers.positions_from_shape(1, 128)
    o1, _ = attention.apply_attention(p, x, cfg, positions=pos, impl="naive")
    o2, _ = attention.apply_attention(p, x, cfg, positions=pos, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-4)


@pytest.mark.parametrize("impl,backend,sq,sk,cache,pre,devices,want", [
    ("auto", "tpu", 274, 274, False, False, 1, "pallas"),  # the ViT trunk
    ("auto", "cpu", 274, 274, False, False, 1, "naive"),
    ("auto", "tpu", 4096, 4096, False, False, 1, "blockwise"),
    ("auto", "tpu", 1, 4096, False, False, 1, "naive"),    # decode
    ("auto", "tpu", 1, 4096, True, False, 1, "naive"),     # cached decode
    ("auto", "tpu", 274, 274, True, False, 1, "naive"),    # cached prefill
    ("auto", "tpu", 274, 1500, False, True, 1, "naive"),   # cross-attn KV
    ("auto", "tpu", 274, 274, False, False, 4, "naive"),   # partitioned
    ("naive", "tpu", 274, 274, False, False, 1, "naive"),
    ("pallas", "cpu", 274, 274, True, False, 1, "pallas"),
    ("blockwise", "tpu", 274, 274, False, False, 1, "blockwise"),
    ("blockwise", "tpu", 1, 4096, True, False, 1, "naive"),
])
def test_resolve_impl(impl, backend, sq, sk, cache, pre, devices, want):
    assert attention.resolve_impl(impl, backend, sq, sk, has_cache=cache,
                                  has_precomputed_kv=pre,
                                  devices=devices) == want


def test_auto_attention_on_cpu_is_naive_and_recorded(tmp_path):
    """Off the TPU, "auto" lowers to the same program as "naive", with the
    recorder on or off; the recorder gets one trace-time event per call."""
    import json
    from repro import obs
    cfg = _attn_cfg()
    key = jax.random.PRNGKey(2)
    p = attention.init_attention(key, cfg)
    x = jax.random.normal(key, (2, 24, cfg.d_model))
    pos = layers.positions_from_shape(2, 24)

    def lowered(impl):
        return jax.jit(lambda p, x: attention.apply_attention(
            p, x, cfg, positions=pos, causal=False, impl=impl)[0]).lower(
                p, x).as_text(debug_info=False)
    naive = lowered("naive")
    assert lowered("auto") == naive
    log = tmp_path / "run.jsonl"
    with obs.enabled(str(log)):
        assert lowered("auto") == naive
    events = [r for r in map(json.loads, log.read_text().splitlines())
              if r.get("name") == "attn/impl"]
    assert [e["fields"] for e in events] == [
        {"impl": "naive", "sq": 24, "sk": 24, "causal": False}]


def test_rope_relative_shift_invariance():
    """RoPE attention logits depend only on relative positions."""
    hd = 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 8, 2, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 2, hd))

    def logits(offset):
        pos = layers.positions_from_shape(1, 8, offset)
        cos, sin = layers.rope_cos_sin(pos, hd, 10_000.0)
        qr = layers.apply_rope(q, cos, sin)
        kr = layers.apply_rope(k, cos, sin)
        return jnp.einsum("bqhd,bkhd->bhqk", qr, kr)

    np.testing.assert_allclose(np.asarray(logits(0)),
                               np.asarray(logits(1000)), atol=1e-3)


def test_mrope_sections_sum():
    pos3 = jnp.zeros((1, 3, 4), jnp.int32)
    cos, sin = layers.mrope_cos_sin(pos3, 16, 10_000.0, (2, 3, 3))
    assert cos.shape == (1, 4, 8)
    # all-equal position grids must reduce to standard rope
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    pos3 = jnp.broadcast_to(pos[:, None, :], (1, 3, 4))
    c1, s1 = layers.mrope_cos_sin(pos3, 16, 10_000.0, (2, 3, 3))
    c2, s2 = layers.rope_cos_sin(pos, 16, 10_000.0)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)


@pytest.mark.slow
@hypothesis.settings(max_examples=6, deadline=None)
@hypothesis.given(seed=st.integers(0, 100),
                  top_k=st.sampled_from([1, 2, 4]))
def test_moe_dense_equals_ragged(seed, top_k):
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-moe-a2.7b")),
        moe=MoEConfig(num_experts=8, top_k=top_k, d_ff_expert=16,
                      num_shared_experts=1, d_ff_shared=16))
    key = jax.random.PRNGKey(seed)
    p = moe.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, cfg.d_model))
    y1, aux1 = moe.apply_moe(p, x, cfg, impl="dense")
    y2, aux2 = moe.apply_moe(p, x, cfg, impl="ragged")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
    assert abs(float(aux1 - aux2)) < 1e-7


def test_moe_router_aux_penalizes_imbalance():
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-moe-a2.7b")),
        moe=MoEConfig(num_experts=4, top_k=1, d_ff_expert=8,
                      router_aux_coef=1.0))
    key = jax.random.PRNGKey(0)
    p = moe.init_moe(key, cfg)
    x = jax.random.normal(key, (1, 64, cfg.d_model))
    # force total collapse onto expert 0
    p_collapsed = dict(p, router=jnp.zeros_like(p["router"])
                       .at[:, 0].set(10.0))
    _, aux_bal = moe.apply_moe(p, x, cfg)
    _, aux_col = moe.apply_moe(p_collapsed, x, cfg)
    assert float(aux_col) > float(aux_bal)


@pytest.mark.slow
@hypothesis.settings(max_examples=6, deadline=None)
@hypothesis.given(chunk=st.sampled_from([4, 16, 64]),
                  s=st.sampled_from([12, 32, 60]))
def test_mamba_chunk_invariance(chunk, s):
    """The chunked scan result must not depend on the chunk size."""
    cfg = reduced(get_config("falcon-mamba-7b"))
    key = jax.random.PRNGKey(0)
    p = mamba.init_mamba(key, cfg)
    x = jax.random.normal(key, (2, s, cfg.d_model)) * 0.5
    y1, _ = mamba.apply_mamba(p, x, cfg, chunk=chunk)
    y2, _ = mamba.apply_mamba(p, x, cfg, chunk=256)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-4)


def test_mamba_pallas_impl_matches():
    cfg = reduced(get_config("falcon-mamba-7b"))
    key = jax.random.PRNGKey(1)
    p = mamba.init_mamba(key, cfg)
    x = jax.random.normal(key, (1, 32, cfg.d_model)) * 0.5
    y1, _ = mamba.apply_mamba(p, x, cfg, impl="jnp", chunk=16)
    y2, _ = mamba.apply_mamba(p, x, cfg, impl="pallas", chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)


def test_sliding_window_blocks_attend_locally():
    """With window w, a token's output is unchanged by edits > w away."""
    cfg = _attn_cfg()
    key = jax.random.PRNGKey(2)
    p = attention.init_attention(key, cfg)
    s, w = 64, 8
    x = jax.random.normal(key, (1, s, cfg.d_model))
    pos = layers.positions_from_shape(1, s)
    o1, _ = attention.apply_attention(p, x, cfg, positions=pos, causal=True,
                                      window=w, impl="naive")
    x2 = x.at[:, 0].set(x[:, 0] + 10.0)      # far outside last token's window
    o2, _ = attention.apply_attention(p, x2, cfg, positions=pos, causal=True,
                                      window=w, impl="naive")
    np.testing.assert_allclose(np.asarray(o1[:, -1]), np.asarray(o2[:, -1]),
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(o1[:, 0] - o2[:, 0]))) > 1e-3


def test_tokenizers_shapes_and_cls():
    key = jax.random.PRNGKey(0)
    d = 32
    for name in ("vision", "text", "audio"):
        spec = tok.MODALITIES[name]
        p = tok.init_tokenizer(key, spec, d)
        if name == "text":
            x = jax.random.randint(key, (2,) + tuple(spec.input_shape), 0,
                                   spec.vocab_size)
        else:
            shape = tuple(spec.input_shape) + ((3,) if name == "vision"
                                               else ())
            x = jax.random.normal(key, (2,) + shape)
        y = tok.apply_tokenizer(p, x, spec)
        assert y.shape == (2, spec.num_tokens, d)
        assert bool(jnp.isfinite(y).all())
    # paper claim: ViT-B tokenizers are ~1M trainable params (vision+audio);
    # our analytic count should be the same order
    n = tok.tokenizer_param_count(tok.MODALITIES["vision"], 768)
    assert 0.5e6 < n < 2e6


def test_moe_ep_equals_dense_on_mesh():
    """Expert-parallel shard_map dispatch == dense dispatch, on a real
    (data, model) device mesh (the production MoE path)."""
    import os
    import jax as _jax
    if len(_jax.devices()) < 2:
        pytest.skip("needs >1 host device (run via dryrun/roofline paths)")
    from repro.parallel import sharding as sh
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-moe-a2.7b")),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                      num_shared_experts=0))
    key = jax.random.PRNGKey(0)
    p = moe.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    mesh = jax.make_mesh((2, len(_jax.devices()) // 2), ("data", "model"))
    with sh.use_mesh(mesh):
        y1, _ = jax.jit(lambda p, x: moe.apply_moe(p, x, cfg,
                                                   impl="dense"))(p, x)
        y2, _ = jax.jit(lambda p, x: moe.apply_moe(p, x, cfg,
                                                   impl="ep"))(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
