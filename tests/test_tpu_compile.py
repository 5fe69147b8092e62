"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode (every other kernel test) cannot see the TPU's tiling and
VMEM rules, so each kernel is compiled here, forward and backward, for a
described (not attached) v5e chip: head_dim 128 and 64 at 4096 tokens,
hymba-1.5b's 4224 positions with its visible meta prefix, and the
ViT-B/16 trunk's 274, vocab 256000 and 32001, d_inner 8192 and
hymba-1.5b's 3200 at 4224 positions.
Nothing runs. The topology is described inside a
fixture, never at import, and the persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import losses
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import quant8 as q8
from repro.kernels import selective_scan as ss


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (heads, kv heads, head_dim, window, batch, seq, causal, prefix):
# minitron-4b global layers and hymba-1.5b's windowed layers at 4096
# tokens with a key-validity mask; the ViT-B/16 trunk of the
# meta-transformer-b16 cell (8 clients x 32 samples of 274 tokens), with
# no mask at all; hymba-1.5b as published (4 clients, 4096 tokens and 128
# meta tokens, which its windowed layers keep in view), as the model
# passes it: no key-validity mask, so only the grid's padding masks keys
FLASH = {"hd128": (32, 8, 128, 0, 1, 4096, True, 0),
         "hd64": (25, 5, 64, 1024, 1, 4096, True, 0),
         "vit_b16": (12, 12, 64, 0, 256, 274, False, 0),
         "hymba_published": (25, 5, 64, 1024, 4, 4224, True, 128)}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(FLASH))
def test_flash_attention_compiles_for_v5e(one_chip, case, direction):
    h, kh, hd, window, b, s, causal, prefix = FLASH[case]
    q, kv = ((b, s, h, hd), BF), ((b, s, kh, hd), BF)
    pos = ((b, s), I32)
    masks = [((b, s), jnp.bool_)] if causal and not prefix else []

    def valid(m):
        return m[0] if m else None
    if direction == "fwd":
        _compile(lambda q, k, v, p, *m: fa.flash_attention_fwd(
            q, k, v, p, p, causal=causal, window=window, k_valid=valid(m),
            prefix=prefix, return_lse=True), one_chip, q, kv, kv, pos,
            *masks)
    else:
        _compile(lambda q, k, v, p, o, lse, do, *m: fa.flash_attention_bwd(
            q, k, v, p, p, valid(m), o, lse, do, causal=causal,
            window=window, prefix=prefix),
            one_chip, q, kv, kv, pos, q, ((b, h, s), F32), q, *masks)


# (d_model, vocab): minitron-4b's 256000-wide head, hymba-1.5b's 32001
XENT = {"vocab256000": (3072, 256_000), "vocab32001": (1600, 32_001)}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(XENT))
def test_softmax_xent_compiles_for_v5e(one_chip, monkeypatch, case,
                                       direction):
    """Through the train step's own call: 4 clients x 4095 next-token
    positions of bf16 hidden states against the f32 trainable head."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    d, v = XENT[case]
    t = 4 * 4095

    def loss(h, w, lab):
        return jnp.sum(losses.chunked_softmax_xent(h, w, lab, impl="pallas"))

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1))
    _compile(fn, one_chip, ((t, d), BF), ((d, v), F32), ((t,), I32))


# (batch, positions, d_inner): falcon-mamba-7b's d_inner 8192, and the
# hymba_full_lm_4k cell's scans (4 clients, 128 meta + 4096 text
# positions, d_inner 3200), each with the blocks `fit_blocks` chooses
SCAN = {"d_inner8192": (1, 2048, 8192), "hymba_full": (4, 4224, 3200)}


@pytest.mark.parametrize("case,direction", [
    pytest.param(c, d, id=d if c == "d_inner8192" else f"{c}-{d}")
    for c in sorted(SCAN) for d in ("fwd", "bwd")])
def test_selective_scan_compiles_for_v5e(one_chip, case, direction):
    b, s, di = SCAN[case]
    ds = 16
    chunk, block_d = ss.fit_blocks(s, di)
    x, bc, a = ((b, s, di), BF), ((b, s, ds), BF), ((di, ds), F32)
    if direction == "fwd":
        _compile(lambda x, dt, b_, c, a: ss.selective_scan_fwd(
            x, dt, b_, c, a, chunk=chunk, block_d=block_d,
            return_ckpt=True), one_chip, x, x, bc, bc, a)
    else:
        ckpt = ((b, s // chunk, ds, di), F32)
        _compile(lambda x, dt, b_, c, a, hk, gy, gh: ss.selective_scan_bwd(
            x, dt, b_, c, a, hk, gy, gh, chunk=chunk, block_d=block_d),
            one_chip, x, x, bc, bc, a, ckpt, x, ((b, di, ds), F32))


@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_quant8_compiles_for_v5e(one_chip, rounding):
    # hymba-1.5b's uplink: [clients, batch, seq, d_model]
    x = ((4, 1, 4096, 1600), BF)
    if rounding == "nearest":
        _compile(lambda x: q8.quant_dequant_fwd(x), one_chip, x)
    else:
        _compile(lambda x, k: q8.quant_dequant_fwd(x, key=k),
                 one_chip, x, ((2,), jnp.uint32))
