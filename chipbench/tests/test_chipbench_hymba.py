"""The `hymba_full_lm_4k` cell's own pieces at a reduced size on the CPU:
the reference's layer plan against the program's, the two controls that
leave out one published mechanism each, the model FLOPs and the scan's
bytes by hand, and the scan's roofline reader. The program against the
reference, the fp8 control and the planted faults run for this cell as
for every reduced cell (`small_hymba` adds it to `small.CELLS`)."""
import importlib
import types

import pytest

from chipbench import compare, flops, generate, hymba_flops, ssm_scan_bytes
from chipbench import trace as tr
from chipbench.metrics import ssm_scan_roofline
from chipbench.reference import common, hymba
from chipbench.tests import small, small_hymba

W = small_hymba.WORKLOAD
TPU = "/device:TPU:0"


def test_reduced_cell_has_every_kind_of_layer_and_both_mechanisms():
    from repro.core import split
    from repro.models import model as M
    cfg, mix = small_hymba.hymba_cell()
    family = importlib.import_module("chipbench.families.hymba")
    model, _ = family.run_config(cfg, mix)
    assert model.meta_tokens == 8 and model.kv_share_groups == ((2, 3),)
    fsegs, tsegs = split.split_segments(M.body_segments(model), 2)

    def kinds(segs):
        return [("pair" if s.kind.kv_pair else "global" if s.kind.is_global
                 else "local", s.steps) for s in segs]
    assert (kinds(fsegs), kinds(tsegs)) == hymba.segment_runs(cfg)
    assert kinds(fsegs) == [("global", 1), ("local", 1)]
    assert kinds(tsegs) == [("pair", 1), ("global", 1)]
    # the meta keys slide out of the 16-position window
    assert cfg["sliding_window"] < mix["seq_len"]


@pytest.mark.parametrize("left_out", ["meta", "share_kv"])
def test_reference_without_a_mechanism_reads_not_correct(left_out):
    """Held to the reduced cell's limits, the reference with no meta
    tokens, or with each layer of a pair on K/V of its own input, reads
    not correct against the reference as published."""
    cfg, mix = small_hymba.hymba_cell()
    seed = 23
    pool = generate.make_pool(cfg, mix, seed)[:mix["check_steps"]]
    key = common.seed_key(seed)
    full = hymba.readings(cfg, mix, key, pool)[0]
    ablated = hymba.readings(cfg, mix, key, pool, **{left_out: False})[0]
    correct, checks = compare.judge(compare.gaps(ablated, full),
                                    small.LIMITS[W])
    assert not correct, checks


def test_mean_keys_with_a_visible_prefix_by_hand():
    assert hymba_flops.mean_keys(4) == pytest.approx(2.5)
    # window 2: positions 0..5 see 1, 2, 2, 2, 2, 2 keys; with 2 meta keys
    # position 2 also sees key 0, positions 3.. keys 0 and 1
    assert hymba_flops.mean_keys(6, 2) == pytest.approx(11 / 6)
    assert hymba_flops.mean_keys(6, 2, 2) == pytest.approx((11 + 1 + 2 * 3)
                                                           / 6)


def test_hymba_step_flops_by_hand():
    cfg = {"family": "hymba", "d_model": 4, "num_heads": 2,
           "num_kv_heads": 1, "head_dim": 2, "d_ff": 6, "vocab_size": 7,
           "num_layers": 3, "global_layers": [0], "sliding_window": 2,
           "meta_tokens": 2, "kv_share_groups": [[1, 2]],
           "ssm": {"expand": 2, "d_state": 2, "dt_rank": 1},
           "mpsl": {"trainable_blocks": 1, "head_adapter_rank": 2}}
    mix = {"n_clients": 2, "batch_per_client": 1, "seq_len": 4}
    importlib.import_module("chipbench.families.hymba")
    q_o, k_v = 2 * 2 * 4 * 4, 2 * 2 * 4 * 2
    mamba = 2 * (4 * 16 + 8 * 5 + 1 * 8 + 8 * 4)
    mlp = 2 * 3 * 4 * 6
    sp = 6                                   # 2 meta + 4 text positions
    glob = q_o + k_v + mamba + mlp + 4 * 3.5 * 4      # layer 0, frozen
    local = 4 * (11 + 1 + 2 * 3) / 6 * 4
    first = q_o + k_v + mamba + mlp + local           # layer 1, frozen
    second = q_o + mamba + mlp + local                # layer 2, trained
    body = 2 * glob + 2 * first + 3 * second
    adapter = 3 * 2 * 2 * 4 * 2
    head = 3 * 2 * 4 * 7
    assert flops.step_flops(cfg, mix) == pytest.approx(
        2 * (sp * body + 4 * adapter + 3 * head))


def test_full_size_flops_and_scan_bytes():
    cfg = small.load("configs", "hymba-1.5b-published.json")
    mix = small.load("traffic", "lm_4k.json")
    assert 1.15e14 < hymba_flops.step_flops(cfg, mix) < 1.25e14
    fwd, bwd = ssm_scan_bytes.layer_bytes(4, 4224, 3200, 16)
    seq, state = 4 * 4224 * 3200, 4 * 4224 * 16
    ckpt = 4 * 17 * 16 * 3200 * 4                    # ceil(4224 / 256)
    a = 3200 * 16 * 4
    assert fwd == 2 * (2 * seq + 2 * state) + a + 2 * seq + ckpt
    assert bwd == 2 * (3 * seq + 2 * state) + 2 * a + ckpt + 2 * 2 * seq \
        + 2 * 4 * state
    assert ssm_scan_bytes.step_bytes(cfg, mix) == 32 * (2 * fwd + bwd)


def test_scan_roofline_reads_the_ssm_scan_scope():
    hlo = {"fusion.1": ("fusion", [], "jit(step)/jvp(frozen_trunk)/while/"
                        "body/ssm/ssm_scan/custom-call"),
           "fusion.2": ("fusion", [], "jit(step)/transpose(jvp(ssm_scan))/"
                        "custom-call"),
           "dot.3": ("dot", [], "jit(step)/jvp(ssm)/dot_general")}
    ops = [("fusion.1", 0, 1000), ("fusion.2", 1000, 3000),
           ("dot.3", 4000, 500)]
    cfg = small.load("configs", "hymba-1.5b-published.json")
    ctx = types.SimpleNamespace(
        trace=tr.DeviceTrace({TPU: {"modules": [("jit_step(1)", 0, 5000)],
                                    "ops": ops}}),
        hlo=hlo, step_module="jit_step", cfg=cfg,
        mix=small.load("traffic", "lm_4k.json"), chips=1,
        device_kind="TPU v5 lite")
    bytes_ = ssm_scan_bytes.step_bytes(ctx.cfg, ctx.mix)
    assert ssm_scan_roofline.read(ctx) == pytest.approx(
        100 * bytes_ / 4000e-9 / 819e9)
    # a program without the scope reads nothing
    ctx.hlo = {"dot.3": hlo["dot.3"]}
    assert ssm_scan_roofline.read(ctx) is None


def test_ssm_scan_scope_reaches_the_step_and_changes_nothing(monkeypatch):
    """The `ssm_scan` scope is in the reduced step's op names, forward and
    backward, and the step lowers to the same program without it."""
    import contextlib
    import jax
    from chipbench import scopes
    from repro.launch import mesh as mesh_lib
    from repro.parallel import sharding
    _, (_, cfg, mix, _) = small.resolved(W)
    family = importlib.import_module("chipbench.families.hymba")
    mesh = mesh_lib.make_host_mesh(jax.devices()[:1])

    def lowered():
        with sharding.use_mesh(mesh):
            cell = family.build(cfg, mix, 3, mesh)
            return cell.step.lower(cell.state, sharding.place_batch(
                cell.pool[0], mesh))
    low = lowered()
    hlo = tr.parse_hlo(low.compile().as_text())
    ops = [o for op, _, o in hlo.values() if op not in tr.NESTING_OPS]
    assert any(scopes.in_scope(o, "ssm_scan") for o in ops)
    assert any(scopes.in_scope(o, "ssm_scan") and "transpose(" in o
               for o in ops)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lowered().as_text(debug_info=False) == \
        low.as_text(debug_info=False)
