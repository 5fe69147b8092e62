"""The program's named scopes and the readers of the device time under
them: each scope reaches the compiled step's op names, also under the
transforms of the backward pass, without changing the program; the
readers count what a hand-built trace holds under each scope, and the
device's idle time while the host loop sits in its annotations."""
import contextlib
import importlib
import types

import jax
import pytest

from chipbench import scopes, trace as tr
from chipbench.metrics import (attention_scope_ms, host_bound_idle_share,
                               ssm_scan_ms, ssm_scope_ms)
from chipbench.tests import small

TPU = "/device:TPU:0"
VIT_SCOPES = ("client_head", "frozen_trunk", "trainable_trunk", "attention",
              "optimizer")


def _step(workload):
    """(lowered, compiled) step of a reduced cell on one device."""
    from repro.launch import mesh as mesh_lib
    from repro.parallel import sharding
    _, (_, cfg, mix, _) = small.resolved(workload)
    family = importlib.import_module("chipbench.families." + cfg["family"])
    mesh = mesh_lib.make_host_mesh(jax.devices()[:1])
    with sharding.use_mesh(mesh):
        cell = family.build(cfg, mix, 3, mesh)
        lowered = cell.step.lower(cell.state,
                                  sharding.place_batch(cell.pool[0], mesh))
    return lowered, lowered.compile()


@pytest.fixture(scope="module")
def compiled():
    """workload -> (step text without locations, parsed HLO, module name),
    each reduced step compiled once for the tests of this file."""
    done = {}

    def get(workload):
        if workload not in done:
            lowered, exe = _step(workload)
            text = exe.as_text()
            done[workload] = (lowered.as_text(debug_info=False),
                              tr.parse_hlo(text),
                              text.split("\n", 1)[0].split()[1].rstrip(","))
        return done[workload]
    return get


def _op_names(hlo):
    return [opn for op, _, opn in hlo.values() if op not in tr.NESTING_OPS]


@pytest.mark.parametrize("workload,names", [
    ("mtb16_vt_cls", VIT_SCOPES),
    ("hymba_lm_4k", ("client_head", "frozen_trunk", "trainable_trunk",
                     "attention", "ssm", "optimizer"))])
def test_every_scope_reaches_the_compiled_step(compiled, workload, names):
    _, hlo, _ = compiled(workload)
    ops = _op_names(hlo)
    for scope in names:
        assert any(scopes.in_scope(o, scope) for o in ops), scope
    # the backward pass keeps the scope inside the transform's name
    for scope in {"attention", "client_head", "ssm"} & set(names):
        assert any(scopes.in_scope(o, scope) and "transpose(" in o
                   for o in ops), scope


def test_scopes_leave_the_program_unchanged(compiled, monkeypatch):
    with_scopes, _, _ = compiled("mtb16_vt_cls")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without, _ = _step("mtb16_vt_cls")
    assert without.as_text(debug_info=False) == with_scopes


def test_in_scope_matches_whole_components_and_transforms():
    name = ("jit(step)/transpose(jvp(frozen_trunk))/while/body/closed_call/"
            "checkpoint/rematted_computation/attention/dot_general")
    assert scopes.in_scope(name, "frozen_trunk")
    assert scopes.in_scope(name, "attention")
    assert not scopes.in_scope(name, "trainable_trunk")
    assert not scopes.in_scope("jit(step)/attention_mask/sub", "attention")
    assert not scopes.in_scope("jit(step)/jvp()/mul", "attention")
    assert scopes.in_scope("jit(step)/vmap(client_head)/x", "client_head")
    assert scopes.in_scope("optimizer/add", "optimizer")


HLO = {
    "fusion.1": ("fusion", [], "jit(step)/transpose(jvp(frozen_trunk))/while/"
                 "body/closed_call/checkpoint/rematted_computation/attention/"
                 "dot_general"),
    "fusion.2": ("fusion", [], "jit(step)/jvp(frozen_trunk)/while/body/"
                 "closed_call/attention/dot_general"),
    "dot.3": ("dot", [], "jit(step)/jvp(trainable_trunk)/while/body/"
              "closed_call/bsd,df->bsf/dot_general"),
    "conv.4": ("convolution", [], "jit(step)/transpose(jvp(client_head))/"
               "vmap(bnp,pd->bnd)/dot_general"),
    "add.5": ("add", [], "jit(step)/optimizer/add"),
    "while.6": ("while", [], "jit(step)/jvp(frozen_trunk)/while"),
    "mul.7": ("multiply", [], "jit(step)/jvp()/mul"),
}


def _ctx(modules, ops=(), host=()):
    trace = tr.DeviceTrace({TPU: {"modules": list(modules),
                                  "ops": list(ops)}}, host)
    return types.SimpleNamespace(trace=trace, hlo=HLO, step_module="jit_step")


def test_scope_time_is_leaf_self_time_per_step():
    ops = [("fusion.1", 0, 30), ("fusion.2", 30, 10), ("dot.3", 40, 20),
           ("conv.4", 60, 4), ("add.5", 64, 6), ("while.6", 0, 70),
           ("mul.7", 70, 8), ("fusion.1", 100, 30), ("other.9", 130, 50)]
    ctx = _ctx([("jit_step(1)", 0, 90), ("jit_step(1)", 100, 190)], ops)
    per_step = {s: scopes.scope_ms(ctx, s) for s in VIT_SCOPES}
    # two executions of the step; the while op holds the others
    assert per_step["attention"] == pytest.approx(1e3 * 70e-9 / 2)
    assert per_step["frozen_trunk"] == pytest.approx(1e3 * 70e-9 / 2)
    assert per_step["trainable_trunk"] == pytest.approx(1e3 * 20e-9 / 2)
    assert per_step["client_head"] == pytest.approx(1e3 * 4e-9 / 2)
    assert per_step["optimizer"] == pytest.approx(1e3 * 6e-9 / 2)
    assert attention_scope_ms.read(ctx) == per_step["attention"]
    # a program without the scope (or without a trace) reads nothing
    assert scopes.scope_ms(ctx, "ssm") is None
    assert ssm_scope_ms.read(types.SimpleNamespace(trace=None, hlo=None)) \
        is None


def test_host_bound_idle_is_idle_time_inside_host_loop_annotations():
    modules = [("m", 0, 100), ("m", 130, 200), ("m", 400, 500)]
    host = [("step/dispatch#step=3#", 90, 140), ("step/get_batch", 190, 300),
            ("host/assemble", 300, 400), ("metrics/readback", 450, 460),
            ("train", 0, 500)]
    ctx = _ctx(modules, host=host)
    # gaps 100-130 and 200-400; the loop sits in 90-140 and 190-300
    assert scopes.host_bound_idle(ctx.trace) == (pytest.approx(130e-9),
                                                 pytest.approx(500e-9))
    share = host_bound_idle_share.read(ctx)
    assert share == pytest.approx(26.0)
    assert share <= 100.0 * (1 - ctx.trace.busy_s() / ctx.trace.window_s())
    # a trace whose host loop has no annotations reads nothing
    assert host_bound_idle_share.read(_ctx(modules, host=host[4:])) is None


def test_ssm_scope_counts_what_the_file_stack_misses(compiled):
    """On the reduced hymba step the `ssm` scope holds more ops than the
    stacks through the Mamba mixer's source file: the backward's too."""
    _, hlo, module = compiled("hymba_lm_4k")
    leaves = [n for n, (op, _, _) in hlo.items() if op not in tr.NESTING_OPS]
    ops = [(n, 10 * i, 1) for i, n in enumerate(leaves)]
    ctx = types.SimpleNamespace(
        trace=tr.DeviceTrace({TPU: {"modules": [(module, 0, 10 * len(ops))],
                                    "ops": ops}}),
        hlo=hlo, step_module=module)
    in_ssm = sum(1 for n in leaves if scopes.in_scope(hlo[n][2], "ssm"))
    assert ssm_scope_ms.read(ctx) == pytest.approx(1e3 * in_ssm * 1e-9)
    assert ssm_scope_ms.read(ctx) > ssm_scan_ms.read(ctx)
