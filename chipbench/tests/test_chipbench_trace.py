"""The trace reduction: idle share from the union of module intervals,
steps of the timed module, leaf self time, and the join of ops to source
files through the HLO's stack frames."""
import importlib
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as tr

TPU = "/device:TPU:0"


def _trace(modules, ops=(), host=()):
    return tr.DeviceTrace({TPU: {"modules": list(modules), "ops": list(ops)}},
                          host)


def test_busy_is_the_union_of_overlapping_modules():
    t = _trace([("jit_step(1)", 0, 100), ("jit_step(2)", 150, 250),
                ("jit_other(3)", 240, 260), ("jit_other(4)", 10, 20)])
    assert t.window_s() == pytest.approx(260e-9)
    assert t.busy_s() == pytest.approx(210e-9)     # 0-100, 150-260


def test_busy_and_window_average_over_chips():
    t = tr.DeviceTrace({TPU: {"modules": [("m", 0, 100)], "ops": []},
                        "/device:TPU:1": {"modules": [("m", 0, 50),
                                                      ("m", 150, 200)],
                                          "ops": []}})
    assert t.window_s() == pytest.approx(150e-9)   # (100 + 200) / 2
    assert t.busy_s() == pytest.approx(100e-9)     # (100 + 100) / 2


def test_a_trace_without_modules_is_refused():
    with pytest.raises(RuntimeError):
        tr.DeviceTrace({TPU: {"modules": [], "ops": []}})


def test_step_runs_count_the_timed_module_only():
    t = _trace([("jit_step(7)", 0, 100), ("jit_step(7)", 120, 230),
                ("jit_stepper(1)", 240, 300), ("jit__delta(2)", 300, 310)])
    assert t.step_runs("jit_step") == (2, pytest.approx(230e-9))
    assert t.step_runs("jit_nothing") == (0, 0.0)


def test_idle_gaps_are_named_by_the_most_specific_host_event():
    t = _trace([("m", 0, 100), ("m", 130, 200), ("m", 400, 500)],
               host=[("run", 0, 500), ("PjitFunction(step)", 90, 140),
                     ("batch", 190, 380), ("tail", 395, 420)])
    gaps = t.idle_gaps()
    # "run" covers both gaps too, but a shorter event covers each
    assert gaps[0] == ["batch", pytest.approx(200e-9)]
    assert gaps[1] == ["PjitFunction(step)", pytest.approx(30e-9)]
    t = _trace([("m", 0, 100), ("m", 200, 300)], host=[("late", 180, 250)])
    assert t.idle_gaps() == [["late", pytest.approx(100e-9)]]


HLO = textwrap.dedent('''\
    HloModule jit_step, is_scheduled=true

    FileNames
    1 "/x/main.py"
    2 "/x/src/repro/models/attention.py"
    3 "/x/src/repro/models/layers.py"

    FunctionNames
    1 "f"

    FileLocations
    1 {file_name_id=1 function_name_id=1 line=5 end_line=5 column=1 end_column=2}
    2 {file_name_id=2 function_name_id=1 line=9 end_line=9 column=1 end_column=2}
    3 {file_name_id=3 function_name_id=1 line=3 end_line=3 column=1 end_column=2}

    StackFrames
    1 {file_location_id=1 parent_frame_id=1}
    2 {file_location_id=2 parent_frame_id=2}
    3 {file_location_id=3 parent_frame_id=3}

    ENTRY %main (p: f32[8]) -> f32[8] {
      %p = f32[8]{0} parameter(0)
      %rope.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/mul" stack_frame_id=3}
      %dot.2 = f32[8]{0} dot(%rope.1, %p), metadata={op_name="jit(step)/dot" stack_frame_id=2}
      %while.3 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/while" stack_frame_id=2}
      ROOT %add.4 = f32[8]{0} add(%dot.2, %p), metadata={op_name="jit(step)/add" stack_frame_id=1}
    }
    ''')


def test_parse_hlo_joins_each_instruction_to_its_python_stack():
    hlo = tr.parse_hlo(HLO)
    # a frame's printed parent is the parent's id plus one
    assert hlo["rope.1"] == ("fusion", ["/x/src/repro/models/layers.py",
                                        "/x/src/repro/models/attention.py",
                                        "/x/main.py"], "jit(step)/mul")
    assert hlo["dot.2"][:2] == ("dot", ["/x/src/repro/models/attention.py",
                                        "/x/main.py"])
    assert hlo["while.3"][0] == "while"
    assert hlo["add.4"][:2] == ("add", ["/x/main.py"])


def test_self_time_counts_leaf_ops_of_a_file_once():
    hlo = tr.parse_hlo(HLO)
    ops = [("rope.1", 0, 10), ("dot.2", 10, 20), ("while.3", 0, 100),
           ("add.4", 30, 5), ("%dot.2 = f32[8] dot(...)", 40, 20),
           ("unknown.9", 50, 1000)]
    t = _trace([("jit_step(1)", 0, 100)], ops)
    # the while op contains the others; an op of another module is unknown;
    # rope.1 runs in layers.py, called from attention.py
    assert t.self_time_in_file(hlo, "repro/models/attention.py") == \
        pytest.approx(50e-9)
    assert t.self_time_in_file(hlo, "repro/models/layers.py") == \
        pytest.approx(10e-9)
    assert t.self_time_in_file(hlo, "repro/models/mamba.py") == 0
    assert t.top_ops(hlo, 1) == [["dot.2 jit(step)/dot [attention.py]",
                                  pytest.approx(40e-9)]]


def test_parse_hlo_reads_a_compiled_program(tmp_path, monkeypatch):
    """The join works on what the compiler prints: ops of a function in one
    file, called from another, carry both files on their stack."""
    (tmp_path / "inner_ops.py").write_text(
        "import jax.numpy as jnp\n"
        "def inner(x):\n    return jnp.sin(x) * 3.0\n")
    (tmp_path / "outer_ops.py").write_text(
        "import jax.numpy as jnp\nfrom inner_ops import inner\n"
        "def outer(x, w):\n    return jnp.tanh(inner(x) @ w)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    outer = importlib.import_module("outer_ops").outer
    text = jax.jit(outer).lower(jnp.ones((8, 8)),
                                jnp.ones((8, 8))).compile().as_text()
    hlo = tr.parse_hlo(text)
    files = {f for _, chain, _ in hlo.values() for f in chain}
    assert any(f.endswith("inner_ops.py") for f in files)
    assert any(f.endswith("outer_ops.py") for f in files)
    inner = [n for n, (_, chain, _) in hlo.items()
             if chain and chain[0].endswith("inner_ops.py")]
    assert inner and all(any(f.endswith("outer_ops.py") for f in hlo[n][1])
                         for n in inner)
    sys.modules.pop("outer_ops", None)
    sys.modules.pop("inner_ops", None)
