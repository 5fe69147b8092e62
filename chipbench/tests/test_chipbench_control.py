"""At a reduced size on the CPU, under the reduced cell's own limits: the
sound bf16 program reads correct, and the control and faults planted in
the timed path read not correct, each by the margin the limits are set
with (the control three times the sound reading, a fault ten times, a
state left unchanged three times, on some number that fails its limit).

The control is the reference computed with fp8 matmul operands, one step
below the bf16 the configurations state; the faults are a step that
returns its state unchanged and a loss over half of each client's rows."""
import functools
import importlib

import pytest

from chipbench import compare, faults, generate, harness
from chipbench.reference import common
from chipbench.tests import small

SEED = 23
FACTOR = {"control": 3.0, "half_batch": 10.0, "state_unchanged": 3.0}


@functools.lru_cache(maxsize=None)
def sound(workload):
    """(result, readings) of the sound program at SEED."""
    bench, resolved = small.resolved(workload)
    r = harness.run_cell(workload, SEED, 0.1, False, require=False,
                         bench=bench, resolved=resolved)
    return r, {k: c["value"] for k, c in r["checks"].items()}


def assert_caught(workload, readings, factor):
    limits = small.LIMITS[workload]
    base = sound(workload)[1]
    correct, checks = compare.judge(readings, limits)
    assert not correct, checks
    caught = [k for k in limits if readings[k] > limits[k]
              and readings[k] >= factor * base[k]]
    assert caught, (readings, base)


@pytest.mark.parametrize("workload", sorted(small.CELLS))
def test_sound_run_reads_correct_under_the_same_limits(workload):
    r, readings = sound(workload)
    assert r["correct"] is True, r["checks"]
    assert all(v > 0 for v in readings.values()), readings


@pytest.mark.parametrize("workload", sorted(small.CELLS))
def test_control_fails_the_cells_limits(workload):
    _, (_, cfg, mix, _) = small.resolved(workload)
    pool = generate.make_pool(cfg, mix, SEED)[:mix["check_steps"]]
    ref = importlib.import_module("chipbench.reference." + cfg["family"])
    key = common.seed_key(SEED)
    f32 = ref.readings(cfg, mix, key, pool)[0]
    fp8 = ref.readings(cfg, mix, key, pool, "fp8")[0]
    assert_caught(workload, compare.gaps(fp8, f32), FACTOR["control"])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", sorted(small.CELLS))
def test_planted_fault_reads_not_correct(workload, fault):
    bench, resolved = small.resolved(workload)
    r = harness.run_cell(workload, SEED, 0.1, False, require=False,
                         bench=bench, resolved=resolved,
                         fault=faults.FAULTS[fault])
    assert r["correct"] is False, r["checks"]
    assert_caught(workload, {k: c["value"] for k, c in r["checks"].items()},
                  FACTOR[fault])
