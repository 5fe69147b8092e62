"""The plain float32 references against the program's own step at a
reduced size, with the program also in float32: each step's loss, and the
norms of every client and server leaf's first gradient and of its change,
agree to float32 rounding."""
import pytest

from chipbench import harness
from chipbench.tests import small

TIGHT = {"loss_gap": 1e-5, "grad_gap": 2e-4, "update_gap": 2e-4}


@pytest.mark.parametrize("workload", sorted(small.CELLS))
def test_reference_matches_the_program_in_float32(workload):
    bench, (wl, cfg, mix, _) = small.resolved(workload)
    cfg.update(compute_dtype="float32", frozen_dtype="float32")
    keep = {}
    r = harness.run_cell(workload, 11, 0.1, False, require=False,
                         bench=bench, resolved=(wl, cfg, mix, TIGHT),
                         keep=keep)
    assert r["correct"], r["checks"]
    grads = keep["reference"]["grad"]
    assert any("client" in k for k in grads) and \
        any("server" in k for k in grads)
    # every leaf moved, and by the program as by the reference
    assert all(v > 0 for v in keep["reference"]["delta"].values())
    assert set(keep["program"]["delta"]) == set(keep["reference"]["delta"])
