"""Every cell of BENCHMARK.json resolves its files by name, and its step
builds and runs at a reduced size; the file keeps to the benchmark's
contract in the parts that a test can check."""
import importlib
import re

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import small

BENCH = small.load("..", "BENCHMARK.json")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_file_keeps_to_its_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", WORKLOADS):
            assert w in e2e[m["moves"]].get("workloads", WORKLOADS)
    for w in WORKLOADS:
        reported = [m for m in BENCH["end_to_end"]
                    if w in m.get("workloads", [w])]
        assert len(reported) >= 2
        assert harness.cell_metrics(BENCH, w, True)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_by_name(workload):
    wl, cfg, mix, limits = harness.resolve(BENCH, workload)
    conf = {c["name"]: c for c in BENCH["configs"]}[wl["config"]]
    assert conf["file"].startswith("chipbench/configs/")
    assert conf["reduced"] == cfg["reduced"]
    assert set(limits) == {"loss_gap", "grad_gap", "update_gap"}
    importlib.import_module("chipbench.families." + cfg["family"])
    importlib.import_module("chipbench.reference." + cfg["family"])
    for m in harness.cell_metrics(BENCH, workload, False) + \
            harness.cell_metrics(BENCH, workload, True):
        assert callable(harness.reader(m["name"]))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_builds_and_steps_at_a_reduced_size(workload):
    from repro.launch import mesh as mesh_lib
    from repro.parallel import sharding
    _, (wl, cfg, mix, _) = small.resolved(workload)
    family = importlib.import_module("chipbench.families." + cfg["family"])
    mesh = mesh_lib.make_host_mesh(jax.devices()[:1])
    with sharding.use_mesh(mesh):
        cell = family.build(cfg, mix, 3, mesh)
        assert len(cell.pool) == mix["pool_batches"]
        state, metrics = cell.step(cell.state,
                                   sharding.place_batch(cell.pool[0], mesh))
    assert np.isfinite(float(metrics["loss"]))
    assert int(state["step"]) == 1


def test_pool_is_made_from_the_seed():
    from chipbench import generate
    cfg, mix = small.vit_cell()
    a, b = generate.make_pool(cfg, mix, 2 ** 31 + 5), \
        generate.make_pool(cfg, mix, 2 ** 31 + 5)
    c = generate.make_pool(cfg, mix, 5)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x)
    assert not np.array_equal(a[0]["vision"], c[0]["vision"])
    # the batches of a pool all differ
    assert not np.array_equal(a[0]["text"], a[1]["text"])
