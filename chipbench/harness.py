"""One run of one benchmark cell: set-up, the measured window, the readers
of its metrics, and the comparison with the plain reference.

Everything a cell needs is found by name from `BENCHMARK.json`:
  configs/<config>.json    the model as run (sizes, dtypes, optimizer)
  traffic/<mix>.json       the mix (clients, batch, lengths, pool)
  families/<family>.py     builds the program's step and state
  reference/<family>.py    the plain float32 reference
  limits/<workload>.json   the limits of the numbers compared
  metrics/<metric>.py      one reader per metric (the part of a metric's
                           name before the first '.')

Set-up: the state from the seed, the pool of batches, and the first
`check_steps` steps through the program's Trainer and prefetcher, which
compile the step and give the readings the reference is compared with.
The window then runs n = max(1, floor(seconds / warm step)) steps through
the same Trainer, timed from the first dispatch to the last step's
outputs being ready.
"""
from __future__ import annotations

import argparse
import functools
import gc
import importlib
import json
import math
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
OUT = os.path.join(HERE, "out")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(bench, workload):
    """(workload entry, config, traffic mix, limits) by name."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(w['name'] for w in bench['workloads'])}")
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg = load_json(ROOT, conf["file"])
    mix = load_json(HERE, "traffic", wl["traffic"] + ".json")
    limits = load_json(HERE, "limits", workload + ".json")["limits"]
    return wl, cfg, mix, limits


def cell_metrics(bench, workload, trace):
    """The metric entries this cell reports: end-to-end ones untraced,
    per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def reader(metric_name):
    return importlib.import_module(
        "chipbench.metrics." + metric_name.split(".")[0]).read


def fail(msg):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def require_chip(count):
    """The devices of a TPU run: JAX must see TPUs, at least `count`, and
    the Pallas kernels must run compiled, never interpreted."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < count:
        fail(f"the cell needs {count} TPU chips, JAX found {len(devs)}")
    from repro.kernels import ops
    if ops.INTERPRET:
        fail("Pallas kernels would run in interpret mode")
    return devs[:count]


class CompileClock:
    """Programs compiled and loaded from the persistent cache, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1
        elif name == "/jax/compilation_cache/compile_time_saved_sec":
            self.hits += 1

    def take(self):
        out = {"compile_s": self.secs, "programs": self.programs,
               "from_cache": self.hits}
        self.secs, self.programs, self.hits = 0.0, 0, 0
        return out


def note(**fields):
    """An earlier line of the run's output: a fact that is not a metric."""
    print("chipbench " + json.dumps(fields), flush=True)


def _spans(path):
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "span":
                out.append(rec)
    return out


def run_cell(workload, seed, seconds, trace, *, require=True, fault=None,
             t_start=None, bench=None, resolved=None, measure=True,
             keep=None):
    """One run of a cell; returns the result object the command prints.

    For CPU tests at a reduced size: `require=False` skips the look for a
    chip, `bench` and `resolved` (workload, config, mix, limits) stand in
    for the files, and `fault` ({"loss": wrapper, "step": wrapper}) plants
    a fault in the timed path. For calibration: `measure=False` skips the
    window, and `keep` (a dict) receives the raw program and reference
    readings."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    wl, cfg, mix, limits = resolved or resolve(bench, workload)
    import jax
    devs = require_chip(wl["chips"]) if require else \
        jax.devices()[:wl["chips"]]
    if require:
        from repro.launch.compile_cache import enable_compilation_cache
        note(compile_cache=enable_compilation_cache())
        # every program, however quick to compile, goes to the cache, so
        # that a second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()

    from repro import obs
    from repro.data import PrefetchLoader
    from repro.launch import mesh as mesh_lib
    from repro.parallel import sharding
    from repro.train import Trainer, TrainerConfig
    from chipbench import compare, flops, program, trace as tr
    from chipbench.reference import common

    os.makedirs(OUT, exist_ok=True)
    span_log = os.path.join(OUT, f"spans-{workload}.jsonl")
    trace_dir = os.path.join(OUT, f"trace-{workload}")
    for p in (span_log, trace_dir):
        shutil.rmtree(p, ignore_errors=True) if os.path.isdir(p) else \
            (os.path.exists(p) and os.remove(p))
    if trace:
        obs.configure(span_log, meta={"workload": workload, "seed": seed})

    family = importlib.import_module("chipbench.families." + cfg["family"])
    k = mix["check_steps"]
    mesh = mesh_lib.make_host_mesh(devs)
    with sharding.use_mesh(mesh):
        cell = family.build(cfg, mix, seed, mesh, **(fault or {}))
        loader = PrefetchLoader(
            program.PoolLoader(cell.pool), depth=2,
            place_fn=functools.partial(sharding.place_batch, mesh=mesh))
        trainer = Trainer(cell.step, cell.state, loader,
                          TrainerConfig(total_steps=k, ckpt_dir=None,
                                        log_every=1 << 30,
                                        metrics_ring=1 << 16),
                          log_fn=lambda s: None)
        cell.state = None
        try:
            # the first steps: compile, warm up, and the readings that the
            # reference checks
            trainer.run(1)
            prog = {"grad": common.grad_norms_from_moment(
                trainer.state["opt"]["mu"], cfg["optimizer"]["b1"])}
            t = time.perf_counter()
            trainer.run(k)
            jax.block_until_ready(trainer.state)
            warm_s = (time.perf_counter() - t) / max(1, k - 1)
            prog["losses"] = [float(m["loss"]) for _, m in
                              trainer.ring.entries_after(0)][:k]
            params_k = jax.device_get(trainer.state["params"])
            span_s = min(seconds, mix["trace_seconds"]) if trace else seconds
            n = max(1, int(span_s // warm_s))
            compiled = None
            if trace:
                compiled = cell.step.lower(
                    trainer.state,
                    sharding.place_batch(cell.pool[0], mesh)).compile()
            setup = clock.take()
            note(setup_compiles=setup, warm_step_s=warm_s, window_steps=n)

            setup_s = time.perf_counter() - t_start
            if trace:
                jax.profiler.start_trace(trace_dir)
            t = time.perf_counter()
            if measure:
                trainer.run(k + n)
                jax.block_until_ready(trainer.state)
            window_s = time.perf_counter() - t
            if trace:
                jax.profiler.stop_trace()
            in_window = clock.take()
            window_losses = [float(m["loss"]) for _, m in
                             trainer.ring.entries_after(k)]
            peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                        0)) for d in devs)
            note(window_compiles=in_window, window_s=window_s,
                 step_spacing_s=window_s / n, peak_bytes_in_use=peak,
                 link_bytes_per_step=obs.comm.per_step_wire_bytes())
        finally:
            loader.close()
            trainer.state = None
            del trainer
            gc.collect()
    if trace:
        obs.shutdown()

    ctx = types.SimpleNamespace(
        workload=workload, cfg=cfg, mix=mix, counts=cell.counts,
        setup_s=setup_s, window_s=window_s, steps=n, first_window_step=k,
        flops_per_step=flops.step_flops(cfg, mix), chips=len(devs),
        device_kind=devs[0].device_kind, compiled=compiled, trace=None,
        hlo=None, spans=None, step_module=None)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        ctx.spans = _spans(span_log)
        ctx.hlo = tr.parse_hlo(compiled.as_text())
        ctx.step_module = compiled.as_text().split("\n", 1)[0].split()[1] \
            .rstrip(",")
        ctx.trace = tr.DeviceTrace.from_file(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s()
        breakdown = {"device_ops": ctx.trace.top_ops(ctx.hlo),
                     "idle_gaps": ctx.trace.idle_gaps()}

    metrics = {}
    for m in cell_metrics(bench, workload, trace) if measure else ():
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the reference, once the program's state is freed
    t = time.perf_counter()
    ref, p0 = cell.reference.readings(cfg, mix, common.seed_key(seed),
                                      cell.pool[:k])
    prog["delta"] = common.delta_norms(params_k, p0)
    del p0, params_k
    readings = compare.gaps(prog, ref)
    correct, checks = compare.judge(readings, limits)
    note(reference_s=time.perf_counter() - t, program_losses=prog["losses"],
         reference_losses=ref["losses"])
    if keep is not None:
        keep.update(program=prog, reference=ref, pool=cell.pool[:k],
                    cfg=cfg, mix=mix)
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    result = {"correct": bool(correct and failed == 0
                              and (window_losses or not measure)),
              "attempted": len(window_losses), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None, t_start=None):
    p = argparse.ArgumentParser(description="one run of one benchmark cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      t_start=t_start)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
