"""Observability-layer tests: recorder/report round trips, prefetcher
health telemetry, and the runtime-vs-analytic cross-check of the
per-link communication accounting against ``core.costs``."""
import dataclasses
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config, reduced
from repro.core import compression, costs, mpsl, split
from repro.data import PrefetchLoader
from repro.launch.train import make_lm_loader
from repro.obs import comm, report
from repro.optim import schedules
from repro.parallel import sharding
from repro.train import Trainer, TrainerConfig


# ---------------------------------------------------------------------------
# Recorder


def test_noop_default_is_inert():
    assert obs.get().enabled is False
    with obs.span("x/y", step=1):        # shared null span: no alloc, no IO
        pass
    obs.event("x/e")
    obs.counter("x/c")
    obs.gauge("x/g", 1.0)
    assert obs.get() is obs.get()        # singleton
    assert obs.span("x/a") is obs.span("x/b", step=2)


def test_recorder_jsonl_roundtrip(tmp_path):
    path = tmp_path / "log.jsonl"
    with obs.enabled(str(path), meta={"who": "test"}) as rec:
        assert obs.get() is rec and rec.enabled
        with rec.span("stage/a", step=3):
            pass
        rec.counter("n/steps", 2)
        rec.counter("n/steps", 3)
        rec.gauge("q/depth", 4, step=3)
        with rec.span("stage/a", step=4):
            pass
        rec.event("boom", level="error", detail="x")
        # error events flush immediately (crash durability): visible
        # before close
        on_disk = [json.loads(l) for l in path.read_text().splitlines()]
        assert any(r["kind"] == "event" and r["level"] == "error"
                   for r in on_disk)
    assert obs.get().enabled is False    # context restored the no-op
    recs = report.load_records(str(path))
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    assert by_kind["meta"][0]["fields"] == {"who": "test"}
    span = by_kind["span"][0]
    assert span["name"] == "stage/a" and span["dur_s"] >= 0
    assert span["fields"] == {"step": 3}
    assert [s["fields"]["step"] for s in by_kind["span"]] == [3, 4]
    assert by_kind["counter"][-1]["total"] == 5
    assert set(by_kind) == {"meta", "span", "counter", "gauge", "event"}


def test_spans_reach_the_profiler_trace(tmp_path):
    """An enabled recorder's span is also a host annotation in a
    jax.profiler trace, inside the trainer's step marker; a disabled
    recorder still hands out the one shared null span."""
    assert obs.span("step/dispatch", step=0) is obs.span("metrics/readback")
    logdir = tmp_path / "trace"
    with obs.enabled(str(tmp_path / "log.jsonl")) as rec:
        jax.profiler.start_trace(str(logdir))
        try:
            with jax.profiler.StepTraceAnnotation("train", step_num=7):
                with rec.span("step/dispatch", step=7):
                    jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    (path,) = logdir.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = {e.name.split("#")[0]: (e.start_ns, e.start_ns + e.duration_ns)
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}
    assert "step/dispatch" in host and "train" in host
    (s0, e0), (s1, e1) = host["train"], host["step/dispatch"]
    assert s0 <= s1 <= e1 <= e0


def test_report_renders_tables():
    records = [
        {"kind": "meta", "name": "run", "run_id": "abc", "fields": {}},
        {"kind": "span", "name": "step/dispatch", "dur_s": 0.01,
         "fields": {}},
        {"kind": "span", "name": "step/dispatch", "dur_s": 0.03,
         "fields": {}},
        {"kind": "link", "name": "uplink.activations",
         "direction": "uplink", "n_clients": 4,
         "per_client_shape": [2, 32, 64], "dtype": "bfloat16",
         "raw_bytes_per_client": 8192, "wire_bytes_per_client": 4352,
         "compressed": True, "bits": 8, "per_step": True,
         "quantized_in_trace": True},
        {"kind": "gauge", "name": "prefetch/queue_depth", "value": 2},
        {"kind": "event", "name": "prefetch/producer_error",
         "level": "error", "fields": {"step": 7, "error": "boom"}},
    ]
    out = report.render(records)
    assert "step/dispatch" in out and "uplink.activations" in out
    assert "traced" in out               # quant state column
    assert "ERROR prefetch/producer_error" in out
    # per-step aggregate: 4 clients x 4352 wire bytes = 17408 = 17.0KB
    assert "17.0KB" in out


# ---------------------------------------------------------------------------
# Prefetcher health telemetry


class _Boom:
    def batch(self, step):
        if step == 3:
            raise RuntimeError("boom")
        return {"x": np.zeros(2)}


def test_prefetch_health_gauges_and_terminal_error_event(tmp_path):
    path = tmp_path / "log.jsonl"
    with obs.enabled(str(path)):
        pf = PrefetchLoader(_Boom(), depth=2)
        pf.batch(0)
        pf.batch(1)
        h = pf.health()
        assert h["restarts"] == 1 and h["queue_capacity"] == 2
        assert h["produced"] >= 2
        assert h["producer_wait_s"] >= 0.0
        # out-of-order read reseeds the producer
        pf.batch(0)
        assert pf.health()["restarts"] == 2
        with pytest.raises(RuntimeError, match="boom"):
            for k in range(1, 5):
                pf.batch(k)
        assert isinstance(pf.last_error, RuntimeError)
    recs = report.load_records(str(path))
    errs = [r for r in recs if r.get("kind") == "event"
            and r.get("level") == "error"]
    assert errs and errs[0]["name"] == "prefetch/producer_error"
    assert errs[0]["fields"]["step"] == 3
    spans = {r["name"] for r in recs if r.get("kind") == "span"}
    assert "host/assemble" in spans


# ---------------------------------------------------------------------------
# Runtime link accounting vs the core.costs analytic model


def _trace_lm_links(compressed: bool, n=2, bn=2, seq=32):
    comm.reset()
    cfg = reduced(get_config("minitron-4b"))
    mp = MPSLConfig(n_clients=n, trainable_blocks=1, head_adapter_rank=4,
                    compress_uplink=compressed,
                    compress_downlink=compressed)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=seq)
    run = RunConfig(model=cfg, shape=shape, mpsl=mp,
                    compute_dtype="bfloat16")
    params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg, run)
    loss_fn = mpsl.make_lm_loss(cfg, run)
    batch = {"tokens": jnp.zeros((n, bn, seq), jnp.int32),
             "labels": jnp.zeros((n, bn, seq), jnp.int32),
             "mask": jnp.ones((n,), jnp.float32)}
    # the loss trace alone fires the accounting hooks — no compute on
    # the batch path, no compile
    jax.eval_shape(loss_fn, params, frozen, batch, jax.random.PRNGKey(1))
    links = {e["name"]: e for e in comm.snapshot()}
    return cfg, mp, shape, links


@pytest.mark.parametrize("compressed", [False, True])
def test_runtime_link_bytes_match_analytic_model(compressed):
    """Measured per-step link bytes must agree with the core.costs
    analytic model: exactly when uncompressed, within the per-row quant8
    scale overhead when compressed."""
    bn, seq = 2, 32
    cfg, mp, shape, links = _trace_lm_links(compressed, bn=bn, seq=seq)
    up = links["uplink.activations"]
    down = links["downlink.gradients"]
    assert up["n_clients"] == mp.n_clients
    assert up["per_client_shape"] == [bn, seq, cfg.d_model]
    assert up["compressed"] is compressed

    measured_per_sample = (up["wire_bytes_per_client"]
                           + down["wire_bytes_per_client"]) / bn
    analytic = costs.mpsl_lm_client_cost(
        cfg, mp, shape, compressed=compressed).comm_mb_per_epoch * 1e6
    overhead = (2 * seq * compression.SCALE_BYTES) if compressed else 0
    assert 0 <= measured_per_sample - analytic <= overhead, (
        measured_per_sample, analytic, overhead)
    if compressed:
        # the quant kernel was actually traced into the program, and the
        # wire format matches compression.compressed_bytes exactly
        assert up.get("quantized_in_trace") is True
        assert up["wire_bytes_per_client"] == compression.compressed_bytes(
            (bn, seq, cfg.d_model))
    else:
        assert up["wire_bytes_per_client"] == up["raw_bytes_per_client"]
    # one-time head-FedAvg link from core.split
    head = links["aggregation.client_head"]
    assert head["per_step"] is False
    assert head["raw_bytes_per_client"] == head["wire_bytes_per_client"] > 0


# ---------------------------------------------------------------------------
# Steps/sec regression gate (CI satellite)


def test_regression_check_gates_on_ratio():
    from benchmarks.regression_check import check

    base = {"entries": [
        {"cell": "a", "variant": "overlap", "steps_per_sec": 10.0},
        {"cell": "b", "variant": "overlap", "steps_per_sec": 4.0},
        {"cell": "retired", "variant": "overlap", "steps_per_sec": 1.0},
    ]}
    new = {"entries": [
        {"cell": "a", "variant": "overlap", "steps_per_sec": 9.0},
        {"cell": "b", "variant": "overlap", "steps_per_sec": 1.0},
        {"cell": "fresh", "variant": "overlap", "steps_per_sec": 2.0},
    ]}
    rows = {(r["cell"], r["variant"]): r
            for r in check(new, base, min_ratio=0.5)}
    assert rows[("a", "overlap")]["status"] == "ok"
    assert rows[("b", "overlap")]["status"] == "FAIL"      # 0.25 < 0.5
    # added/retired cells are reported, never gated on
    assert rows[("retired", "overlap")]["status"] == "missing-in-new"
    assert rows[("fresh", "overlap")]["status"] == "missing-in-baseline"


# ---------------------------------------------------------------------------
# End-to-end: obs-enabled trainer produces a renderable run log without
# changing the dispatch/sync pattern


def test_trainer_obs_end_to_end(tmp_path, monkeypatch):
    log_dir = os.environ.get("OBS_LOG_DIR")      # CI uploads this artifact
    base = pathlib.Path(log_dir) if log_dir else tmp_path
    base.mkdir(parents=True, exist_ok=True)
    log_path = base / "trainer_runlog.jsonl"

    comm.reset()
    blocks = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (blocks.append(1), real_block(x))[1])

    steps = 5
    with obs.enabled(str(log_path), meta={"test": "trainer_e2e"}):
        cfg = reduced(get_config("minitron-4b"))
        mp = MPSLConfig(n_clients=2, trainable_blocks=1,
                        head_adapter_rank=4)
        run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                        compute_dtype="float32", learning_rate=1e-3)
        params, frozen, _ = split.init_mpsl_lm(jax.random.PRNGKey(0), cfg,
                                               run)
        state = mpsl.place_state(mpsl.init_state(params, frozen))
        loss_fn = mpsl.make_lm_loss(cfg, run)
        step_fn = mpsl.jit_train_step(
            mpsl.make_train_step(loss_fn, run, schedules.constant(1e-3)))
        dispatches = []

        def counted_step(state, batch):
            dispatches.append(1)
            return step_fn(state, batch)

        loader = PrefetchLoader(make_lm_loader(cfg, 2, 2, 24, seed=0),
                                depth=2, place_fn=sharding.place_batch)
        t = Trainer(counted_step, state, loader,
                    TrainerConfig(total_steps=steps, log_every=100),
                    log_fn=lambda s: None)
        out = t.run()
        loader.close()

    assert out["final_loss"] is not None
    # telemetry neutrality: one dispatch per step, and the only device
    # syncs are the two log-boundary readbacks (first-step log + final)
    assert len(dispatches) == steps
    assert len(blocks) == 2

    recs = report.load_records(str(log_path))
    spans = {}
    for r in recs:
        if r.get("kind") == "span":
            spans[r["name"]] = spans.get(r["name"], 0) + 1
    assert spans["step/dispatch"] == steps
    assert spans["step/get_batch"] == steps
    assert spans["metrics/readback"] == 2
    assert spans.get("host/assemble", 0) >= steps      # prefetch producer
    assert spans.get("h2d/place_batch", 0) >= steps
    links = {r["name"] for r in recs if r.get("kind") == "link"}
    assert "uplink.activations" in links
    assert "downlink.gradients" in links
    gauges = {r["name"] for r in recs if r.get("kind") == "gauge"}
    assert "train/loss" in gauges and "prefetch/queue_depth" in gauges
    dispatched = [r["fields"]["step"] for r in recs
                  if r.get("kind") == "span" and r["name"] == "step/dispatch"]
    assert dispatched == list(range(steps))
    events = {r["name"] for r in recs if r.get("kind") == "event"}
    assert {"trainer/run_start", "trainer/run_end"} <= events
    rendered = report.render(recs)
    assert "step/dispatch" in rendered
    assert "uplink.activations" in rendered


# ---------------------------------------------------------------------------
# Recorder rotation (bounded chaos/soak run logs)


def test_recorder_rotation_bounds_log_size(tmp_path):
    path = tmp_path / "log.jsonl"
    with obs.enabled(str(path), meta={"who": "rot"}, flush_every=1,
                     max_bytes=1500) as rec:
        for i in range(200):
            rec.event("spam", i=i)
    assert rec.rotations >= 1
    rotated = tmp_path / "log.jsonl.1"
    assert rotated.exists()
    # total footprint bounded by ~2x the cap (one flush of slack each)
    assert path.stat().st_size <= 2 * 1500
    assert rotated.stat().st_size <= 2 * 1500

    head = [json.loads(l) for l in path.read_text().splitlines()]
    tail = [json.loads(l) for l in rotated.read_text().splitlines()]
    # the live file re-opens self-describing: meta record first, carrying
    # the rotation count and the original run fields
    assert head[0]["kind"] == "meta"
    assert head[0]["fields"] == {"who": "rot"}
    assert head[0]["rotation"] >= 1
    # the rotation boundary loses nothing: rotated + live cover a
    # contiguous suffix of the stream, ending at the newest event
    seen = [r["fields"]["i"] for r in tail + head
            if r.get("kind") == "event" and r["name"] == "spam"]
    assert seen == list(range(min(seen), 200))


# ---------------------------------------------------------------------------
# Mask-aware link accounting (runtime participation weighting)


def test_mask_aware_link_accounting_matches_costs():
    """The trace-time link records assume full participation; the
    runtime mask weighting must agree with the core.costs analytic model
    scaled by the recorded participation fraction."""
    bn, seq = 2, 32
    cfg, mp, shape, links = _trace_lm_links(False, bn=bn, seq=seq)
    agg = comm.per_step_wire_bytes()
    assert agg["participation_frac"] == 1.0      # nothing recorded yet
    assert agg["total_masked"] == agg["total"]

    # runtime mask: one of two clients cut on half the steps; replays of
    # a step (speculative re-assembly, restart) are idempotent
    comm.note_participation(0, 2.0, 2)
    comm.note_participation(1, 1.0, 2)
    comm.note_participation(1, 1.0, 2)
    ps = comm.participation_summary()
    assert ps["steps"] == 2
    assert ps["avg_frac"] == 0.75 and ps["min_frac"] == 0.5

    agg = comm.per_step_wire_bytes()
    assert agg["total_masked"] == int(round(agg["total"] * 0.75))
    # cross-check against the analytic per-client cost (uncompressed ->
    # exact): total = per-sample analytic * Bn * N, masked = frac * total
    analytic = costs.mpsl_lm_client_cost(
        cfg, mp, shape, compressed=False).comm_mb_per_epoch * 1e6
    assert agg["total"] == pytest.approx(analytic * bn * mp.n_clients)
    assert agg["total_masked"] == pytest.approx(
        0.75 * analytic * bn * mp.n_clients, abs=1)

    # the run-log mirror emits the participation gauges
    class _Cap:
        def __init__(self):
            self.gauges = {}

        def link(self, rec):
            pass

        def gauge(self, name, value, **fields):
            self.gauges[name] = (value, fields)

    cap = _Cap()
    comm.emit_snapshot(cap)
    val, fields = cap.gauges["comm/participation_frac"]
    assert val == 0.75 and fields["steps"] == 2
    assert cap.gauges["comm/per_step_wire_bytes_masked"][0] == agg[
        "total_masked"]
    comm.reset()


# ---------------------------------------------------------------------------
# Per-runner-class regression baselines


def test_regression_baseline_class_resolution(tmp_path):
    from benchmarks.regression_check import main, resolve_baseline

    base = tmp_path / "BENCH_pipeline.json"
    base.write_text(json.dumps({"entries": [
        {"cell": "a", "variant": "overlap", "steps_per_sec": 10.0}]}))
    # class file missing -> fall back to the class-less baseline
    path, found = resolve_baseline(str(base), "gha-ubuntu")
    assert path == str(base) and not found
    cls = tmp_path / "BENCH_pipeline.gha-ubuntu.json"
    cls.write_text(json.dumps({"entries": [
        {"cell": "a", "variant": "overlap", "steps_per_sec": 4.0}]}))
    path, found = resolve_baseline(str(base), "gha-ubuntu")
    assert path == str(cls) and found
    assert resolve_baseline(str(base), None) == (str(base), True)

    # the gate resolves the class baseline: 4.9 sps passes vs the
    # class's 4.0 at 0.5, but fails vs the class-less 10.0
    bench = tmp_path / "new.json"
    bench.write_text(json.dumps({"entries": [
        {"cell": "a", "variant": "overlap", "steps_per_sec": 4.9}]}))
    argv = ["--bench", str(bench), "--baseline", str(base),
            "--baseline-class", "gha-ubuntu", "--min-ratio", "0.5"]
    assert main(argv) == 0
    assert main(["--bench", str(bench), "--baseline", str(base),
                 "--min-ratio", "0.5"]) == 1
    # --update with a class rewrites the class file, not the shared one
    assert main(["--bench", str(bench), "--baseline", str(base),
                 "--baseline-class", "gha-ubuntu", "--update"]) == 0
    assert json.loads(cls.read_text()) == json.loads(bench.read_text())
    assert json.loads(base.read_text())["entries"][0][
        "steps_per_sec"] == 10.0


def test_committed_runner_class_baseline_exists():
    # ci.yml gates the full job with --baseline-class gha-ubuntu; the
    # class baseline it resolves must stay committed
    root = pathlib.Path(__file__).resolve().parents[1]
    doc = json.loads((root / "BENCH_pipeline.gha-ubuntu.json").read_text())
    assert doc["entries"]
    assert {"cell", "variant", "steps_per_sec"} <= set(doc["entries"][0])
