"""Fault-tolerant MPSL training loop.

Fault-tolerance mechanisms (designed for thousands of nodes, exercised
here on the host mesh):

  * checkpoint/restart — async sharded checkpoints every `ckpt_every`
    steps; on construction the trainer auto-resumes from the latest
    complete checkpoint. The data pipeline is step-indexed, so the
    restarted run consumes exactly the batches the failed run would have.
  * straggler / dropout masking — the loader emits a per-step client
    participation mask; the MPSL aggregated loss renormalizes weights, so
    a slow or dead client simply contributes weight 0 that step (the
    paper's weighted aggregation makes this exact, not approximate).
  * elastic clients — a client joining mid-run receives the FedAvg of the
    live client heads (aggregation.broadcast_head); head banks are sized
    N_max so population changes don't recompile.
  * crash-consistency — checkpoint publishing is atomic (write-temp +
    rename); a kill at any point leaves a loadable directory.

Pipeline overlap: the loop itself never forces a device sync. Metrics
stay on device in a small ring (`MetricsRing`) and are read back only at
log boundaries and at the end of the run, with an explicit
`block_until_ready` on just that entry; the run-level `steps_per_sec` is
the synchronized number. Each iteration runs inside a
`jax.profiler.StepTraceAnnotation("train", step_num=i)`, the marker a
profiler's step view reads (`--profile-dir`). With a prefetching loader
(`repro.data.PrefetchLoader`) and a donated step
(`core.mpsl.jit_train_step`), host batch assembly, H2D transfer, and
device compute all overlap.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.core import aggregation, mpsl
from repro.obs.spans import ProfileWindow


class MetricsRing:
    """Fixed-size ring of on-device step metrics. Pushing never syncs;
    reading blocks on exactly one entry. Keeping at most `size` metric
    dicts alive bounds how many in-flight steps the host can run ahead."""

    def __init__(self, size: int = 64):
        self.size = size
        self._slots = [None] * size

    def push(self, step: int, metrics):
        self._slots[step % self.size] = (step, metrics)

    def latest(self):
        live = [s for s in self._slots if s is not None]
        return max(live, key=lambda s: s[0]) if live else None

    def read_latest(self) -> Optional[Dict[str, Any]]:
        """Host copy of the newest entry (blocks on that entry alone)."""
        ent = self.latest()
        if ent is None:
            return None
        step, m = ent
        jax.block_until_ready(m)
        return dict({k: np.asarray(v) for k, v in m.items()}, step=step)

    def entries_after(self, start_step: int):
        """Live (step, metrics) entries with step > start_step, ascending.
        Metrics stay on device — touching a value is what blocks, so
        callers that only inspect dict keys stay sync-free."""
        live = [s for s in self._slots
                if s is not None and s[0] > start_step]
        return sorted(live, key=lambda s: s[0])


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: Optional[str] = None
    keep: int = 3
    log_every: int = 10
    metrics_ring: int = 64
    # opt-in jax.profiler trace window (deep dives; inert when None —
    # the span telemetry never measures device time, by design)
    profile_dir: Optional[str] = None
    profile_start: int = 5
    profile_steps: int = 2


class Trainer:
    def __init__(self, step_fn: Callable, state, loader, config: TrainerConfig,
                 log_fn: Callable[[str], None] = print,
                 recorder=None):
        self.step_fn = step_fn
        self.state = state
        self.loader = loader
        self.cfg = config
        self.log = log_fn
        # ambient recorder resolved at construction; pass one explicitly
        # to pin a sink. All obs calls are host-side only — the jitted
        # program and its dispatch pattern are identical with
        # telemetry on or off (asserted in tests/test_pipeline.py).
        self.obs = recorder if recorder is not None else obs_mod.get()
        self.ckpt = (AsyncCheckpointer(config.ckpt_dir, config.keep)
                     if config.ckpt_dir else None)
        self.metrics_history: list = []
        self.ring = MetricsRing(config.metrics_ring)
        self.skipped_steps: list = []   # non-finite guard skips (fault mode)
        self._skip_scan_from = 0        # ring high-water mark for the scan
        self._profile = ProfileWindow(config.profile_dir,
                                      config.profile_start,
                                      config.profile_steps)
        self._maybe_resume()

    # -- fault tolerance ----------------------------------------------------

    def _maybe_resume(self):
        if not self.ckpt:
            return
        step = latest_step(self.cfg.ckpt_dir)
        if step is None:
            return
        restored, manifest = restore_checkpoint(self.cfg.ckpt_dir,
                                                self.state)
        if restored is not None:
            self.state = restored
            self.log(f"[trainer] resumed from step {step}")

    def checkpoint_now(self):
        if self.ckpt:
            step = int(self.state["step"])
            self.ckpt.save(step, self.state, extra={"step": step})

    def rejoin_client(self, client_idx: int):
        """Elastic join: reinitialize a client head from the FedAvg of the
        current bank (paper Sec. 3.3 aggregation, applied online)."""
        heads = self.state["params"]["client"]
        agg = aggregation.fedavg_heads(heads)

        def put(bank, one):
            return bank.at[client_idx].set(one.astype(bank.dtype))

        self.state["params"]["client"] = jax.tree_util.tree_map(
            put, heads, agg)

    # -- loop ----------------------------------------------------------------

    def _drain_skips(self):
        """Fault mode only: surface non-finite-guard skips at the same
        boundaries as the metrics readback. When the step is unguarded
        ("skipped" never appears in metrics) this touches no device
        value — the sync pattern of a clean run is unchanged. Entries
        older than the ring evict unseen; chaos runs keep log_every
        below the ring size (asserted nowhere, documented here)."""
        for step, m in self.ring.entries_after(self._skip_scan_from):
            self._skip_scan_from = max(self._skip_scan_from, step)
            if "skipped" not in m:
                continue
            if float(np.asarray(m["skipped"])) >= 0.5:
                # ring entries are pushed at i+1; report the batch/step
                # index i that was skipped (matches the injection event)
                self.skipped_steps.append(step - 1)
                self.obs.event("fault/step_skipped", step=step - 1)
                self.obs.counter("fault/steps_skipped")

    def _log_latest(self, total: int, t0: float):
        with self.obs.span("metrics/readback"):
            m = self.ring.read_latest()      # the only mid-loop device sync
        self._drain_skips()
        loss = float(m["loss"])
        step = int(m["step"])
        self.metrics_history.append({"step": step, "loss": loss})
        self.obs.gauge("train/loss", loss, step=step)
        self.obs.gauge("train/participating", int(m["participating"]),
                       step=step)
        health = getattr(self.loader, "health", None)
        if callable(health):
            for k, v in health().items():
                self.obs.gauge(f"prefetch/{k}", v, step=step)
        self.log(f"[trainer] step {m['step']}/{total} "
                 f"loss={loss:.4f} "
                 f"clients={int(m['participating'])} "
                 f"({time.perf_counter() - t0:.1f}s)")

    def run(self, steps: Optional[int] = None) -> Dict[str, Any]:
        total = steps if steps is not None else self.cfg.total_steps
        t0 = time.perf_counter()
        start = int(self.state["step"])
        self._skip_scan_from = max(self._skip_scan_from, start)
        self.obs.event("trainer/run_start", start_step=start,
                       total_steps=total)
        host_s = 0.0                    # time spent assembling/placing input
        for i in range(start, total):
            self._profile.on_step(i)
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                t_step = time.perf_counter()
                with self.obs.span("step/get_batch", step=i):
                    batch = self.loader.batch(i)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                host_s += time.perf_counter() - t_step
                with self.obs.span("step/dispatch", step=i):
                    self.state, metrics = self.step_fn(self.state, batch)
                self.ring.push(i + 1, metrics)
                if (i + 1) % self.cfg.log_every == 0 or i == start:
                    self._log_latest(total, t0)
                if self.ckpt and (i + 1) % self.cfg.ckpt_every == 0:
                    with self.obs.span("ckpt/save", step=i + 1):
                        self.ckpt.save(i + 1, self.state)
                    self.obs.counter("trainer/checkpoints")
        self._profile.stop()
        # final readback reflects the LAST step, not the last logged step
        with self.obs.span("metrics/readback"):
            final = self.ring.read_latest()
        self._drain_skips()
        if final is not None and (not self.metrics_history or
                                  self.metrics_history[-1]["step"]
                                  < int(final["step"])):
            self.metrics_history.append({"step": int(final["step"]),
                                         "loss": float(final["loss"])})
        wall = time.perf_counter() - t0
        if self.ckpt:
            self.ckpt.save(total, self.state)
            self.ckpt.wait()
        ran = total - start
        result = {"final_loss": (float(final["loss"])
                                 if final is not None else None),
                  "history": self.metrics_history,
                  "steps_per_sec": (ran / wall) if wall > 0 and ran else 0.0,
                  "host_stall_frac": (host_s / wall) if wall > 0 else 0.0,
                  "skipped_steps": list(self.skipped_steps),
                  "wall_s": wall}
        # close out the run log: link accounting captured at trace time
        # and the run summary
        obs_mod.comm.emit_snapshot(self.obs)
        self.obs.event("trainer/run_end", steps=ran,
                       final_loss=result["final_loss"],
                       steps_per_sec=round(result["steps_per_sec"], 4),
                       host_stall_frac=round(result["host_stall_frac"], 4),
                       wall_s=round(wall, 4))
        self.obs.flush()
        return result
