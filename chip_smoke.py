"""On-chip smoke run of the MPSL trainer at published widths.

  python chip_smoke.py              # one TPU chip: phases (a)-(d)
  python chip_smoke.py --four-chip  # four chips: the sharded path only

One process drives the chip. Before anything else it checks that JAX sees
a TPU and that the Pallas kernels will run compiled; otherwise it exits
non-zero without a result line. Phases on one chip:

  (a) every Pallas kernel, compiled, fwd and VJP against kernels/ref.py
      at real widths (head_dim 128 / 64, hymba-1.5b's 4224 positions
      with its meta prefix, vocab 256000 / 32001, d_inner 8192);
  (b) hymba-1.5b as published (widths, 128 meta tokens, K/V-sharing
      pairs; 4 clients, seq 4096, 2 trainable blocks, bf16) through the
      trainer for 8 steps, with a jax.profiler trace of steps 5-6 and the
      selective scan's core as each call site resolved it (`ssm/impl`);
  (c) two steps with the int8 cut-layer compression on;
  (d) (b)'s 2-step trace must hold TPU device events.

With --four-chip only: minitron-4b (which one chip cannot hold) trains 4
steps FSDP-sharded over the chips, reporting each chip's peak memory; and
one hymba-1.5b step on the 4-chip mesh is checked against the same step
on one chip, both from one host copy of the seeded initial state.

Each phase prints its numbers; any failure exits non-zero. The last line
of stdout is {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
TRACE_DIR = os.path.join(ROOT, ".chip_smoke_trace")

# bf16 inputs and outputs: errors are normalised by the reference's max |.|
BF16_TOL = 2e-2
# one bf16 train step on four chips against one chip (loss at init ~10.8)
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 5e-2

# hymba-1.5b at published widths on one v5e chip. The per-client batch is
# 1: compiled for v5e, the step's program needs 19.42 GB of HBM at 2
# sequences per client (over the 15.75 GB the chip offers) and fits at 1.
HYMBA = ["--arch", "hymba-1.5b", "--full", "--trainable-blocks", "2",
         "--n-clients", "4", "--batch-per-client", "1", "--seq", "4096",
         "--steps", "8", "--lr", "1e-3", "--log-every", "1"]
MINITRON = ["--arch", "minitron-4b", "--full", "--trainable-blocks", "2",
            "--n-clients", "4", "--batch-per-client", "1", "--seq", "4096",
            "--steps", "4", "--lr", "1e-3", "--log-every", "1"]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices")
    from repro.kernels import ops
    if ops.INTERPRET:
        fail("Pallas kernels would run in interpret mode")
    if len(devs) != count:
        fail(f"expected {count} TPU device(s), found {len(devs)}")
    return devs


class CompileClock:
    """Seconds the XLA backend spends compiling, from JAX's own events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def take(self):
        out = (self.secs, self.count)
        self.secs, self.count = 0.0, 0
        return out


def norm_err(a, r) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    r = np.asarray(r, np.float32)
    return float(np.max(np.abs(a - r)) / max(np.max(np.abs(r)), 1e-6))


def compare(name, outs, refs, tol=BF16_TOL):
    import jax
    for i, (a, r) in enumerate(zip(jax.tree_util.tree_leaves(outs),
                                   jax.tree_util.tree_leaves(refs))):
        check(a.shape == r.shape, f"{name}[{i}] shape {a.shape} != {r.shape}")
        err = norm_err(a, r)
        print(f"  {name}[{i}] {tuple(a.shape)} max_err={err:.3e} "
              f"tol={tol:.0e}", flush=True)
        check(math.isfinite(err) and err <= tol,
              f"{name}[{i}] error {err:.3e} over {tol:.0e}")


# ---------------------------------------------------------------------------
# (a) kernels


def phase_kernels(clock):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    bf = jnp.bfloat16

    def normal(i, shape, scale=1.0, dtype=bf):
        return (jax.random.normal(jax.random.fold_in(key, i), shape)
                * scale).astype(dtype)

    def vjp_pair(f, g, args, ct):
        """(out, grads) of the kernel path and of the reference, each one
        compiled program."""
        def run(fn):
            def go(ct, *a):
                out, pull = jax.vjp(fn, *a)
                return out, pull(ct)
            return jax.jit(go)(ct, *args)
        mine = run(f)
        with jax.default_matmul_precision("highest"):
            theirs = run(g)
        return mine, theirs

    # flash attention: minitron-4b's head_dim 128 (GQA 32/8, global),
    # hymba-1.5b's head_dim 64 (GQA 25/5, 1024-token window), and hymba as
    # published: 4224 positions whose first 128 (the meta tokens) stay in
    # every window's view, so the kernels skip most tiles
    for h, kh, hd, window, s, prefix in [(32, 8, 128, 0, 2048, 0),
                                         (25, 5, 64, 1024, 2048, 0),
                                         (25, 5, 64, 1024, 4224, 128)]:
        b = 1
        q, k, v = (normal(1, (b, s, h, hd)), normal(2, (b, s, kh, hd)),
                   normal(3, (b, s, kh, hd)))
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        ct = normal(4, (b, s, h, hd))
        mine, theirs = vjp_pair(
            lambda q, k, v: ops.flash_attention(
                q, k, v, pos, pos, causal=True, window=window,
                prefix=prefix),
            lambda q, k, v: ref.flash_attention_ref(
                q, k, v, pos, pos, causal=True, window=window,
                prefix=prefix),
            (q, k, v), ct)
        compare(f"flash_attention hd={hd} window={window} s={s} "
                f"prefix={prefix}", mine, theirs)

    # fused LM-head CE: vocab 256000 at d_model 3072, vocab 32001 at 1600
    for t, d, vocab in [(1024, 3072, 256_000), (4096, 1600, 32_001)]:
        hid = normal(5, (t, d))
        w = normal(6, (d, vocab), d ** -0.5)
        labels = jax.random.randint(jax.random.fold_in(key, 7), (t,), 0,
                                    vocab)
        ct = normal(8, (t,), dtype=jnp.float32)
        mine, theirs = vjp_pair(
            lambda hid, w: ops.softmax_xent_tokens(hid, w, labels),
            lambda hid, w: ref.softmax_xent_ref(hid, w, labels)[0],
            (hid, w), ct)
        compare(f"softmax_xent d={d} vocab={vocab}", mine, theirs)

    # selective scan at falcon-mamba-7b's d_inner 8192
    b, s, di, ds = 1, 1024, 8192, 16
    x = normal(9, (b, s, di), 0.5)
    dt = (jax.nn.softplus(normal(10, (b, s, di), dtype=jnp.float32))
          * 0.1).astype(bf)
    bi, ci = normal(11, (b, s, ds)), normal(12, (b, s, ds))
    a_log = jnp.log(jnp.abs(normal(13, (di, ds), dtype=jnp.float32)) + 0.5)
    cts = (normal(14, (b, s, di)), normal(15, (b, di, ds), 0.1, jnp.float32))
    mine, theirs = vjp_pair(
        lambda *a: ops.selective_scan(*a, None, 256, 512),
        ref.selective_scan_ref, (x, dt, bi, ci, a_log), cts)
    compare(f"selective_scan d_inner={di}", mine, theirs)

    # int8 cut-layer quant-dequant: the hymba uplink [N, Bn, S, D]
    xq = normal(16, (4, 1, 4096, 1600))
    compare("quant8 nearest", jax.jit(ops.quant_dequant)(xq),
            ref.quant_dequant_ref(xq))
    ct = normal(17, xq.shape)
    y, pull = jax.vjp(lambda x: ops.quant_dequant(
        x, jax.random.fold_in(key, 18)), xq)
    (gx,) = pull(ct)
    x32 = np.asarray(xq, np.float32).reshape(-1, 1600)
    step = np.maximum(np.abs(x32).max(-1, keepdims=True) / 127.0, 1e-12)
    dev = (np.asarray(y, np.float32).reshape(-1, 1600) - x32) / step
    bias = float(dev.mean())
    print(f"  quant8 stochastic: max |y-x|/step={np.abs(dev).max():.3f} "
          f"mean (y-x)/step={bias:.2e}", flush=True)
    # one quantisation step, plus the bf16 rounding of the output (up to
    # 127 steps * 2^-8)
    check(np.abs(dev).max() <= 1.5, "stochastic rounding moved over a step")
    check(abs(bias) <= 1e-2, f"stochastic rounding is biased ({bias:.2e})")
    check(bool(jnp.all(gx == ct)), "quant8 cotangent is not straight-through")
    secs, n = clock.take()
    print(f"  compile_s={secs:.1f} ({n} programs)", flush=True)


# ---------------------------------------------------------------------------
# the trainer


def train_run(argv):
    from repro.launch import train
    return train.train(train.parse_args(argv))


def losses_of(result):
    return [h["loss"] for h in result["history"]]


def init_state(argv, mesh):
    """The seeded initial train state on ``mesh``, as the launcher makes
    it."""
    import jax
    from repro.launch import train
    from repro.parallel import sharding
    args = train.parse_args(argv)
    cfg, run = train.make_run_config(args)
    with sharding.use_mesh(mesh):
        return train.init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                      run, mesh)


def one_step(argv, mesh, host_state=None):
    """Loss and grad norm of the first train step on ``mesh``, built by the
    launcher's own functions, from ``host_state`` (a host copy) or else
    from the seeded initial state."""
    from repro.core import mpsl
    from repro.launch import train
    from repro.parallel import sharding
    args = train.parse_args(argv)
    cfg, run = train.make_run_config(args)
    if host_state is None:
        state = init_state(argv, mesh)
    else:
        state = mpsl.place_state(host_state, mesh)
    with sharding.use_mesh(mesh):
        step = train.make_step_fn(cfg, run, args)
        batch = train.make_lm_loader(cfg, args.n_clients,
                                     args.batch_per_client, args.seq,
                                     args.seed).batch(0)
        state, metrics = step(state, sharding.place_batch(batch, mesh))
        out = float(metrics["loss"]), float(metrics["grad_norm"])
    del state
    return out


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def phase_train(clock, devs):
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    log = os.path.join(TRACE_DIR, "run.jsonl")
    result = train_run(HYMBA + ["--published-mechanisms", "--profile-dir",
                                TRACE_DIR, "--obs-log", log])
    secs, n = clock.take()
    losses = losses_of(result)
    with open(log) as f:
        scans = [r["fields"] for r in map(json.loads, f)
                 if r.get("name") == "ssm/impl"]
    print(f"  ssm/impl per traced call site: {scans}", flush=True)
    check(bool(scans), "no ssm/impl event in the run log")
    print(f"  device_kind={devs[0].device_kind} compile_s={secs:.1f} "
          f"({n} programs) peak_bytes_in_use={peak_bytes(devs[0])}",
          flush=True)
    print(f"  losses={[round(l, 4) for l in losses]} "
          f"steps_per_sec(incl. compile)={result['steps_per_sec']:.3f}",
          flush=True)
    check(len(losses) == 8, f"expected 8 losses, got {len(losses)}")
    check(all(math.isfinite(l) for l in losses), "non-finite loss")
    ln_v = math.log(32_001)
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.3f} not near ln(32001)={ln_v:.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


def phase_compress(clock):
    result = train_run(HYMBA + ["--steps", "2", "--compress"])
    secs, n = clock.take()
    losses = losses_of(result)
    print(f"  compress losses={[round(l, 4) for l in losses]} "
          f"compile_s={secs:.1f} ({n} programs)", flush=True)
    check(len(losses) == 2 and all(math.isfinite(l) for l in losses),
          f"compressed run losses {losses}")


def device_time_summary(plane, top: int = 8) -> None:
    """Print where a TPU plane's time went: the share of the traced window
    in which an XLA module ran, and the ops that took longest. Op times
    nest (a while loop's time includes its body's ops), so they are not
    shares of the window."""
    lines = {line.name: list(line.events) for line in plane.lines}
    modules = sorted(lines.get("XLA Modules", []), key=lambda e: e.start_ns)
    if modules:
        window = modules[-1].end_ns - modules[0].start_ns
        busy, end = 0.0, modules[0].start_ns
        for e in modules:           # union of the module intervals
            busy += max(0.0, e.end_ns - max(e.start_ns, end))
            end = max(end, e.end_ns)
        print(f"  {plane.name}: modules {len(modules)}, window "
              f"{window / 1e9:.3f} s, busy {busy / 1e9:.3f} s, idle share "
              f"{1 - busy / window:.4f}", flush=True)
    by_op = {}
    for e in lines.get("XLA Ops", []):
        name = e.name.split(" = ")[0]     # the op, without its HLO text
        t, c = by_op.get(name, (0.0, 0))
        by_op[name] = (t + e.duration_ns, c + 1)
    for name, (t, c) in sorted(by_op.items(),
                               key=lambda kv: -kv[1][0])[:top]:
        print(f"    {t / 1e9:9.4f} s x{c:<5d} {name}", flush=True)


def phase_trace():
    import jax
    files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    check(len(files) == 1, f"expected one trace file, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    planes = {p.name: sum(1 for line in p.lines for _ in line.events)
              for p in data.planes}
    tpu = {k: v for k, v in planes.items() if k.startswith("/device:TPU")}
    print(f"  trace {os.path.getsize(files[0])} bytes, TPU planes {tpu}",
          flush=True)
    check(any(v > 0 for v in tpu.values()), f"no TPU events in {planes}")
    for p in data.planes:
        if tpu.get(p.name):
            device_time_summary(p)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def phase_four_chip(clock, devs):
    from repro.launch import mesh as mesh_lib
    result = train_run(MINITRON)
    secs, n = clock.take()
    losses = losses_of(result)
    peaks = [peak_bytes(d) for d in devs]
    print(f"  minitron-4b FSDP x{len(devs)} losses="
          f"{[round(l, 4) for l in losses]} compile_s={secs:.1f} "
          f"({n} programs)", flush=True)
    print(f"  peak_bytes_in_use per device={peaks}", flush=True)
    check(len(losses) == 4 and all(math.isfinite(l) for l in losses),
          f"minitron losses {losses}")
    check(max(peaks) <= 1.25 * min(peaks), f"unbalanced peaks {peaks}")
    del result
    gc.collect()

    # one initial state, built on the mesh as the launcher builds it and
    # copied to the host; then the same step from that copy on one chip
    # and on the whole mesh
    import jax
    mesh = mesh_lib.make_host_mesh()
    host = jax.device_get(init_state(HYMBA, mesh))
    l1, g1 = one_step(HYMBA, mesh_lib.make_host_mesh(devs[:1]), host)
    l4, g4 = one_step(HYMBA, mesh, host)
    secs, n = clock.take()
    print(f"  hymba step on {len(devs)} chips: loss={l4:.5f} "
          f"grad_norm={g4:.5f} | on 1 chip: loss={l1:.5f} "
          f"grad_norm={g1:.5f} compile_s={secs:.1f} ({n} programs)",
          flush=True)
    check(abs(l4 - l1) <= LOSS_RTOL * abs(l1),
          f"sharded loss {l4} != single-device loss {l1}")
    check(abs(g4 - g1) <= GRAD_NORM_RTOL * abs(g1),
          f"sharded grad norm {g4} != single-device grad norm {g1}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chip", action="store_true",
                   help="run only the four-chip sharded path")
    args = p.parse_args(argv)
    count = 4 if args.four_chip else 1
    devs = require_tpu(count)

    from repro.launch.compile_cache import enable_compilation_cache
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    clock = CompileClock()
    if args.four_chip:
        phases = [("four-chip", lambda: phase_four_chip(clock, devs))]
    else:
        phases = [
            ("a kernels", lambda: phase_kernels(clock)),
            ("b train", lambda: phase_train(clock, devs)),
            ("c compress", lambda: phase_compress(clock)),
            ("d trace", phase_trace),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"[{name}]", flush=True)
        try:
            fn()
        except SystemExit:
            raise
        except Exception as e:  # any phase error fails the run
            traceback.print_exc()
            fail(f"phase {name}: {type(e).__name__}: {e}")
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s", flush=True)
        gc.collect()          # free the last phase's device state now

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
