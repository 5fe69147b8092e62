"""MPSL training driver.

  PYTHONPATH=src python -m repro.launch.train --arch minitron-4b \
      --steps 50 --reduced --ckpt-dir /tmp/ckpt
  python -m repro.launch.train --arch hymba-1.5b --full \
      --trainable-blocks 2 --batch-per-client 1 --seq 4096

The run uses every device the process sees: one (data, model) host mesh
with the MPSL client axis and FSDP over ``data``. The state is
initialised under jit straight into its shardings, so no device ever
holds the whole f32 parameter tree. --reduced trains the same-family
small config in float32 (what CI and the examples use); --full runs the
published widths in the RunConfig compute dtype (bfloat16).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools

import jax
import numpy as np

from repro import faults, obs
from repro.configs import (MPSLConfig, PUBLISHED_MECHANISMS, RunConfig,
                           SHAPES, get_config, reduced)
from repro.core import mpsl, split
from repro.data import (ClientLoader, PrefetchLoader, SyntheticLM,
                        dirichlet_partition)
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compilation_cache
from repro.optim import schedules
from repro.parallel import sharding
from repro.train import Trainer, TrainerConfig


def make_lm_loader(cfg, n_clients: int, bn: int, seq: int, seed: int = 0,
                   drop_prob: float = 0.0):
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, size=4096,
                     seed=seed)
    shards = dirichlet_partition(ds.labels, n_clients, alpha=0.1, seed=seed,
                                 min_per_client=bn)

    base = ClientLoader(ds, shards, bn, seed=seed, drop_prob=drop_prob)

    class LMWrapper:
        def batch(self, step):
            b = base.batch(step)
            return {"tokens": b["tokens"].astype(np.int32),
                    "labels": b["labels"].astype(np.int32),
                    "mask": b["mask"]}

    return LMWrapper()


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="minitron-4b")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--n-clients", type=int, default=4)
    p.add_argument("--batch-per-client", type=int, default=2)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--trainable-blocks", type=int, default=-1)
    p.add_argument("--drop-prob", type=float, default=0.0)
    p.add_argument("--compress", action="store_true")
    p.add_argument("--published-mechanisms", action="store_true",
                   help="turn on what the arch's registry entry leaves "
                        "off (hymba-1.5b: meta tokens and K/V-sharing "
                        "pairs); with --full")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--log-every", type=int, default=10,
                   help="read the loss back every N steps (1 = each step)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prefetch", type=int, default=2,
                   help="prefetch depth (0 = synchronous loader)")
    p.add_argument("--no-donate", dest="donate", action="store_false",
                   default=True, help="disable train-state buffer donation")
    p.add_argument("--obs-log", default=None,
                   help="write a JSONL telemetry run log to this path "
                        "(render with `python -m repro.obs.report`)")
    p.add_argument("--obs-log-max-bytes", type=int, default=None,
                   help="rotate the run log to <path>.1 past this size "
                        "(bounds long chaos/soak runs to ~2x the cap)")
    p.add_argument("--fault-plan", default=None,
                   help="chaos mode: a FaultPlan JSON file or inline "
                        "spec, e.g. 'producer_crash@3,nan_batch@13,"
                        "straggler@11:1:0.2,ckpt_fail@20'. Activates "
                        "injection plus the recovery machinery "
                        "(non-finite step guard, producer/checkpoint "
                        "retries)")
    p.add_argument("--profile-dir", default=None,
                   help="jax.profiler trace of steps 5-6 into this "
                        "directory; the run fails if the trace cannot "
                        "start or stop")
    return p.parse_args(argv)


def make_run_config(args, **overrides):
    """(model config, RunConfig) for parsed args; ``overrides`` replace
    RunConfig fields (e.g. ``compute_dtype``)."""
    cfg = get_config(args.arch)
    if args.published_mechanisms:
        cfg = dataclasses.replace(cfg, **PUBLISHED_MECHANISMS[args.arch])
    if args.reduced:
        cfg = reduced(cfg)
        overrides.setdefault("compute_dtype", "float32")
    mp = MPSLConfig(n_clients=args.n_clients,
                    trainable_blocks=args.trainable_blocks,
                    compress_uplink=args.compress,
                    compress_downlink=args.compress)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], mpsl=mp,
                    learning_rate=args.lr, seed=args.seed, **overrides)
    return cfg, run


def init_train_state(key, cfg, run, mesh):
    """The MPSL train state, initialised under jit directly into its
    shardings on ``mesh``: parameters are created shard by shard, and the
    frozen part is cast to its storage dtype inside the same program."""
    def init(key):
        params, frozen, _ = split.init_mpsl_lm(key, cfg, run)
        return mpsl.init_state(params, frozen, run.seed)

    shardings = mpsl.state_shardings(jax.eval_shape(init, key), mesh)
    return jax.jit(init, out_shardings=shardings)(key)


def make_step_fn(cfg, run, args, guard_nonfinite: bool = False):
    loss_fn = mpsl.make_lm_loss(cfg, run)
    sched = schedules.warmup_cosine(args.lr, 10, args.steps)
    return mpsl.jit_train_step(
        mpsl.make_train_step(loss_fn, run, sched,
                             guard_nonfinite=guard_nonfinite),
        donate=args.donate)


def train(args):
    """Run the MPSL trainer for parsed ``args`` on every device the
    process sees and return the Trainer's result."""
    enable_compilation_cache()
    log = obs.get_logger("train")
    if args.obs_log:
        obs.configure(args.obs_log,
                      meta={"driver": "train", "arch": args.arch,
                            "steps": args.steps,
                            "n_clients": args.n_clients,
                            "batch_per_client": args.batch_per_client,
                            "seq": args.seq, "compress": args.compress,
                            "prefetch": args.prefetch, "seed": args.seed,
                            "fault_plan": args.fault_plan},
                      max_bytes=args.obs_log_max_bytes)

    fault_plan = (faults.FaultPlan.from_spec(args.fault_plan)
                  if args.fault_plan else None)
    if fault_plan is not None:
        faults.activate(fault_plan)
        log.info(f"fault plan active: {len(fault_plan.events)} events "
                 f"({', '.join(fault_plan.kinds_present())}), "
                 f"deadline {fault_plan.deadline_s}s",
                 n_events=len(fault_plan.events),
                 kinds=fault_plan.kinds_present())

    cfg, run = make_run_config(args)
    mesh = mesh_lib.make_host_mesh()
    with sharding.use_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(args.seed), cfg, run,
                                 mesh)
        step_fn = make_step_fn(cfg, run, args,
                               guard_nonfinite=fault_plan is not None)
        # the producer thread does not see this thread's mesh context
        loader = PrefetchLoader(
            make_lm_loader(cfg, args.n_clients, args.batch_per_client,
                           args.seq, args.seed, args.drop_prob),
            depth=args.prefetch,
            place_fn=functools.partial(sharding.place_batch, mesh=mesh))
        trainer = Trainer(step_fn, state, loader,
                          TrainerConfig(total_steps=args.steps,
                                        ckpt_every=args.ckpt_every,
                                        ckpt_dir=args.ckpt_dir,
                                        log_every=args.log_every,
                                        profile_dir=args.profile_dir))
        del state
        try:
            result = trainer.run()
        finally:
            loader.close()
    log.info(f"done: final loss {result['final_loss']:.4f} "
             f"({result['steps_per_sec']:.2f} steps/s, "
             f"host stall {100 * result['host_stall_frac']:.0f}%)",
             final_loss=result["final_loss"],
             steps_per_sec=round(result["steps_per_sec"], 4),
             host_stall_frac=round(result["host_stall_frac"], 4))
    if fault_plan is not None:
        log.info(f"chaos: {len(trainer.skipped_steps)} step(s) skipped by "
                 f"the non-finite guard, "
                 f"{loader.retries} producer retr"
                 f"{'y' if loader.retries == 1 else 'ies'}",
                 skipped_steps=result["skipped_steps"],
                 producer_retries=loader.retries)
        faults.deactivate()
    if args.obs_log:
        obs.shutdown()
        log.info(f"run log -> {args.obs_log} "
                 f"(python -m repro.obs.report {args.obs_log})")
    return result


def main(argv=None):
    train(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
