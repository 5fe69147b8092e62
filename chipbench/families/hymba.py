"""The MPSL language-model step on Hymba-1.5B as published, as the program
runs it: `mpsl.make_lm_loss` under `mpsl.make_train_step`, jitted with the
state donated, on a model with the configuration file's meta tokens and
K/V-sharing pairs. Importing it registers the model FLOPs of its steps
(`hymba_flops`)."""
from __future__ import annotations

import dataclasses
import functools

from chipbench import flops, generate, hymba_flops, program
from chipbench.families import lm
from chipbench.reference import common, hymba as reference

flops.STEP_FLOPS["hymba"] = hymba_flops.step_flops


def run_config(cfg, mix):
    """The program's model and run configuration for this configuration
    file: the `lm` family's, with the meta tokens and K/V-sharing pairs."""
    model, run = lm.run_config(cfg, mix)
    model = dataclasses.replace(
        model, meta_tokens=cfg["meta_tokens"],
        kv_share_groups=tuple(tuple(g) for g in cfg["kv_share_groups"]))
    return model, dataclasses.replace(run, model=model)


def build(cfg, mix, seed, mesh, loss=None, step=None):
    """The cell on `mesh`; `loss` and `step` wrap the program's loss and
    step function (tests that plant a fault)."""
    from repro.core import mpsl, split
    from repro.optim import schedules
    model, run = run_config(cfg, mix)
    loss_fn = mpsl.make_lm_loss(model, run)
    if loss is not None:
        loss_fn = loss(loss_fn)
    step_fn = mpsl.make_train_step(loss_fn, run,
                                   schedules.constant(run.learning_rate))
    if step is not None:
        step_fn = step(step_fn)
    step_fn = mpsl.jit_train_step(step_fn)
    state = program.make_state(
        functools.partial(reference.init_weights, cfg, mix),
        lambda k: split.init_mpsl_lm(k, model, run)[:2],
        common.seed_key(seed), mesh)
    seqs = mix["n_clients"] * mix["batch_per_client"]
    return program.Cell(state=state, step=step_fn,
                        pool=generate.make_pool(cfg, mix, seed),
                        counts={"samples": seqs,
                                "tokens": seqs * mix["seq_len"]},
                        reference=reference)
