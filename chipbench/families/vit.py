"""The MPSL classification step on a Meta-Transformer ViT trunk, as the
program runs it: `mpsl.make_vit_loss` under `mpsl.make_train_step`,
jitted with the state donated."""
from __future__ import annotations

import functools

from chipbench import generate, program
from chipbench.reference import common, vit as reference


def run_config(cfg, mix):
    """The program's model and run configuration for this configuration
    file; the file's sizes are the ones run."""
    import dataclasses
    from repro.configs import MPSLConfig, RunConfig, SHAPES, get_config
    from repro.models import tokenizers
    for m in mix["modalities"]:
        spec, tk = tokenizers.MODALITIES[m], cfg["tokenizers"][m]
        if spec.num_tokens != tk["tokens"]:
            raise RuntimeError(f"the program's {m} tokenizer makes "
                               f"{spec.num_tokens} tokens, the configuration "
                               f"states {tk['tokens']}")
    model = dataclasses.replace(
        get_config(cfg["arch"]), num_layers=cfg["num_layers"],
        d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["tokenizers"]["text"]["vocab_size"])
    opt = cfg["optimizer"]
    program.check_optimizer(opt)
    mp = MPSLConfig(n_clients=mix["n_clients"],
                    trainable_blocks=cfg["mpsl"]["trainable_blocks"],
                    fusion=mix["fusion"])
    return model, RunConfig(model=model, shape=SHAPES["train_4k"], mpsl=mp,
                            compute_dtype=cfg["compute_dtype"],
                            param_dtype=cfg["param_dtype"],
                            frozen_dtype=cfg["frozen_dtype"],
                            learning_rate=opt["lr"],
                            weight_decay=opt["weight_decay"],
                            grad_clip=opt["grad_clip"])


def build(cfg, mix, seed, mesh, loss=None, step=None):
    """The cell on `mesh`; `loss` and `step` wrap the program's loss and
    step function (tests that plant a fault)."""
    from repro.core import mpsl, split
    from repro.optim import schedules
    model, run = run_config(cfg, mix)
    modalities = tuple(mix["modalities"])
    loss_fn = mpsl.make_vit_loss(model, run, modalities=modalities,
                                 task="classification",
                                 n_classes=mix["n_classes"])
    if loss is not None:
        loss_fn = loss(loss_fn)
    step_fn = mpsl.make_train_step(loss_fn, run,
                                   schedules.constant(run.learning_rate))
    if step is not None:
        step_fn = step(step_fn)
    step_fn = mpsl.jit_train_step(step_fn)
    state = program.make_state(
        functools.partial(reference.init_weights, cfg, mix),
        lambda k: split.init_mpsl_vit(k, model, run, modalities,
                                      mix["n_classes"])[:2],
        common.seed_key(seed), mesh)
    samples = mix["n_clients"] * mix["batch_per_client"]
    tokens = sum(cfg["tokenizers"][m]["tokens"] for m in modalities)
    return program.Cell(state=state, step=step_fn,
                        pool=generate.make_pool(cfg, mix, seed),
                        counts={"samples": samples,
                                "tokens": samples * tokens},
                        reference=reference)
