"""Reduced-size stand-ins for the benchmark's cells, for CPU tests: the
same families, references and harness, at widths a test run can hold."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def vit_cell():
    cfg = load("configs", "meta-transformer-b16.json")
    cfg.update(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128)
    cfg["mpsl"] = dict(cfg["mpsl"], trainable_blocks=1)
    mix = load("traffic", "vt_early_cls.json")
    mix.update(n_clients=2, batch_per_client=2, n_classes=5, pool_batches=3)
    return cfg, mix


def lm_cell():
    cfg = load("configs", "hymba-1.5b.json")
    cfg.update(num_layers=3, d_model=64, num_heads=4, num_kv_heads=2,
               head_dim=16, d_ff=128, vocab_size=256, sliding_window=16,
               global_layers=[0, 2])
    cfg["ssm"] = dict(cfg["ssm"], d_state=4, dt_rank=4)
    cfg["mpsl"] = dict(cfg["mpsl"], trainable_blocks=2, head_adapter_rank=4)
    mix = load("traffic", "lm_4k.json")
    mix.update(n_clients=2, batch_per_client=1, seq_len=64, pool_batches=3)
    return cfg, mix


CELLS = {"mtb16_vt_cls": vit_cell, "hymba_lm_4k": lm_cell}

# Limits of the reduced cells, set as the full-size ones are, from
# `calibrate.calibrate` at these sizes on the CPU over seeds 21, 23, 31,
# 37 and 41 (largest sound bf16 reading -> smallest fp8-control reading):
#   vit  loss_gap 8.5e-4 -> 1.3e-3, grad_gap 6.2e-3 -> 8.9e-3,
#        update_gap 1.6e-2 -> 7.5e-3; half batch >= 6.3e-2, 7.5e-2, 8.9e-2
#   lm   loss_gap 6.6e-4 -> 1.9e-3, grad_gap 4.3e-2 -> 4.3e-2,
#        update_gap 8.0e-3 -> 1.8e-2; half batch >= 5.6e-3, 0.36, 0.28
# Each limit is above every sound reading; on every one of those seeds the
# control exceeds the loss_gap limit (vit seed 21: the grad_gap limit).
LIMITS = {
    "mtb16_vt_cls": {"loss_gap": 2e-3, "grad_gap": 1.2e-2,
                     "update_gap": 3e-2},
    "hymba_lm_4k": {"loss_gap": 1.5e-3, "grad_gap": 8e-2,
                    "update_gap": 1.5e-2},
}


def resolved(workload):
    """(bench, (workload entry, config, mix, limits)) for a reduced cell,
    in BENCHMARK.json or not yet, held to the reduced cell's limits."""
    cfg, mix = CELLS[workload]()
    return load("..", "BENCHMARK.json"), ({"name": workload, "chips": 1},
                                          cfg, mix, dict(LIMITS[workload]))
