"""Optimizers: AdamW with trainable-subtree masking, schedules, clipping."""
from repro.optim.adamw import (adamw_init, adamw_update, apply_updates,
                               global_norm, clip_by_global_norm)
from repro.optim.schedules import warmup_cosine, constant
