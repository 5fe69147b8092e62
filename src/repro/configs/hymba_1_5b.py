"""hymba-1.5b — hybrid: parallel attention + mamba heads [arXiv:2411.13676; hf].

32L d_model=1600 25H (GQA kv=5) d_ff=5504 vocab=32001, ssm_state=16.
Each block runs an attention branch and an SSM branch in parallel over the
same input; outputs are per-branch-normed and averaged (Hymba Section 2).
Sliding-window attention everywhere except 3 global layers -> bounded KV
at 500k context => sub-quadratic: runs long_500k.

The published model also has 128 meta tokens and cross-layer KV sharing
(ModelConfig `meta_tokens` and `kv_share_groups`, see models/hybrid.py).
CONFIG still has both off; PUBLISHED holds their values, with the pairs
assumed (the published config.json's kv_reuse_group is not at hand):
consecutive local layers (1,2)..(13,14) and (16,17)..(28,29), layer 30
alone. The MPSL trainer runs with them (`launch/train.py
--published-mechanisms`); serving does not yet.
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="hymba-1.5b",
    family="hybrid",
    num_layers=32,
    d_model=1600,
    num_heads=25,
    num_kv_heads=5,
    d_ff=5504,
    vocab_size=32_001,
    head_dim=64,
    activation="silu",
    norm="rmsnorm",
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2),
    sliding_window=1024,
    global_layers=(0, 15, 31),
    rope_theta=10_000.0,
    max_seq=1_048_576,
)

PUBLISHED = dict(
    meta_tokens=128,
    kv_share_groups=tuple((i, i + 1) for i in (*range(1, 14, 2),
                                               *range(16, 29, 2))),
)
