"""Model FLOPs against hand counts, and the table of peaks."""
import pytest

from chipbench import flops
from chipbench.metrics import step_mfu


def test_mean_keys_by_hand():
    assert flops.mean_keys(4, causal=False) == 4
    assert flops.mean_keys(4, causal=True) == pytest.approx(2.5)
    assert flops.mean_keys(4, causal=True, window=2) == pytest.approx(1.75)
    assert flops.mean_keys(4, causal=True, window=8) == pytest.approx(2.5)


def _vit():
    cfg = {"family": "vit", "d_model": 4, "num_heads": 2, "head_dim": 2,
           "d_ff": 8, "num_layers": 3, "mpsl": {"trainable_blocks": 1},
           "tokenizers": {"vision": {"image": [4, 4, 3], "patch": 2,
                                     "tokens": 5},
                          "text": {"vocab_size": 10, "tokens": 3}}}
    mix = {"n_clients": 2, "batch_per_client": 3, "n_classes": 5,
           "modalities": ["text", "vision"]}
    return cfg, mix


def test_vit_step_by_hand():
    cfg, mix = _vit()
    seq = 8                                  # 3 text + 5 vision tokens
    # per token: q, k, v, o (4 d x h*hd MACs), MLP 2 d x f, scores 4 S h hd
    layer = 2 * (4 * 4 * 4 + 2 * 4 * 8) + 4 * seq * 2 * 2
    body = (3 * 1 + 2 * 2) * layer * seq     # 1 trained, 2 frozen
    client = 3 * 2 * (2 * 2 * 3) * 4 * 4     # 4 patches of 12 values
    head = 3 * 2 * 4 * 5
    assert flops.step_flops(cfg, mix) == pytest.approx(6 * (body + client
                                                            + head))


def test_lm_step_by_hand():
    cfg = {"family": "lm", "d_model": 4, "num_heads": 2, "num_kv_heads": 1,
           "head_dim": 2, "d_ff": 6, "vocab_size": 7, "num_layers": 2,
           "global_layers": [1], "sliding_window": 2,
           "ssm": {"expand": 2, "d_state": 2, "dt_rank": 1},
           "mpsl": {"trainable_blocks": 1, "head_adapter_rank": 2}}
    mix = {"n_clients": 2, "batch_per_client": 1, "seq_len": 4}
    proj = 2 * (2 * 4 * 4 + 2 * 4 * 2)
    proj += 2 * (4 * 16 + 8 * 5 + 1 * 8 + 8 * 4)
    mlp = 2 * 3 * 4 * 6
    local = proj + mlp + 4 * 1.75 * 4        # layer 0: window 2, frozen
    glob = proj + mlp + 4 * 2.5 * 4          # layer 1: global, trained
    body = 2 * local + 3 * glob
    adapter = 3 * 2 * 2 * 4 * 2
    head = 3 * 2 * 4 * 7 * 3 / 4
    assert flops.step_flops(cfg, mix) == pytest.approx(
        2 * 4 * (body + adapter + head))


def test_full_size_cells_match_the_issue_estimates():
    from chipbench.tests import small
    cfg, mix = small.load("configs", "meta-transformer-b16.json"), \
        small.load("traffic", "vt_early_cls.json")
    assert flops.step_flops(cfg, mix) == pytest.approx(2.74e13, rel=0.02)
    cfg, mix = small.load("configs", "hymba-1.5b.json"), \
        small.load("traffic", "lm_4k.json")
    assert 1.0e14 < flops.step_flops(cfg, mix) < 1.3e14


def test_peaks_are_keyed_by_device_kind():
    assert step_mfu.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        step_mfu.peak_flops("TPU v9 imaginary")
