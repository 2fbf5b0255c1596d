"""Each kernel's operation and byte count against a hand count at a small
shape, and the model FLOPs per token of RWKV-6."""

import pytest

from chipbench.core import device
from chipbench.core.harness import ROOT, load_module

WORK = ROOT / "chipbench" / "work"
PEAKS = device.PEAKS["TPU v5 lite"]


def test_rwkv6_step_hand_count():
    w = load_module(WORK / "rwkv6_step.py")
    got = w.call(batch=2, heads=3, key=4, value=4, tokens=1)
    # 2*3 (row, head) pairs, each 7*4*4 + 4 operations
    assert got["ops"] == 6 * (7 * 16 + 4)
    # state 6*16 f32 in and out, r/k/w/v 6*4 f32 each, u 3*4 f32, y bf16
    assert got["bytes"] == 2 * 6 * 16 * 4 + 6 * 16 * 4 + 3 * 4 * 4 + 6 * 4 * 2


def test_rwkv6_step_is_memory_bound_at_serving_size():
    w = load_module(WORK / "rwkv6_step.py")
    work = w.call(batch=16, heads=32, key=64, value=64, tokens=1)
    assert w.least_seconds(work, PEAKS) == work["bytes"] / PEAKS[
        "hbm_bytes_per_s"]


@pytest.mark.parametrize("cell,g,n_vec,n_state", [("lstm", 4, 3, 4),
                                                  ("gru", 3, 4, 2)])
def test_fused_rnn_hand_count(cell, g, n_vec, n_state):
    w = load_module(WORK / "fused_rnn.py")
    got = w.call(cell=cell, hidden=8, features=8, timesteps=5)
    assert got["ops"] == 2 * g * 8 * 16 * 5
    assert got["bytes"] == (g * 8 * 16 + n_vec * g * 8 * 4 + n_state * 8 * 4
                            + 5 * 16 * 2)


def test_fused_rnn_long_sequence_is_compute_bound():
    w = load_module(WORK / "fused_rnn.py")
    work = w.call(cell="gru", hidden=1024, features=1024, timesteps=1500)
    assert w.least_seconds(work, PEAKS) == work["ops"] / PEAKS["int8_ops"]


def test_rwkv6_flops_per_token_hand_count():
    w = load_module(WORK / "rwkv6_lm.py")
    m = {"n_layers": 2, "d_model": 128, "d_ff": 448, "head_dim": 64,
         "vocab_size": 512}
    mats = 6 * 128 ** 2 + 2 * 128 * 448 + 2 * 128 * 160 + 2 * 128 * 64
    wkv = 2 * 7 * 64 * 64                     # 2 heads of 64 x 64 state
    assert w.flops_per_token(m) == 2 * (2 * mats + wkv) + 2 * 128 * 512


def test_rwkv6_16b_is_about_3_gflop_per_token():
    w = load_module(WORK / "rwkv6_lm.py")
    m = {"n_layers": 24, "d_model": 2048, "d_ff": 7168, "head_dim": 64,
         "vocab_size": 65536}
    assert 2.9e9 < w.flops_per_token(m) < 3.0e9
