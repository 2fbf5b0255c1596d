"""The attention kernels' operation and byte counts against hand counts,
and qwen2.5-14b's attention at its serving sizes."""

from chipbench.core import device
from chipbench.core.harness import ROOT, load_module

WORK = ROOT / "chipbench" / "work"
PEAKS = device.PEAKS["TPU v5 lite"]


def test_flash_decode_hand_count():
    w = load_module(WORK / "flash_decode.py")
    got = w.call(tokens=10, layers=2, heads=4, kv_heads=2, head_dim=8)
    # 20 (position, layer) pairs; 4 heads each take q.k and p.v, 2*8 each
    assert got["ops"] == 20 * 4 * (2 * 8 + 2 * 8)
    # each pair reads 2 kv heads' key and value of 8 bf16 elements
    assert got["bytes"] == 20 * 2 * 2 * 8 * 2


def test_flash_decode_is_memory_bound():
    w = load_module(WORK / "flash_decode.py")
    work = w.call(tokens=24 * 1300, layers=48, heads=40, kv_heads=8,
                  head_dim=128)
    assert w.least_seconds(work, PEAKS) == work["bytes"] / PEAKS[
        "hbm_bytes_per_s"]
    # about 1.3k live positions in each of 24 slots: 196,608 bytes each
    # over 48 layers, 1.5 GB a tick on each of four chips
    assert work["bytes"] == 24 * 1300 * 196_608
    assert 1.5e9 < work["bytes"] / 4 < 1.6e9


def test_flash_attention_hand_count():
    w = load_module(WORK / "flash_attention.py")
    got = w.call(length=3, layers=2, heads=4, kv_heads=2, head_dim=8)
    # 6 causal (query, key) pairs; per layer and head 4*8 operations each
    assert got["ops"] == 2 * 4 * 6 * 4 * 8
    # q and output 4 heads, k and v 2 heads, 3 tokens of 8 bf16 elements
    assert got["bytes"] == 2 * 3 * 8 * (4 + 4 + 2 + 2) * 2


def test_flash_attention_is_compute_bound_at_chat_lengths():
    w = load_module(WORK / "flash_attention.py")
    work = w.call(length=1024, layers=48, heads=40, kv_heads=8, head_dim=128)
    assert w.least_seconds(work, PEAKS) == work["ops"] / PEAKS["bf16_flops"]


def test_qwen2_lm_hand_count():
    w = load_module(WORK / "qwen2_lm.py")
    m = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10}
    # per layer q and o 8x8, k and v 8x4, gate, up and down 8x16; head 8x10
    assert w.flops_per_token(m) == 2 * (2 * (64 + 64 + 32 + 32 + 3 * 128)
                                        + 80)


def test_qwen2_lm_counts_every_matrix_of_qwen2_5_14b():
    """Two FLOPs per weight of every matrix the token multiplies: the
    14,770,033,664 parameters less the embedding (a gather), the Q/K/V
    biases and the norm gains."""
    import json

    w = load_module(WORK / "qwen2_lm.py")
    m = json.loads((ROOT / "chipbench" / "configs" / "qwen2.5-14b.json"
                    ).read_text())["model"]
    matrices = (14_770_033_664 - 152_064 * 5120 - 48 * (5120 + 2 * 1024)
                - (2 * 48 + 1) * 5120)
    assert w.flops_per_token(m) == 2 * matrices
