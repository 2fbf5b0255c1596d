"""The peak table, and the trace reduction on a short trace recorded on a
TPU v5e chip (``data/rnn.xplane.pb``: 30 ms of the DeepBench cell's
window, traced by the harness)."""

from pathlib import Path

import numpy as np
import pytest

from chipbench.core import device, profile

TRACE = Path(__file__).resolve().parent / "data" / "rnn.xplane.pb"


def test_v5e_peaks_are_the_published_ones():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 394e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v4")


@pytest.mark.parametrize("text,name", [
    ("%rwkv6_step.5 = (bf16[1,16]) custom-call(bf16[1] %fusion.2)",
     "rwkv6_step"),
    ("%get-tuple-element.9 = f32[2] get-tuple-element(%rwkv6_step.5)",
     "get-tuple-element"),
    ("%fused_gru_persistent.1 = (bf16[3]) custom-call()",
     "fused_gru_persistent"),
    ("%all-reduce-start.3 = f32[8] all-reduce-start(f32[8] %x)",
     "all-reduce-start"),
    ("%copy-start = (bf16[16]) copy-start(%p)", "copy-start"),
    ("jit_step(123)", "jit_step(123)")])
def test_op_name_ignores_operands(text, name):
    assert profile.op_name(text) == name


def test_union_and_cover():
    assert profile._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                                [5, 8]]
    spans = [(0, 10, "step"), (10, 30, "stamp"), (40, 50, "submit")]
    assert profile._cover(spans, 8, 25) == "stamp"
    assert profile._cover(spans, 32, 38) == "host"


@pytest.fixture(scope="module")
def reduced():
    if not TRACE.is_file():
        pytest.fail(f"the recorded trace {TRACE} is missing")
    return profile.reduce(str(TRACE))


def _events(name_filter=None):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(TRACE))
    window = None
    ops = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == profile.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return window, ops


def test_reduction_of_a_recorded_trace(reduced):
    window, ops = _events()
    lo, hi = window
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy time, counted independently on a 10 ns grid
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for a, b, _ in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 10):int((b - lo) // 10)] = True
    assert reduced["busy_s"] == pytest.approx(grid.sum() * 1e-8, abs=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the fused kernels: every call inside the window, by its own name
    inside = [(a, b) for a, b, n in ops if lo <= a and b <= hi
              and profile.op_name(n).startswith(("fused_lstm", "fused_gru"))]
    secs, calls = profile.kernel_time(reduced, ("fused_lstm", "fused_gru"))
    assert calls >= len(inside) > 0
    assert secs >= sum(b - a for a, b in inside) / 1e9 > 0
    # the breakdown: at most ten of each, the longest first
    ops_s = [s for _, s in reduced["device_ops"]]
    gaps = [s for _, s in reduced["idle_gaps"]]
    assert 0 < len(ops_s) <= 10 and ops_s == sorted(ops_s, reverse=True)
    assert 0 < len(gaps) <= 10 and gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-9
    assert {n for n, _ in reduced["idle_gaps"]} <= set(
        profile.HOST_SPANS) | {"host"}
    assert reduced["collective_s"] == 0.0
