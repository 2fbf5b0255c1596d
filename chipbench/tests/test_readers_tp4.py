"""The chat-tp4 cell's three per-layer readers: ``flash_decode_roofline``,
``flash_attention_roofline`` and ``collective_share.tp4``, on a short
four-chip trace recorded on TPU v5e chips (``data/tp4.xplane.pb``: one
engine step of the cell's traced run, a 2048-token prefill and a decode
tick, 274 ms, cut from the run's trace with its window span moved to
cover it; event stats, host threads other than the window's and device
lines other than "XLA Ops" dropped), against counts made by hand from
the trace; and on runs where they find nothing to read."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench.core import device, profile
from chipbench.core.harness import ROOT, load_module

DATA = Path(__file__).resolve().parent / "data"
LAYERS = ROOT / "chipbench" / "layers"
MODEL = {"n_layers": 48, "d_model": 5120, "n_heads": 40, "n_kv_heads": 8,
         "head_dim": 128}
PEAKS = device.PEAKS["TPU v5 lite"]


def _reader(name):
    return load_module(LAYERS / f"{name}.py").read


def _ctx(chips=4):
    return SimpleNamespace(root=ROOT, config={"model": MODEL}, peaks=PEAKS,
                           devices=[None] * chips)


def _run(trace, ticks=(0, 3)):
    return SimpleNamespace(trace=trace, traced={
        "t0": 0.0, "t1": 1.0, "c0": {"ticks": ticks[0]},
        "c1": {"ticks": ticks[1]}})


def _system(kv=(), prefill=()):
    return SimpleNamespace(engine=SimpleNamespace(
        kv_history=list(kv), prefill_history=list(prefill)))


@pytest.fixture(scope="module")
def recorded():
    return profile.reduce(str(DATA / "tp4.xplane.pb"))


def _by_hand(name):
    """Per-device seconds of the ops whose HLO name starts with ``name``,
    and per-device (busy, collective) seconds, read directly from the
    trace, clipped to its window."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(DATA / "tp4.xplane.pb")).planes)
    lo = hi = None
    for plane in planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == profile.WINDOW_SPAN:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
    secs, busy, coll = {}, {}, {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ivs, civs, own = [], [], 0.0
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns,
                                                hi)
                if b <= a:
                    continue
                op = profile.op_name(e.name)
                ivs.append((a, b))
                if op.startswith(name):
                    own += (b - a) / 1e9
                if op.startswith(profile.COLLECTIVES):
                    civs.append((a, b))
        secs[plane.name] = own
        busy[plane.name] = sum(b - a for a, b in profile._union(ivs)) / 1e9
        coll[plane.name] = sum(b - a for a, b in profile._union(civs)) / 1e9
    return secs, busy, coll


def test_recorded_trace_holds_the_kernels_and_collectives(recorded):
    assert recorded["n_devices"] == 4
    for name in ("flash_decode", "flash_attention"):
        secs, calls = profile.kernel_time(recorded, (name,))
        assert secs > 0 and calls > 0
    assert all(d["collective_s"] > 0 for d in recorded["per_device"])


def test_flash_decode_roofline_by_hand(recorded):
    kv = [24 * 1300, 24 * 1301, 24 * 1302]
    got = _reader("flash_decode_roofline")(_run(recorded), _system(kv),
                                           _ctx())
    secs, _, _ = _by_hand("flash_decode")
    mean = sum(secs.values()) / len(secs)
    live = sum(kv) * 48 * 2 * 8 * 128 * 2           # bytes of live K and V
    assert got == pytest.approx(
        100.0 * live / PEAKS["hbm_bytes_per_s"] / 4 / mean, rel=1e-9)


def test_flash_attention_roofline_by_hand(recorded):
    prefill = [(1000,), (), ()]
    got = _reader("flash_attention_roofline")(
        _run(recorded), _system(prefill=prefill), _ctx())
    secs, _, _ = _by_hand("flash_attention")
    mean = sum(secs.values()) / len(secs)
    flops = 4 * 48 * 40 * 128 * 1000 * 1001 // 2    # causal pairs only
    assert got == pytest.approx(
        100.0 * flops / PEAKS["bf16_flops"] / 4 / mean, rel=1e-9)


def test_collective_share_by_hand(recorded):
    got = _reader("collective_share.tp4")(_run(recorded), None, _ctx())
    _, busy, coll = _by_hand("")
    shares = [coll[k] / busy[k] for k in busy]
    assert 0 < got < 100
    assert got == pytest.approx(100.0 * sum(shares) / len(shares), rel=1e-9)


@pytest.mark.parametrize("name", ["flash_decode_roofline",
                                  "flash_attention_roofline",
                                  "collective_share.tp4"])
def test_nothing_to_read(recorded, name):
    """No trace, no ticks in the traced part, an engine without the
    histories (an older program), or a trace without the kernels or
    devices: None, never an error."""
    read = _reader(name)
    cpu = {"per_device": [], "ops": {}, "n_devices": 0}
    assert read(_run(None), _system([1] * 3, [(8,)] * 3), _ctx()) is None
    assert read(_run(cpu), _system([1] * 3, [(8,)] * 3), _ctx()) is None
    if name != "collective_share.tp4":
        assert read(_run(recorded, (3, 3)), _system([1] * 3, [(8,)] * 3),
                    _ctx()) is None
        assert read(_run(recorded), SimpleNamespace(engine=object()),
                    _ctx()) is None
