"""The program spans in a traced run: the segmenting of the host timeline,
the idle attribution and the self times, on hand-made spans and on a
short trace recorded on a TPU v5e chip (``data/spans.xplane.pb``), and
the two metrics that read them, on the tiny cells."""

import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.core import profile, spans
from chipbench.core.harness import load_module
from chipbench.tests import tiny

DATA = Path(__file__).resolve().parent / "data"

# a harness step around an engine step and its phases, a stamp, and a
# submit that runs past the window [0, 120]
HAND = [(0, 100, "step"), (10, 90, "engine.step"),
        (12, 30, "engine.schedule"), (15, 25, "engine.prefill"),
        (30, 40, "engine.launch"), (40, 80, "engine.readback"),
        (80, 88, "engine.bookkeep"), (100, 110, "stamp"),
        (115, 130, "submit")]


def test_program_spans_are_the_programs():
    from repro.obs.spans import SPANS

    assert spans.PROGRAM_SPANS == SPANS


def test_timeline_names_the_innermost_span():
    assert spans.timeline(HAND, 0, 120) == [
        (0, 10, "step"), (10, 12, "engine.step"),
        (12, 15, "engine.schedule"), (15, 25, "engine.prefill"),
        (25, 30, "engine.schedule"), (30, 40, "engine.launch"),
        (40, 80, "engine.readback"), (80, 88, "engine.bookkeep"),
        (88, 90, "engine.step"), (90, 100, "step"), (100, 110, "stamp"),
        (110, 115, "host"), (115, 120, "submit")]


def test_idle_attribution_by_hand():
    idle = [(5, 20), (45, 50), (85, 105), (112, 120)]
    got = spans.attribute(idle, spans.timeline(HAND, 0, 120))
    want = {"step": 15, "engine.step": 4, "engine.schedule": 3,
            "engine.prefill": 5, "engine.readback": 5,
            "engine.bookkeep": 3, "stamp": 5, "host": 3, "submit": 5}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})


def test_self_seconds_by_hand():
    stats = spans.self_seconds(HAND, 0, 120)
    want = {"step": (100, 20), "engine.step": (80, 4),
            "engine.schedule": (18, 8), "engine.prefill": (10, 10),
            "engine.readback": (40, 40), "submit": (5, 5)}
    for name, (total, own) in want.items():
        assert stats[name]["count"] == 1
        assert stats[name]["seconds"] == pytest.approx(total / 1e9)
        assert stats[name]["self_seconds"] == pytest.approx(own / 1e9)


def test_within_pairs_a_step_with_its_phases():
    found = {"window": (0, 120),
             "spans": [s for s in HAND if s[2] in spans.PROGRAM_SPANS],
             "harness": [s for s in HAND if s[2] not in spans.PROGRAM_SPANS]}
    (outer, inner), = spans.within(found, "engine.step")
    assert outer == (10, 90, "engine.step")
    assert [n for _, _, n in inner] == [
        "engine.schedule", "engine.prefill", "engine.launch",
        "engine.readback", "engine.bookkeep"]
    (outer, inner), = spans.within(found, "step")
    assert len(inner) == 6
    assert spans.within(found, "submit") == []       # not wholly inside


def _recorded():
    """The recorded trace's window, device ops and the spans on the
    window's thread, read directly."""
    from jax.profiler import ProfileData

    names = set(spans.PROGRAM_SPANS) | set(profile.HOST_SPANS)
    ops, host = [], None
    for plane in ProfileData.from_file(str(DATA / "spans.xplane.pb")).planes:
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                ops += events
            if any(n == profile.WINDOW_SPAN for _, _, n in events):
                host = events
    window, = [(a, b) for a, b, n in host if n == profile.WINDOW_SPAN]
    return window, ops, [s for s in host if s[2] in names]


def test_recorded_trace_by_hand_counts():
    """On the chip's trace: idle time under each name, counted on a 10 ns
    grid by painting harness spans, then program spans in order of start
    (a nested span starts later and paints over its parent); self times
    as duration less the program spans inside."""
    found = spans.read(str(DATA / "spans.xplane.pb"))
    (lo, hi), ops, named = _recorded()
    assert {n for _, _, n in named} >= {"call", "rnn.plan", "rnn.operands",
                                        "rnn.launch"}
    cells = int((hi - lo) // 10)

    def cell(t):
        return min(max(int((t - lo) // 10), 0), cells)

    busy = np.zeros(cells, bool)
    for a, b, _ in ops:
        busy[cell(a):cell(b)] = True
    label = np.full(cells, "host", dtype=object)
    for program in (False, True):
        for a, b, n in sorted(named):
            if (n in spans.PROGRAM_SPANS) == program:
                label[cell(a):cell(b)] = n
    want = {n: 10e-9 * int(np.sum(~busy & (label == n)))
            for n in set(label[~busy])}
    assert found["n_devices"] == 1
    assert found["idle_s"] == pytest.approx(sum(want.values()), abs=1e-6)
    assert set(found["idle"]) == set(want)
    for n, s in want.items():
        assert found["idle"][n] == pytest.approx(s, abs=1e-6)
    assert 0 < found["idle"].get("host", 0.0) < 0.2 * found["idle_s"]
    (o, inner), = spans.within(found, "call")      # one request
    assert [n for _, _, n in inner] == ["rnn.plan", "rnn.operands",
                                        "rnn.launch"]
    assert found["stats"]["call"]["count"] == 1
    assert found["stats"]["call"]["self_seconds"] == pytest.approx(
        (o[1] - o[0] - sum(b - a for a, b, _ in inner)) / 1e9)
    for n in ("rnn.plan", "rnn.operands", "rnn.launch"):
        assert found["stats"][n]["self_seconds"] == pytest.approx(
            found["stats"][n]["seconds"])


def _ctx(tmp_path, trace):
    """A reader's context whose profiler directory holds ``trace``."""
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(trace, where / "host.xplane.pb")
    lines = []
    return SimpleNamespace(profiler=SimpleNamespace(directory=str(tmp_path)),
                           log=lines.append, lines=lines)


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    """The trace of a program that has no spans (``rnn.xplane.pb``) gives
    no metric, and its idle time all falls under harness spans or host."""
    ctx = _ctx(tmp_path, DATA / "rnn.xplane.pb")
    reader = load_module(tiny.BENCH / "layers" / "host_ms.rnn.py")
    assert reader.read(None, None, ctx) is None
    found = spans.read(spans.trace_path(ctx))
    assert found["spans"] == [] and found["n_devices"] == 1
    assert set(found["idle"]) <= set(profile.HOST_SPANS) | {"host"}
    red = profile.reduce(spans.trace_path(ctx))
    assert found["idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-6)
    assert len(ctx.lines) == 1 and ctx.lines[0].startswith("device idle")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("spans"))


@pytest.mark.parametrize("cell,metric", [
    ("tiny-rwkv.tiny-closed", "host_ms.decode"),
    ("tiny-rnn.tiny-b1", "host_ms.rnn")])
def test_tiny_cells_read_host_ms(tiny_root, cell, metric):
    line = tiny.run(tiny_root, cell, seconds=2.0, trace=True)
    assert line["correct"]
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"][metric]["unit"] == "ms"
