"""The program's own spans in a traced run, and the device idle time under
them.

The program marks the phases of the serving engine's step (``engine.*``)
and of one fused RNN request (``rnn.*``) with host spans on the
profiler's clock (``PROGRAM_SPANS``; the program lists the same names in
``repro.obs.spans.SPANS``).  :func:`read` reads the traced run's
``.xplane.pb``, clips to the ``profile.WINDOW_SPAN`` span, and returns:

* per span name, program and harness (``profile.HOST_SPANS``): its
  count, seconds, and self seconds (its time less what the program spans
  inside it cover);
* the device idle seconds under each name: each idle nanosecond goes to
  the innermost program span covering it, else to the harness span
  covering it, else to ``host``;
* the spans themselves, unclipped, for readers that pair them (a step
  with its readback, a request with its phases).

Spans are read from the thread that holds the window span: the harness
calls the program from it.  Other threads' events, some of which share a
harness span's name (the CPU client's ``call``), are left out.

A program without these spans, such as an older tree, gives none: the
readers built on them then return None.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.core import profile

PROGRAM_SPANS = ("engine.step", "engine.schedule", "engine.prefill",
                 "engine.launch", "engine.readback", "engine.bookkeep",
                 "rnn.plan", "rnn.operands", "rnn.launch")
HOST = "host"

Span = Tuple[int, int, str]      # start ns, end ns, name


def trace_path(ctx) -> Optional[str]:
    """The traced run's ``.xplane.pb`` (where ``Profiler.stop`` finds
    it), or None."""
    if ctx.profiler is None:
        return None
    found = sorted(glob.glob(os.path.join(
        ctx.profiler.directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def timeline(spans: Sequence[Span], lo: int, hi: int) -> List[Span]:
    """``[lo, hi]`` cut into segments, each named by the innermost program
    span covering it (the one that started last), else the harness span
    covering it, else ``HOST``."""
    marks = []
    for i, (a, b, _) in enumerate(spans):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            marks += [(a, 1, i), (b, 0, i)]
    marks.sort()                # at one instant, ends before starts
    out: List[Span] = []
    active: set = set()

    def cover(t0: int, t1: int) -> None:
        name = HOST
        if active:
            name = spans[max(active, key=lambda k: (
                spans[k][2] in PROGRAM_SPANS, spans[k][0]))][2]
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))

    t = lo
    for at, starts, i in marks:
        if at > t:
            cover(t, at)
            t = at
        if starts:
            active.add(i)
        else:
            active.discard(i)
    if hi > t:
        cover(t, hi)
    return out


def attribute(idle: Sequence[Tuple[int, int]],
              segments: Sequence[Span]) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``idle`` intervals under each
    segment's name."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s0, s1, name = segments[k]
            over = min(b, s1) - max(a, s0)
            if over > 0:
                out[name] = out.get(name, 0.0) + over / 1e9
            k += 1
    return out


def self_seconds(spans: Sequence[Span], lo: int,
                 hi: int) -> Dict[str, Dict[str, float]]:
    """Count, seconds and self seconds per name of the spans clipped to
    ``[lo, hi]``; a span's self time leaves out the program spans nested
    directly inside it (those nested deeper lie inside them)."""
    clipped = sorted(((max(a, lo), min(b, hi), n) for a, b, n in spans
                      if min(b, hi) > max(a, lo)),
                     key=lambda s: (s[0], -s[1]))
    stats: Dict[str, Dict[str, float]] = {}
    stack: List[List] = []       # [start, end, name, child ns]
    done: List[List] = []
    for a, b, name in clipped:
        while stack and stack[-1][1] <= a:
            done.append(stack.pop())
        if stack and b <= stack[-1][1] and name in PROGRAM_SPANS:
            stack[-1][3] += b - a
        stack.append([a, b, name, 0])
    done += stack
    for a, b, name, child in done:
        rec = stats.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "self_seconds": 0.0})
        rec["count"] += 1
        rec["seconds"] += (b - a) / 1e9
        rec["self_seconds"] += (b - a - child) / 1e9
    return stats


def read(path: str) -> Dict[str, object]:
    """Read one ``.xplane.pb`` trace (see the module docstring).  Device
    idle seconds are averaged over devices."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    spans: List[Span] = []
    busy: List[List[Tuple[int, int]]] = []
    for plane in data.planes:
        if profile._DEVICE_PLANE.match(plane.name):
            busy.append([(e.start_ns, e.start_ns + e.duration_ns)
                         for ln in plane.lines if ln.name in profile._OP_LINES
                         for e in ln.events])
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            named = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events if e.name == profile.WINDOW_SPAN
                     or e.name in PROGRAM_SPANS
                     or e.name in profile.HOST_SPANS]
            marks = [s for s in named if s[2] == profile.WINDOW_SPAN]
            if marks:       # the harness's thread, which runs the program
                window = marks[0][:2]
                spans = [s for s in named if s[2] != profile.WINDOW_SPAN]
    if window is None:
        raise RuntimeError(f"no {profile.WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    spans.sort()
    segments = timeline(spans, lo, hi)
    idle: Dict[str, float] = {}
    for intervals in busy:
        clipped = [iv for iv in (profile._clip(a, b, lo, hi)
                                 for a, b in intervals) if iv]
        edges = [lo] + [x for iv in profile._union(clipped)
                        for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, s in attribute(gaps, segments).items():
            idle[name] = idle.get(name, 0.0) + s / len(busy)
    return {"window": window, "window_s": (hi - lo) / 1e9,
            "n_devices": len(busy),
            "spans": [s for s in spans if s[2] in PROGRAM_SPANS],
            "harness": [s for s in spans if s[2] not in PROGRAM_SPANS],
            "stats": self_seconds(spans, lo, hi),
            "idle": idle, "idle_s": sum(idle.values())}


def within(found: Dict[str, object], outer: str) -> List[Tuple[Span,
                                                                List[Span]]]:
    """Each span named ``outer`` (program or harness) that lies wholly in
    the window, with the program spans inside it."""
    lo, hi = found["window"]
    outers = [s for s in found["spans"] + found["harness"]
              if s[2] == outer and lo <= s[0] and s[1] <= hi]
    inner = found["spans"]              # sorted by start
    starts = [s[0] for s in inner]
    out = []
    for o in outers:
        i, j = bisect.bisect_left(starts, o[0]), bisect.bisect_right(
            starts, o[1])
        out.append((o, [s for s in inner[i:j] if s[1] <= o[1]
                        and s is not o]))
    return out


def describe(found: Dict[str, object]) -> str:
    """One line: the share of device idle time under each span name and
    under ``host``, then each name's count and mean and self ms."""
    idle_s = found["idle_s"]
    shares = ", ".join(
        f"{name} {100.0 * s / idle_s:.1f}%"
        for name, s in sorted(found["idle"].items(), key=lambda kv: -kv[1])
    ) if idle_s > 0 else "none"
    spans = ", ".join(
        f"{name} {int(r['count'])}x {1e3 * r['seconds'] / r['count']:.4f}"
        f"/{1e3 * r['self_seconds'] / r['count']:.4f}"
        for name, r in sorted(found["stats"].items()))
    return (f"device idle {idle_s:.4f} s of {found['window_s']:.4f} s by "
            f"span: {shares} | spans (count, mean/self ms): {spans}")


def mean_ms(durations_ns: Sequence[int]) -> Optional[float]:
    """The mean of nanosecond durations in ms; None where there are none."""
    return sum(durations_ns) / len(durations_ns) / 1e6 \
        if durations_ns else None
