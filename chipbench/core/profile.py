"""The device trace of a traced run, and its reduction to numbers.

A traced run (``--trace 1``) records a JAX profiler trace of part of its
window.  The harness wraps that part in the host span ``WINDOW_SPAN`` and
its own calls into the program in short spans (``submit``, ``step``,
``stamp``, ``input``, ``call``, ``sync``).  :func:`reduce` reads the
``.xplane.pb`` file and returns, per device and averaged over devices:

* busy seconds: the union of the intervals in which an op ran, inside the
  window; idle is the rest;
* device seconds and call count per op name;
* collective seconds (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute);
* the longest idle gaps, each named by the harness span that covers most
  of it on the host.

Host and device events share the profiler's clock (nanoseconds from the
start of the trace).
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"
HOST_SPANS = ("submit", "step", "stamp", "input", "call", "sync")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINES = ("XLA Ops",)
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?\s*=")
LABEL_CHARS = 200


class Profiler:
    """Start and stop the JAX profiler into ``directory`` (emptied first)."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, not every call
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.directory}")
        return found[-1]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def op_name(text: str) -> str:
    """The op an event stands for: the HLO instruction's name without its
    numeric suffix (``%rwkv6_step.5 = (...) custom-call(...)`` ->
    ``rwkv6_step``); the event name itself where it is not HLO text.
    Kernels and collectives are matched on this, never on the operands
    the text mentions."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce(path: str, host_spans: Sequence[str] = HOST_SPANS,
           top: int = 10) -> Dict[str, object]:
    """Reduce one ``.xplane.pb`` trace (see the module docstring).  Times
    in the result are seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in host_spans:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    window_s = (hi - lo) / 1e9
    spans.sort()
    per_device = []
    ops: Dict[str, List[float]] = {}
    gaps: List[Tuple[float, str]] = []
    for _, plane in sorted(devices, key=lambda x: x[0]):
        intervals, coll = [], []
        lines = [ln for ln in plane.lines if ln.name in _OP_LINES]
        for line in lines:
            for e in line.events:
                iv = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
                if iv is None:
                    continue
                intervals.append(iv)
                label = e.name[:LABEL_CHARS]
                rec = ops.setdefault(label, [0.0, 0, op_name(e.name)])
                rec[0] += (iv[1] - iv[0]) / 1e9
                rec[1] += 1
                if rec[2].startswith(COLLECTIVES):
                    coll.append(iv)
        busy = _union(intervals)
        busy_ns = sum(b - a for a, b in busy)
        coll_ns = sum(b - a for a, b in _union(coll))
        per_device.append({"busy_s": busy_ns / 1e9,
                           "collective_s": coll_ns / 1e9})
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a)
    n = max(1, len(per_device))
    gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": window_s,
        "n_devices": len(per_device),
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "collective_s": sum(d["collective_s"] for d in per_device) / n,
        "per_device": per_device,
        "ops": {k: {"seconds": v[0] / n, "calls": v[1] / n, "op": v[2]}
                for k, v in ops.items()},
        "device_ops": [[k, v[0] / n] for k, v in ranked[:top]],
        "idle_gaps": [[_cover(spans, a, b), g / 1e9] for g, a, b in gaps],
    }


def _cover(spans: Sequence[Tuple[float, float, str]], a: float,
           b: float) -> str:
    """The host span that overlaps ``[a, b]`` most ("host" where none)."""
    best, name = 0.0, "host"
    for s0, s1, s_name in spans:
        if s0 >= b:
            break
        over = min(s1, b) - max(s0, a)
        if over > best:
            best, name = over, s_name
    return name


def kernel_time(reduced: Dict[str, object],
                names: Sequence[str]) -> Tuple[float, float]:
    """(device seconds, calls) of the ops named by any of ``names`` (a
    kernel's ``name=``, matched at the start of the op's name, so that
    ``fused_gru`` also finds ``fused_gru_persistent``), averaged over
    devices."""
    secs, calls = 0.0, 0.0
    for rec in reduced["ops"].values():
        if any(rec["op"].startswith(n) for n in names):
            secs += rec["seconds"]
            calls += rec["calls"]
    return secs, calls
