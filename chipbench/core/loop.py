"""What the request loops share: host-clock records, the harness's own
spans, and the traced part of the window."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

from chipbench.core import profile

now = time.perf_counter


def span(name: str):
    """A host span in the profiler's trace (nearly free when no trace is
    being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass(eq=False)
class LMRequest:
    """One served request, stamped on the host clock."""

    index: int
    prompt: List[int]
    req: Any                        # the engine's Request
    stamps: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    admit: Optional[float] = None   # start of the step that admitted it
    seen: int = 0

    @property
    def output(self) -> List[int]:
        return self.req.output

    @property
    def done(self) -> bool:
        return bool(self.req.done)


def after_step(live: List[LMRequest], t_step: float,
               t_now: float) -> List[LMRequest]:
    """Stamp every token a step returned (tokens of one readback share a
    stamp) and the step that admitted each request; return those that
    completed."""
    finished = []
    for r in live:
        n = len(r.req.output)
        if n > r.seen:
            r.stamps.append((t_now, n - r.seen))
            r.seen = n
        if r.admit is None and r.req.t_admit is not None:
            r.admit = t_step
        if r.req.done:
            finished.append(r)
    return finished


class TraceWindow:
    """The traced part of a run's window: ``trace_seconds`` of the
    traffic file (default 5) in the middle of the window, wrapped in the
    ``profile.WINDOW_SPAN`` host span.  Inactive unless ``--trace 1``."""

    def __init__(self, ctx, system, t0: float):
        self.ctx, self.system = ctx, system
        tw = min(float(ctx.seconds),
                 float(ctx.traffic.get("trace_seconds", 5.0)))
        self.start_at = t0 + (float(ctx.seconds) - tw) / 2.0
        self.stop_at = self.start_at + tw
        self.state = "before" if ctx.trace else "off"
        self.info: Dict[str, Any] = {}
        self._ann = None
        self._path = None

    def poll(self, t: float) -> None:
        if self.state == "before" and t >= self.start_at:
            self.ctx.profiler.start()
            self._ann = span(profile.WINDOW_SPAN)
            self._ann.__enter__()
            self.info["t0"] = now()
            self.info["c0"] = self.system.counters()
            self.state = "on"
        elif self.state == "on" and t >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        self.info["t1"] = now()
        self.info["c1"] = self.system.counters()
        self._ann.__exit__(None, None, None)
        self._path = self.ctx.profiler.stop()
        self.state = "done"

    def reduce(self) -> Optional[Dict[str, Any]]:
        self.stop()
        if self._path is None:
            return None
        return profile.reduce(self._path)
