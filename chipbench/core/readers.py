"""Arithmetic the metric and layer readers share."""

from __future__ import annotations

import math
from typing import Dict, Iterable, Optional


def tokens_in(run, t0: float, t1: float) -> int:
    """Output tokens whose readback was stamped in ``[t0, t1]``."""
    return sum(n for r in run.requests for t, n in r.stamps if t0 <= t <= t1)


def idle_pct(run) -> Optional[float]:
    tr = run.trace
    if not tr or tr["window_s"] <= 0 or tr["n_devices"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def traced(run) -> Optional[Dict]:
    """The traced part's host stamps and counters, or None."""
    info = run.traced
    return info if info and "t1" in info else None


def lm_mfu(run, system, ctx) -> Optional[float]:
    """Model FLOPs of the tokens the traced part processed (prompt tokens
    of the requests it admitted, plus every output token it delivered)
    over its length times the chips' bf16 peak."""
    info = traced(run)
    if info is None:
        return None
    from chipbench.core.harness import load_module

    work = load_module(ctx.root / "chipbench" / "work" /
                       f"{ctx.config['work']}.py")
    t0, t1 = info["t0"], info["t1"]
    prompt = sum(len(r.prompt) for r in run.requests
                 if r.admit is not None and t0 <= r.admit <= t1)
    tokens = prompt + tokens_in(run, t0, t1)
    if tokens == 0:
        return None
    flops = tokens * work.flops_per_token(ctx.config["model"])
    chips = len(ctx.devices)
    return 100.0 * flops / ((t1 - t0) * chips * ctx.peaks["bf16_flops"])


def geomean(xs: Iterable[float]) -> float:
    xs = list(xs)
    return math.exp(sum(math.log(x) for x in xs) / len(xs))
