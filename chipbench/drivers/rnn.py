"""The batch-1 RNN request loop: one client, requests back to back.

Requests cycle the configuration's tasks in an order drawn from the seed.
Each request's input is made on the device before it is sent; the request
is timed from the call into the program to ``block_until_ready`` on its
output.  The window closes after ``--seconds``; the request running then
finishes and counts.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.core import harness, weights
from chipbench.core.loop import TraceWindow, now, span


@dataclasses.dataclass
class RNNRequest:
    index: int
    task: int
    start: float
    end: float


def run(system, ctx, compiles) -> harness.Run:
    n = len(system.tasks)
    order = np.random.default_rng(weights.seed32(ctx.seed)).permutation(n)
    recs, failed = [], 0
    compiles.open()
    t0 = now()
    end = t0 + float(ctx.seconds)
    window = TraceWindow(ctx, system, t0)
    j = 0
    while now() < end:
        i = int(order[j % n])
        with span("input"):
            x = system.make_input(i, j)
        window.poll(now())
        with span("call"):
            t1 = now()
            y = system.call(i, x)
        with span("sync"):
            y.block_until_ready()
            t2 = now()
        recs.append(RNNRequest(j, i, t1, t2))
        system.keep(i, j, y)
        j += 1
    n_compiles = compiles.close()
    trace = window.reduce()
    for i, name in enumerate(system.names):
        lat = [r.end - r.start for r in recs if r.task == i]
        if lat:
            ctx.log(f"task {name}: {len(lat)} requests, mean "
                    f"{1e3 * sum(lat) / len(lat):.4f} ms")
    return harness.Run(window_start=t0, window_end=end, requests=recs,
                       attempted=len(recs), failed=failed, trace=trace,
                       traced=window.info, extra={"compiles": n_compiles})
