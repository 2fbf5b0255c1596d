"""Closed loop: ``clients`` callers, each sending its next request as soon
as its previous one completes.

Sizes come from ``core.traffic.fixed_sizes`` (one master draw, shuffled by
the seed); token ids from the seed.  Every client sends its first request
at the window's start, and each later one when its previous one
completes.  The window closes after ``--seconds``; requests still running
then are neither finished nor failed, and nothing is drained.
"""

from __future__ import annotations

import numpy as np

from chipbench.core import harness, traffic, weights
from chipbench.core.loop import (LMRequest, TraceWindow, after_step, now,
                                 span)


def run(system, ctx, compiles) -> harness.Run:
    tr = ctx.traffic
    sizes = traffic.fixed_sizes(int(tr["pool"]), tr["prompt"], tr["output"],
                                weights.seed32(ctx.seed))
    rng = np.random.default_rng(weights.seed32(ctx.seed) + 11)
    prompts = [traffic.token_ids(rng, p, system.vocab) for p, _ in sizes]
    recs, live = [], []

    def send() -> None:
        i = len(recs)
        prompt, (_, n_out) = prompts[i % len(prompts)], sizes[i % len(sizes)]
        with span("submit"):
            req = system.submit(prompt, n_out)
        rec = LMRequest(i, prompt, req)
        recs.append(rec)
        live.append(rec)

    compiles.open()
    t0 = now()
    end = t0 + float(ctx.seconds)
    window = TraceWindow(ctx, system, t0)
    for _ in range(int(tr["clients"])):
        send()
    while True:
        t = now()
        if t >= end:
            break
        window.poll(t)
        with span("step"):
            system.step()
        t1 = now()
        with span("stamp"):
            finished = after_step(live, t, t1)
        for rec in finished:
            live.remove(rec)
            send()
    n_compiles = compiles.close()
    trace = window.reduce()
    return harness.Run(window_start=t0, window_end=end, requests=recs,
                       attempted=len(recs), failed=0, trace=trace,
                       traced=window.info,
                       extra={"compiles": n_compiles})
