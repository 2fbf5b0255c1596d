"""Geometric mean over the tasks of each task's mean request latency in
the window (the paper's Table 6 geomean), in ms."""

from chipbench.core.readers import geomean


def value(run, ctx):
    per_task = {}
    for r in run.requests:
        per_task.setdefault(r.task, []).append(r.end - r.start)
    if len(per_task) < len(ctx.config["tasks"]):
        return None
    return geomean(1e3 * sum(v) / len(v) for v in per_task.values())
