"""Gate-product operations of the requests served in the traced part
over its length times the chip's int8 peak, in %."""

from chipbench.core.harness import load_module
from chipbench.core.readers import traced


def read(run, system, ctx):
    info = traced(run)
    if info is None:
        return None
    work = load_module(ctx.root / "chipbench" / "work" / "fused_rnn.py")
    ops = 0.0
    for r in run.requests:
        if info["t0"] <= r.start and r.end <= info["t1"]:
            t = system.tasks[r.task]
            ops += work.call(cell=t["cell"], hidden=t["hidden"],
                             features=t["hidden"],
                             timesteps=t["timesteps"])["ops"]
    if ops == 0:
        return None
    return 100.0 * ops / ((info["t1"] - info["t0"]) * len(ctx.devices)
                          * ctx.peaks["int8_ops"])
