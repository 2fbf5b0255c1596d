"""The fused RNN kernels' share of their roofline: the least time of the
requests served in the traced part (``work/fused_rnn.py`` at the chip's
peaks) over the device time of the ``fused_lstm*`` / ``fused_gru*`` ops
in the trace, in %."""

from chipbench.core import profile
from chipbench.core.harness import load_module
from chipbench.core.readers import traced


def read(run, system, ctx):
    info = traced(run)
    if info is None or not run.trace:
        return None
    secs, calls = profile.kernel_time(run.trace, ("fused_lstm", "fused_gru"))
    if calls == 0 or secs <= 0:
        return None
    work = load_module(ctx.root / "chipbench" / "work" / "fused_rnn.py")
    least = 0.0
    for r in run.requests:
        if info["t0"] <= r.start and r.end <= info["t1"]:
            t = system.tasks[r.task]
            least += work.least_seconds(work.call(
                cell=t["cell"], hidden=t["hidden"], features=t["hidden"],
                timesteps=t["timesteps"]), ctx.peaks)
    return 100.0 * least / secs if least > 0 else None
