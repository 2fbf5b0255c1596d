"""The ``rwkv6_step`` kernel's share of its roofline: the least time its
calls in the traced part could take (``work/rwkv6_step.py`` at the chip's
peaks) over their device time in the trace, in %."""

from chipbench.core import profile
from chipbench.core.harness import load_module


def read(run, system, ctx):
    if not run.trace:
        return None
    secs, calls = profile.kernel_time(run.trace, ("rwkv6_step",))
    if calls == 0 or secs <= 0:
        return None
    work = load_module(ctx.root / "chipbench" / "work" / "rwkv6_step.py")
    m = ctx.config["model"]
    least = work.least_seconds(
        work.call(batch=system.max_batch, heads=m["n_heads"],
                  key=m["head_dim"], value=m["head_dim"], tokens=1),
        ctx.peaks)
    return 100.0 * least * calls / secs
