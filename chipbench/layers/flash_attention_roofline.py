"""The ``flash_attention`` kernel's share of its roofline: the least time
of the causal attention of the real prompt tokens prefilled in the traced
ticks (``engine.prefill_tokens``' lengths, counted by
``work/flash_attention.py`` at the chip's peaks) spread evenly over the
chips, over the device time of the ops named ``flash_attention*`` on
each chip, in %.  Padding rows and positions do not count."""

from chipbench.core import profile
from chipbench.core.harness import load_module
from chipbench.core.readers import traced


def read(run, system, ctx):
    info = traced(run)
    history = getattr(getattr(system, "engine", None), "prefill_history",
                      None)
    if info is None or not run.trace or history is None:
        return None
    secs, calls = profile.kernel_time(run.trace, ("flash_attention",))
    lengths = [n for tick in history[info["c0"]["ticks"]:info["c1"]["ticks"]]
               for n in tick]
    if calls == 0 or secs <= 0 or not lengths:
        return None
    m = ctx.config["model"]
    work = load_module(ctx.root / "chipbench" / "work" /
                       "flash_attention.py")
    least = sum(work.least_seconds(work.call(
        length=n, layers=m["n_layers"], heads=m["n_heads"],
        kv_heads=m["n_kv_heads"], head_dim=m["head_dim"]), ctx.peaks)
        for n in lengths)
    return 100.0 * least / len(ctx.devices) / secs
