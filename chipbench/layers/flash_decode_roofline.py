"""The ``flash_decode`` kernel's share of its roofline, per chip: the
least time to read the live KV cache of the traced ticks
(``engine.kv_tokens``, counted by ``work/flash_decode.py`` at the chip's
peaks) spread evenly over the chips, over the device time of the ops
named ``flash_decode`` on each chip, in %."""

from chipbench.core import profile
from chipbench.core.harness import load_module
from chipbench.core.readers import traced


def read(run, system, ctx):
    info = traced(run)
    history = getattr(getattr(system, "engine", None), "kv_history", None)
    if info is None or not run.trace or history is None:
        return None
    secs, calls = profile.kernel_time(run.trace, ("flash_decode",))
    tokens = sum(history[info["c0"]["ticks"]:info["c1"]["ticks"]])
    if calls == 0 or secs <= 0 or tokens == 0:
        return None
    m = ctx.config["model"]
    work = load_module(ctx.root / "chipbench" / "work" / "flash_decode.py")
    least = work.least_seconds(work.call(
        tokens=tokens, layers=m["n_layers"], heads=m["n_heads"],
        kv_heads=m["n_kv_heads"], head_dim=m["head_dim"]), ctx.peaks)
    return 100.0 * least / len(ctx.devices) / secs
