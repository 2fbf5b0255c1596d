"""Host time of the serving engine's step: the mean, over the
``engine.step`` spans wholly in the traced part, of the step's time less
its ``engine.readback`` (the wait for the device), in ms.  Also logs
which spans the device's idle time falls under."""

from chipbench.core import spans


def read(run, system, ctx):
    path = spans.trace_path(ctx)
    if path is None:
        return None
    found = spans.read(path)
    ctx.log(spans.describe(found))
    host = [(o[1] - o[0]) - sum(b - a for a, b, n in inner
                                if n == "engine.readback")
            for o, inner in spans.within(found, "engine.step")]
    return spans.mean_ms(host)
