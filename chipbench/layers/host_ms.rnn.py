"""Host time of one RNN request in the program: the mean, over the
requests wholly in the traced part (one harness ``call`` span each), of
the request's ``rnn.plan`` + ``rnn.operands`` + ``rnn.launch`` time, in
ms.  Also logs which spans the device's idle time falls under."""

from chipbench.core import spans

PHASES = ("rnn.plan", "rnn.operands", "rnn.launch")


def read(run, system, ctx):
    path = spans.trace_path(ctx)
    if path is None:
        return None
    found = spans.read(path)
    ctx.log(spans.describe(found))
    host = [sum(b - a for a, b, n in inner if n in PHASES)
            for _, inner in spans.within(found, "call")
            if any(n in PHASES for _, _, n in inner)]
    return spans.mean_ms(host)
