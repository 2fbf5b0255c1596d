"""Share of the traced part in which no op ran on the device, in %."""

from chipbench.core.readers import idle_pct


def read(run, system, ctx):
    return idle_pct(run)
