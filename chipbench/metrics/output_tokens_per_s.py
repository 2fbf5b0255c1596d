"""All output tokens delivered in the window over the window's seconds."""

from chipbench.core.readers import tokens_in


def value(run, ctx):
    return tokens_in(run, run.window_start, run.window_end) / run.seconds
