"""Model FLOPs of the tokens the traced part processed over its length
times the bf16 peak of the chips, in %."""

from chipbench.core.readers import lm_mfu


def read(run, system, ctx):
    return lm_mfu(run, system, ctx)
