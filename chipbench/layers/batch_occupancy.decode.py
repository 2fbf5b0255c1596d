"""Mean share of the engine's slots in use over the ticks of the traced
part (the engine's ``util_history``), in %."""

from chipbench.core.readers import traced


def read(run, system, ctx):
    info = traced(run)
    if info is None:
        return None
    util = system.engine.util_history[info["c0"]["ticks"]:
                                      info["c1"]["ticks"]]
    return 100.0 * sum(util) / len(util) if util else None
