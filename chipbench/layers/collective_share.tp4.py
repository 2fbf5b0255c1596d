"""Share of each chip's busy time in the traced part spent in collectives
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute),
averaged over the chips, in %."""


def read(run, system, ctx):
    per = (run.trace or {}).get("per_device") or []
    shares = [d["collective_s"] / d["busy_s"] for d in per if d["busy_s"] > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
