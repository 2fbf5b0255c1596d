"""A language model served by the program's engine.

Built through the entry points a user calls: ``launch.serve.resolve_plan``
(with the planner's tile plans for the attached chip), ``models.lm
.build_model`` and ``ServingEngine.from_plan``.  The weights are the
benchmark's: made on the device from the seed by ``core.weights`` with the
configuration's ``init`` rules, in the program's parameter layout.

The check runs the configuration's plain reference over a sample of the
finished requests (prompt and served tokens) and reads, at every served
position, how far the served token's reference logit lies below the
reference's best; ``mean_logit_gap`` is its mean.  With ``ctx.control``
the control stands in the program's place: ``mean_logit_gap`` is then the
same read of the token that the reference computed in the control
precision puts first, held to the same limit, and the program's own
reading is printed beside it (``program_mean_logit_gap``, no limit).
"""

from __future__ import annotations

import dataclasses
import gc
from typing import Any, Dict, List

import numpy as np

from chipbench.core import harness, weights


# the compared number of a run that finished no request: fails any limit
NOTHING_COMPARED = 1e9


def build(ctx) -> "LMSystem":
    return LMSystem(ctx)


class LMSystem:
    def __init__(self, ctx):
        from jax.sharding import Mesh, SingleDeviceSharding

        from repro.configs import get_config
        from repro.dist.sharding import make_sharder
        from repro.launch.serve import build_parser, resolve_plan
        from repro.models.lm import build_model
        from repro.obs.trace import Tracer
        from repro.serving import ServingEngine

        cfg = ctx.config
        argv = ["--arch", cfg["arch"], "--seed", str(weights.seed32(ctx.seed))]
        for key, value in cfg["plan"].items():
            argv += ["--" + key.replace("_", "-"), str(value)]
        parser = build_parser()
        self.plan = resolve_plan(parser.parse_args(argv), parser)
        mcfg = dataclasses.replace(get_config(self.plan.arch),
                                   **cfg.get("model_overrides", {}))
        for key, value in cfg["model"].items():
            if getattr(mcfg, key) != value:
                raise ValueError(f"{cfg['name']}: the program runs {key}="
                                 f"{getattr(mcfg, key)!r}, the configuration "
                                 f"file says {value!r}")
        self.mcfg = mcfg
        model = build_model(mcfg)
        n = len(ctx.devices)
        if n > 1:
            mesh = Mesh(np.asarray(ctx.devices).reshape(1, n),
                        ("data", "model"))
            sharder = make_sharder(mcfg, mesh, self.plan.shard_mode)
            shardings = sharder.param_shardings(model.param_specs())
        else:
            sharder = make_sharder(mcfg, None, self.plan.shard_mode)
            shardings = SingleDeviceSharding(ctx.devices[0])
        ctx.log(f"plan: {self.plan.summary()}; tile plans "
                f"{dict(self.plan.tile_plans)}")
        self.params = weights.make(model.abstract_params(), cfg["init"],
                                   ctx.seed, shardings)
        self.tracer = Tracer() if ctx.trace else None
        self.engine = ServingEngine.from_plan(
            self.plan, self.params, model=model, sharder=sharder,
            seed=weights.seed32(ctx.seed), tracer=self.tracer)
        self.vocab = mcfg.vocab_size
        self.max_batch = self.plan.max_batch
        self._warmup(ctx)

    # ------------------------------------------------------------ serving
    def buckets(self, lo: int, hi: int) -> List[int]:
        return sorted({self.engine.bucket(n) for n in range(lo, hi + 1)})

    def _warmup(self, ctx) -> None:
        """Compile what the cell's traffic uses and nothing else: the
        decode program, the prefill of every bucket its prompts fall in,
        and the slot scatter for every admission group size 1..max_batch
        (smallest bucket)."""
        lo, hi = int(ctx.traffic["prompt"]["min"]), int(
            ctx.traffic["prompt"]["max"])
        buckets = self.buckets(lo, hi)
        rng = np.random.default_rng(weights.seed32(ctx.seed))
        first = max(lo, 1)
        for k in range(1, self.max_batch + 1):
            for _ in range(k):
                self.engine.submit(rng.integers(0, self.vocab, first).tolist(),
                                   max_new_tokens=3)
            self.engine.run()
        for b in buckets[1:]:
            n = min(b, hi)
            self.engine.submit(rng.integers(0, self.vocab, n).tolist(),
                               max_new_tokens=3)
            self.engine.run()
        ctx.log(f"warm-up: buckets {buckets}, admission groups "
                f"1..{self.max_batch}")

    def submit(self, prompt, max_new):
        return self.engine.submit(prompt, max_new_tokens=max_new)

    def step(self) -> None:
        self.engine.step()

    def has_work(self) -> bool:
        return self.engine.has_work()

    def counters(self) -> Dict[str, int]:
        return {"ticks": len(self.engine.util_history),
                "events": len(self.tracer.events) if self.tracer else 0}

    def release(self) -> None:
        """Free the engine's device state (the weights stay for the
        reference)."""
        self.engine = None
        gc.collect()

    # --------------------------------------------------------------- check
    def sample(self, run, ctx) -> List[Any]:
        """The finished requests to compare: the one with most served
        tokens, then others drawn from the seed, up to the cell's count."""
        done = [r for r in run.requests if r.done and r.output]
        if not done:
            return []
        spec = ctx.checks["sample"]
        longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                           r.index))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng(weights.seed32(ctx.seed) + 1)
        rng.shuffle(rest)
        return [longest] + rest[:int(spec["requests"]) - 1]

    def check(self, run, ctx) -> Dict[str, Dict[str, float]]:
        """``mean_logit_gap``: the mean, over the sample's served tokens,
        of the reference's best logit minus the served token's.  The
        widest gap and the share of served tokens off the reference's
        best are printed beside it."""
        ref = harness.load_module(ctx.root / "chipbench" / "reference" /
                                  f"{ctx.config['reference']}.py")
        picked = self.sample(run, ctx)
        limit = ctx.checks["limits"]["mean_logit_gap"]
        if not picked:
            return {"mean_logit_gap": {"value": NOTHING_COMPARED,
                                       "limit": limit}}
        seqs = [(list(r.prompt), list(r.output)) for r in picked]
        control = ctx.checks.get("control") if ctx.control else None
        gaps = ref.served_gaps(self.params, ctx.config["model"], seqs,
                               control=control,
                               block=int(ctx.checks["sample"].get("block", 4)))
        ctx.log(f"reference: {len(picked)} requests, {gaps['positions']} "
                f"served tokens; widest gap {gaps['served_widest']!r}, "
                f"{gaps['served_off_pct']!r}% off the reference's best")
        if not control:
            return {"mean_logit_gap": {"value": gaps["served"],
                                       "limit": limit}}
        ctx.log(f"control ({control}): widest gap "
                f"{gaps['control_widest']!r}, "
                f"{gaps['control_off_pct']!r}% off")
        return {"mean_logit_gap": {"value": gaps["control"], "limit": limit},
                "program_mean_logit_gap": {"value": gaps["served"],
                                           "limit": None}}
