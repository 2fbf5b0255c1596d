"""End-to-end serving driver (the paper's workload kind, on a transformer):
batched requests through the continuous-batching engine.

  PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-12b]
      [--requests 12]
"""

import argparse
import time

import jax
import numpy as np

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.testing import reduced_config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = ServingPlan(arch=args.arch, max_batch=4, max_len=48,
                       temperature=0.8, top_k=20)
    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=Sharder(None, {}))
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, cfg.vocab_size,
                                       rng.integers(4, 16)).tolist(),
                          max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    t0 = time.time()
    engine.run()
    dt = time.time() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.output) for r in reqs)
    print(f"{cfg.name}: {done}/{len(reqs)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s (CPU, reduced config)")


if __name__ == "__main__":
    main()
