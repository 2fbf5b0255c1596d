"""The paper's DeepBench RNN cells, served one request at a time through
``core.cells.serve(impl="kernel")``: the fused Pallas kernel at the tile
the program's DSE picks for batch 1.

The weights are the benchmark's: per task, f32 master weights (uniform
+-1/sqrt(H + D)) and biases (normal, ``bias_std``) drawn on the device
from the seed in one jitted call, then rounded to int8 with a
per-(gate, unit) scale over the contraction axis: the layout the
program's kernel path reads.  A request's input (T, 1, D) bf16 is drawn
on the device from the seed and the request's index.

The check keeps, per task, a reservoir of ``sample.per_task`` finished
requests drawn from the seed, and compares each request's whole output
sequence with the plain reference (``reference/rnn.py``) on the same int8
weights: ``rnn_max_abs_gap`` is the widest absolute difference.  With
``ctx.control`` the reference with int4 weights stands in the program's
place: ``rnn_max_abs_gap`` is then its widest difference from the int8
reference, held to the same limit, and the program's own reading is
printed beside it (``program_max_abs_gap``, no limit).  The f32 master
weights, from which the int4 ones are rounded, are kept only then.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from chipbench.core import harness, weights


def build(ctx) -> "RNNSystem":
    return RNNSystem(ctx)


class RNNSystem:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core.cells import RNNCellConfig, serve

        self._serve = serve
        cfg = ctx.config
        self.tasks = cfg["tasks"]
        self.names = [f"{t['cell']}-h{t['hidden']}-t{t['timesteps']}"
                      for t in self.tasks]
        self.cfgs = [RNNCellConfig(t["cell"], t["hidden"],
                                   timesteps=t["timesteps"], batch=1,
                                   precision=cfg["precision"]["weights"])
                     for t in self.tasks]
        self.device = ctx.devices[0]
        bias_std = float(cfg["init"]["bias_std"])
        x_std = float(cfg["init"]["input_std"])
        shapes = [(t["cell"], t["hidden"], t["hidden"]) for t in self.tasks]
        keep_masters = bool(ctx.control and ctx.checks.get("control_bits"))

        def gen(key):
            masters, served = [], []
            for i, (cell, H, D) in enumerate(shapes):
                G = 4 if cell == "lstm" else 3
                k = jax.random.split(jax.random.fold_in(key, i), 4)
                s = 1.0 / math.sqrt(H + D)
                m = {"w_x": jax.random.uniform(k[0], (D, G, H), jnp.float32,
                                               -s, s),
                     "w_h": jax.random.uniform(k[1], (H, G, H), jnp.float32,
                                               -s, s),
                     "b": bias_std * jax.random.normal(k[2], (G, H))}
                if cell == "gru":
                    m["b_h"] = bias_std * jax.random.normal(k[3], (G, H))
                w = dict(m)
                for name in ("w_x", "w_h"):
                    amax = jnp.max(jnp.abs(m[name]), axis=0, keepdims=True)
                    scale = jnp.maximum(amax, 1e-8) / 127.0
                    w[name] = jnp.clip(jnp.round(m[name] / scale), -127,
                                       127).astype(jnp.int8)
                    w[name + "_scale"] = scale[0]
                masters.append(m)
                served.append(w)
            return (masters if keep_masters else None), served

        fn = jax.jit(gen, out_shardings=jax.sharding.SingleDeviceSharding(
            self.device))
        self.masters, self.w = jax.block_until_ready(
            fn(jax.random.PRNGKey(weights.seed32(ctx.seed))))
        self.x_key = jax.random.PRNGKey(weights.seed32(ctx.seed) ^ 0x5EED)
        self._input = [jax.jit(self._input_fn(t["timesteps"], t["hidden"],
                                              x_std))
                       for t in self.tasks]
        self.kept: Dict[int, List] = {i: [] for i in range(len(self.tasks))}
        self.seen = [0] * len(self.tasks)
        self.per_task = int(ctx.checks["sample"]["per_task"])
        self.rng = np.random.default_rng(weights.seed32(ctx.seed) + 3)
        for i in range(len(self.tasks)):         # warm-up: every task once
            jax.block_until_ready(self.call(i, self.make_input(i, -1 - i)))
        ctx.log(f"warm-up: {len(self.tasks)} tasks")

    @staticmethod
    def _input_fn(T: int, D: int, std: float):
        import jax
        import jax.numpy as jnp

        def f(key, j):
            k = jax.random.fold_in(key, j)
            return (std * jax.random.normal(k, (T, 1, D))).astype(
                jnp.bfloat16)
        return f

    def make_input(self, i: int, j: int):
        import jax

        return jax.block_until_ready(self._input[i](self.x_key, j))

    def call(self, i: int, x):
        return self._serve(self.cfgs[i], self.w[i], x, impl="kernel")

    def keep(self, i: int, j: int, y) -> None:
        """Reservoir-sample the finished request (task ``i``, index ``j``)."""
        self.seen[i] += 1
        kept = self.kept[i]
        if len(kept) < self.per_task:
            kept.append((j, y))
        else:
            slot = int(self.rng.integers(0, self.seen[i]))
            if slot < self.per_task:
                kept[slot] = (j, y)

    def counters(self) -> Dict[str, int]:
        return {}

    def release(self) -> None:
        pass

    def check(self, run, ctx) -> Dict[str, Dict[str, float]]:
        import jax.numpy as jnp

        ref = harness.load_module(ctx.root / "chipbench" / "reference" /
                                  f"{ctx.config['reference']}.py")
        limit = ctx.checks["limits"]["rnn_max_abs_gap"]
        gap, ctl, n = 0.0, 0.0, 0
        bits = ctx.checks.get("control_bits") if ctx.control else None
        for i, kept in self.kept.items():
            cell = self.tasks[i]["cell"]
            w = dict(self.w[i])
            wr = {k: v for k, v in w.items() if not k.endswith("_scale")}
            for name in ("w_x", "w_h"):
                wr[name] = ref.widen(w[name], w[name + "_scale"])
            wc = None
            if bits:
                wc = dict(wr)
                for name in ("w_x", "w_h"):
                    wc[name] = ref.widen(*ref.quantize(self.masters[i][name],
                                                       int(bits)))
            for j, y in kept:
                x = self.make_input(i, j)
                yr = ref.run(cell, wr, x)
                gap = max(gap, float(jnp.max(jnp.abs(
                    y.astype(jnp.float32) - yr))))
                if wc is not None:
                    yc = ref.run(cell, wc, x)
                    ctl = max(ctl, float(jnp.max(jnp.abs(yc - yr))))
                n += 1
        if n < len(self.tasks):
            gap = ctl = 1e9     # a task with no finished request is no pass
        if not bits:
            return {"rnn_max_abs_gap": {"value": gap, "limit": limit,
                                        "requests": n}}
        return {"rnn_max_abs_gap": {"value": ctl, "limit": limit,
                                    "requests": n},
                "program_max_abs_gap": {"value": gap, "limit": None}}
