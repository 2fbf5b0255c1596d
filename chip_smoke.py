"""Bring-up smoke test: the serving path on a TPU, through the entry points
a user calls, at published widths, with random weights made from a seed.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # four chips: the sharded path only

One chip:
  * rwkv6-1.6b (24 x 2048, vocab 65536, f32 params) served through
    ``launch.serve.resolve_plan`` / ``build_model`` /
    ``ServingEngine.from_plan``: 16 requests of 10-600 prompt tokens and
    32 new tokens each must complete; the compiled decode program must
    hold the ``rwkv6_step`` Pallas kernel; teacher-forced logits (prefill
    + 4 decode steps) of the Pallas plan must match the same model's
    ``impl: "jnp"`` plan.
  * The ten DeepBench LSTM/GRU tasks at batch 1, int8 weights, their own
    timesteps: ``core.cells.serve(impl="kernel")`` on the DSE's tile
    against ``impl="blas"``.
Four chips (``--chips 4``):
  * qwen2.5-14b at published widths cut to 4 layers: teacher-forced
    logits unsharded on chip 0 (jnp path), then under the decode sharder
    on a 1x4 mesh with params placed by ``Sharder.param_shardings`` and
    the attention tile plan active (``flash_decode`` per cache shard);
    each chip must hold about a quarter of the params, and the engine
    must serve a few requests.

Timings printed on the way are labelled smoke timings: one run,
compiles included, not benchmark numbers.  A line before them says how
many entries the persistent compile cache held at the start, i.e.
whether the compile times are cold.  The last line of stdout is
one JSON object ``{"ok": true, "device": {...}}``.  Without a TPU, or
when any check fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# rwkv6-1.6b serving: several prompts cross the 128-token WKV chunk; five
# prefill buckets (16, 128, 256, 512, 1023) keep the compile count small
RWKV_PROMPTS = (10, 13, 16, 100, 120, 128, 129, 200, 250, 256, 300, 400,
                480, 512, 550, 600)
RWKV_NEW = 32
RWKV_MAX_BATCH = 8
RWKV_MAX_LEN = 1024
# Pallas vs jnp logits, as a share of the largest jnp logit.  Both paths
# round the wkv output to bf16, but the kernel reduces over the key axis
# in f32 on the VPU where XLA's einsum runs on the MXU, so values can
# land a bf16 step (2^-8 relative) apart and such steps add up over 24
# layers.  A wrong head tile or state update moves logits by O(1).
RWKV_REL_TOL = 5e-2
# examples/serve_rnn_deepbench.py's agreement bound on the hidden state
DEEPBENCH_TOL = 5e-2
# qwen2.5-14b, 4 layers: sharded Pallas (flash kernels, cross-chip LSE
# merge, all-reduced matmuls) vs unsharded jnp.  Both accumulate bf16
# products in f32 but in another order, so re-rounded bf16 activations can
# differ by a step; a dropped head or cache shard moves logits by O(1).
QWEN_LAYERS = 4
QWEN_REL_TOL = 5e-2
QWEN_MAX_LEN = 512


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class smoke_timing:
    """Print the wall time of a block as a smoke timing."""

    def __init__(self, what: str):
        self.what = what

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"smoke timing (not a benchmark): {self.what}: "
                f"{self.seconds:.1f} s")


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not installed"


def check_device(min_count: int = 1):
    """Name the devices and their toolchain; exit non-zero unless JAX's
    devices are at least ``min_count`` TPUs."""
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    log(f"toolchain: jax={_version('jax')} jaxlib={_version('jaxlib')} "
        f"libtpu={_version('libtpu')}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); nothing was run")
    if len(devices) < min_count:
        raise SystemExit(f"chip_smoke: need {min_count} TPU chips, JAX "
                         f"sees {len(devices)}")
    return devices


def cache_note(path) -> str:
    """Where the persistent compile cache is and how full it starts."""
    if not path:
        return "compile cache: off"
    n = sum(1 for _ in Path(path).iterdir()) if Path(path).is_dir() else 0
    return f"compile cache: {path} ({n} entries at start)"


def _resolve(argv):
    from repro.launch.serve import build_parser, kernel_banner, resolve_plan

    parser = build_parser()
    plan = resolve_plan(parser.parse_args(argv), parser)
    log(f"plan: {plan.summary()}")
    log(f"kernel tiles: {kernel_banner(plan)}")
    return plan


def _init(model, seed: int, sharding=None):
    import jax

    init = jax.jit(model.init, out_shardings=sharding)
    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    n = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"params: {n / 1e9:.2f} GB f32")
    return params, n


def teacher_forced(model, params, sharder, prompts, feed, *, max_len: int):
    """Logits of a right-padded prefill over ``prompts`` and one decode
    step per row of ``feed`` (None: feed the greedy token), as host f32
    (steps + 1, B, V); plus the fed tokens and the decode program HLO."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = max(len(p) for p in prompts)
    tokens = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    batch = {"tokens": jnp.asarray(tokens),
             "lengths": jnp.asarray([len(p) for p in prompts], jnp.int32)}
    prefill = jax.jit(lambda p, b: model.prefill(p, b, sharder,
                                                 max_len=max_len))
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t, sharder))
    cache, logits = prefill(params, batch)
    out, fed, hlo = [np.asarray(logits, np.float32)], [], None
    for i in range(4 if feed is None else len(feed)):
        t = (np.argmax(out[-1], -1) if feed is None else feed[i])
        t = jnp.asarray(t, jnp.int32)
        if hlo is None:
            hlo = decode.lower(params, cache, t).compile().as_text()
        cache, logits = decode(params, cache, t)
        out.append(np.asarray(logits, np.float32))
        fed.append(np.asarray(t))
    return np.stack(out), fed, hlo


def _rel_err(got, ref) -> float:
    import numpy as np

    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _step_errs(got, ref) -> str:
    """The relative error of each teacher-forced step (0 = prefill)."""
    return " ".join(f"{_rel_err(g, r):.1e}" for g, r in zip(got, ref))


def _peak_gb(device) -> float:
    return device.memory_stats()["peak_bytes_in_use"] / 1e9


def _has_kernel(hlo: str, name: str) -> bool:
    return any("tpu_custom_call" in line and name in line
               for line in hlo.splitlines())


def serve_rwkv(seed: int) -> None:
    """rwkv6-1.6b through the serving entry points at published widths."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.dist.sharding import make_sharder
    from repro.kernels.dispatch import kernel_impls
    from repro.models.lm import build_model
    from repro.serving import ServingEngine

    log("== rwkv6-1.6b serving (one chip) ==")
    plan = _resolve(["--arch", "rwkv6-1.6b", "--max-batch",
                     str(RWKV_MAX_BATCH), "--max-len", str(RWKV_MAX_LEN),
                     "--seed", str(seed)])
    require(not plan.reduced, "the plan serves the published widths")
    require(kernel_impls(plan.tile_plans).get("rwkv") == "pallas",
            "the plan runs the rwkv6_step kernel")
    cfg = get_config(plan.arch)
    model = build_model(cfg)
    with smoke_timing("param init"):
        params, _ = _init(model, seed)
    sharder = make_sharder(cfg, None, plan.shard_mode)
    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=sharder, seed=seed)
    with smoke_timing("decode program compile"):
        hlo = engine.lower_decode().compile().as_text()
    require(_has_kernel(hlo, "rwkv6_step"),
            "rwkv6_step is a tpu_custom_call of the decode program")
    log("decode program holds rwkv6_step as a tpu_custom_call")

    rng = np.random.default_rng(seed)
    reqs = [engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          max_new_tokens=RWKV_NEW) for n in RWKV_PROMPTS]
    with smoke_timing(f"serve {len(reqs)} requests (prefill compiles "
                      f"included)") as t:
        engine.run()
    done = sum(r.done and len(r.output) == RWKV_NEW for r in reqs)
    s = engine.stats()
    log(f"served {done}/{len(reqs)} requests, {s['total_tokens']} tokens, "
        f"{s['ticks']} ticks, {s['prefill_compiles']} prefill shapes, "
        f"{t.seconds:.1f} s wall")
    require(done == len(reqs), "every request completed")

    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (200, 37)]
    jnp_model = model.with_tile_plans(
        {k: dict(v, impl="jnp") for k, v in plan.tile_plans.items()})
    ref, feed, _ = teacher_forced(jnp_model, params, sharder, prompts, None,
                                  max_len=RWKV_MAX_LEN)
    got, _, hlo = teacher_forced(model.with_tile_plans(plan.tile_plans),
                                 params, sharder, prompts, feed,
                                 max_len=RWKV_MAX_LEN)
    with jax.default_matmul_precision("highest"):
        f32, _, _ = teacher_forced(jnp_model, params, sharder, prompts, feed,
                                   max_len=RWKV_MAX_LEN)
    require(_has_kernel(hlo, "rwkv6_step"), "the Pallas plan ran the kernel")
    err = _rel_err(got, ref)
    log(f"teacher-forced logits, Pallas vs jnp plan: max |diff| / max "
        f"|logit| = {err:.3e} (tolerance {RWKV_REL_TOL:g}); max |logit| "
        f"{np.max(np.abs(ref)):.3f}; per step {_step_errs(got, ref)}")
    log(f"against the jnp plan at matmul precision 'highest': Pallas "
        f"{_rel_err(got, f32):.3e}, jnp plan {_rel_err(ref, f32):.3e}")
    log(f"peak HBM in use: {_peak_gb(jax.devices()[0]):.2f} GB")
    require(np.isfinite(got).all(), "Pallas logits are finite")
    require(err <= RWKV_REL_TOL, "Pallas logits match the jnp plan")


def deepbench(seed: int) -> None:
    """The paper's workload: each DeepBench task, fused kernel vs BLAS."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import DEEPBENCH_TASKS
    from repro.core.cells import (RNNCellConfig, init_weights,
                                  quantize_weights, serve)
    from repro.core.dse import best_plan

    log("== DeepBench RNN tasks, batch 1, int8 weights (one chip) ==")
    for task in DEEPBENCH_TASKS:
        cfg = RNNCellConfig(task.cell, task.hidden,
                            timesteps=task.timesteps, batch=1,
                            precision="int8")
        key = jax.random.PRNGKey(seed)
        w = quantize_weights(cfg, init_weights(cfg, key))
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (task.timesteps, 1, cfg.d), jnp.bfloat16)
        with smoke_timing(f"{task.name} kernel (compile included)"):
            y_k = jax.block_until_ready(serve(cfg, w, x, impl="kernel"))
        y_b = serve(cfg, w, x, impl="blas")
        err = float(jnp.max(jnp.abs(y_k.astype(jnp.float32) - y_b)))
        plan = best_plan(cfg, max_batch=1)
        log(f"{task.name}: bh={plan.bh} tiles={plan.n_tiles} "
            f"max|kernel - blas| = {err:.3e} (tolerance {DEEPBENCH_TOL:g})")
        require(bool(np.isfinite(np.asarray(y_k, np.float32)).all()),
                f"{task.name} kernel output is finite")
        require(err < DEEPBENCH_TOL, f"{task.name} kernel agrees with blas")


def sharded_qwen(devices, seed: int) -> None:
    """qwen2.5-14b (4 layers) sharded over four chips vs one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    from repro.configs import get_config
    from repro.dist.sharding import Sharder, make_sharder
    from repro.kernels.dispatch import kernel_impls
    from repro.models.lm import build_model
    from repro.serving import ServingEngine

    log(f"== qwen2.5-14b, {QWEN_LAYERS} layers, sharded over "
        f"{len(devices)} chips ==")
    plan = _resolve(["--arch", "qwen2.5-14b", "--max-batch", "4",
                     "--max-len", str(QWEN_MAX_LEN), "--seed", str(seed)])
    require(kernel_impls(plan.tile_plans).get("attn") == "pallas",
            "the plan runs the flash attention kernels")
    cfg = dataclasses.replace(get_config(plan.arch), n_layers=QWEN_LAYERS)
    model = build_model(cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (100, 37)]

    with smoke_timing("unsharded reference on chip 0"):
        params, total = _init(model, seed, SingleDeviceSharding(devices[0]))
        ref, feed, _ = teacher_forced(model, params, Sharder(None, {}),
                                      prompts, None, max_len=QWEN_MAX_LEN)
    del params
    gc.collect()

    mesh = Mesh(np.asarray(devices).reshape(1, len(devices)),
                ("data", "model"))
    sharder = make_sharder(cfg, mesh, plan.shard_mode)
    with smoke_timing("sharded param init"):
        params, _ = _init(model, seed,
                          sharder.param_shardings(model.param_specs()))
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d in devices:
        in_use = d.memory_stats()["bytes_in_use"]
        log(f"chip {d.id}: param shards {held[d] / 1e9:.2f} GB, "
            f"memory_stats bytes_in_use {in_use / 1e9:.2f} GB "
            f"({in_use / total:.3f} of {total / 1e9:.2f} GB params)")
        require(0.2 <= in_use / total <= 0.35,
                f"chip {d.id} holds about a quarter of the params")

    sharded = model.with_tile_plans(plan.tile_plans)
    with smoke_timing("sharded teacher-forced run"):
        got, _, hlo = teacher_forced(sharded, params, sharder, prompts,
                                     feed, max_len=QWEN_MAX_LEN)
    require(_has_kernel(hlo, "flash_decode"),
            "flash_decode is a tpu_custom_call of the sharded decode step")
    err = _rel_err(got, ref)
    log(f"teacher-forced logits, 4-chip sharded Pallas vs 1-chip jnp: "
        f"max |diff| / max |logit| = {err:.3e} (tolerance "
        f"{QWEN_REL_TOL:g}); max |logit| {np.max(np.abs(ref)):.3f}; "
        f"per step {_step_errs(got, ref)}")
    require(np.isfinite(got).all(), "sharded logits are finite")
    require(err <= QWEN_REL_TOL, "sharded logits match the unsharded run")

    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=sharder, seed=seed)
    reqs = [engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          max_new_tokens=8) for n in (40, 90, 130, 200)]
    with smoke_timing("sharded serve of 4 requests (compiles included)"):
        engine.run()
    done = sum(r.done and len(r.output) == 8 for r in reqs)
    log(f"sharded engine served {done}/{len(reqs)} requests; peak HBM in "
        f"use per chip: "
        + " ".join(f"{_peak_gb(d):.2f}" for d in devices) + " GB")
    require(done == len(reqs), "the sharded engine completed every request")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serving-path smoke test on a TPU (see module doc).")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: rwkv6-1.6b serving + DeepBench; 4: only the "
                         "sharded qwen2.5-14b phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = check_device(args.chips)
    import jax

    log(cache_note(jax.config.jax_compilation_cache_dir))
    if args.chips == 4:
        sharded_qwen(devices[:4], args.seed)
    else:
        serve_rwkv(args.seed)
        deepbench(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
