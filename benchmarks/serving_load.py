"""Serving-load benchmark: continuous batching under Poisson arrivals.

The paper's real-time scenario — asynchronous batch-of-1 arrivals — turned
into a regression-trackable benchmark: for every cell of
``repro.configs.SERVING_LOAD_SWEEP`` it replays a seeded Poisson workload
through the continuous-batching engine on a virtual clock and aggregates
per-request latency percentiles (queue-wait, TTFT, TPOT) plus tokens/sec
and mean slot utilization.  The grid has three sections:

* the base grid — dense / MoE / RWKV architecture x ``max_batch`` x
  arrival rate, unchanged since the harness landed (its cell names and
  ``metrics`` blocks are the stable perf-trajectory history);
* a prompt-length-distribution sweep (fixed / lognormal / bimodal) over
  the saturating RWKV cell;
* the *overload scenario*: the same seeded over-capacity workload with a
  3% heavy-decode tail and per-request deadlines, served under FCFS, EDF,
  and preemptive EDF — new cells whose ``slo`` / ``sched`` blocks track
  what scheduling policy buys (see repro.serving.scheduler).

  PYTHONPATH=src python -m benchmarks.serving_load [--full] [--seed N] \\
      [--out BENCH_serving.json]

The ``metrics`` block of every cell is computed on the virtual clock, so
it is a *pure function of (sweep, seed)*: two runs with the same seed are
byte-identical, which is what makes ``BENCH_serving.json`` diffable as the
repo's perf trajectory (see benchmarks/README.md).  Wall-clock numbers
(host-dependent, noisy) are reported separately under ``wall`` and are
excluded from the determinism contract.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, Iterator, List, Optional, Sequence

import jax
import numpy as np

from benchmarks.common import Row
from repro.configs import (
    FLEET_SERVING_SWEEP,
    FleetLoadCell,
    SERVING_LOAD_SWEEP,
    ServingLoadCell,
    get_config,
)
from repro.dist.sharding import make_sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan, WorkloadProfile, io as plan_io
from repro.serving import ServingEngine, drive, profile_items
from repro.serving import metrics as smetrics
from repro.testing import reduced_config

SCHEMA = "serving_load/v1"
DEFAULT_OUT = "BENCH_serving.json"


def _build(arch: str, reduced: bool):
    cfg = reduced_config(arch) if reduced else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _calibrate_tick_seconds(engine: ServingEngine, vocab_size: int,
                            seed: int, n_requests: int = 6) -> float:
    """Measured wall cost of one engine tick, on an engine that is already
    warm (its decode chunk and prefill buckets compiled during the virtual
    run): a short closed-loop rerun, wall seconds / ticks.  Host-noisy —
    lives in the ``wall`` block, never in ``metrics``."""
    rng = np.random.default_rng(seed + 0x5EED)
    ticks_before = engine.ticks
    for _ in range(n_requests):
        n = int(rng.integers(4, 13))
        engine.submit([int(x) for x in rng.integers(0, vocab_size, n)],
                      max_new_tokens=8)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    return dt / max(1, engine.ticks - ticks_before)


def run_cell(cell: ServingLoadCell, *, duration: float = 32.0, seed: int = 0,
             reduced: bool = True, trace_dir: Optional[str] = None,
             _built=None) -> Dict[str, object]:
    """One sweep cell: build (or reuse) the model, serve the cell's
    workload profile under the cell's *plan* on a virtual clock, return
    {identity, plan, metrics, wall}.

    Every cell embeds its resolved plan dict, so the committed trajectory
    records exactly which design point produced each number (and any cell
    can be re-served from its recorded plan alone — see
    benchmarks/README.md).  Cells with non-default scheduling dimensions
    additionally report a deterministic ``sched`` block; base-grid cells
    emit the historical document shape plus the ``plan`` key.

    ``trace_dir`` archives a per-cell structured event trace
    (``repro.obs.Tracer``, Chrome trace_event JSON, Perfetto-viewable)
    under ``<trace_dir>/<cell name with / -> _>.trace.json`` — the
    virtual clock makes the files byte-stable per seed, so they can be
    diffed like the ``metrics`` blocks."""
    import dataclasses

    cfg, model, params = _built or _build(cell.arch, reduced)
    # the embedded plan must record the model actually measured: a
    # full-size run flips the plan's `reduced` identity bit too
    plan = cell.plan if cell.plan.reduced == reduced else \
        dataclasses.replace(cell.plan, reduced=reduced)
    sharder = make_sharder(cfg, None, plan.shard_mode)
    tracer = None
    if trace_dir is not None:
        from repro.obs import Tracer

        tracer = Tracer()
    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=sharder, seed=seed,
                                     tracer=tracer)
    duration = cell.duration if cell.duration is not None else duration
    items = profile_items(cell.workload, vocab_size=cfg.vocab_size,
                          seed=seed, duration=duration)
    t0 = time.perf_counter()
    reqs = drive(engine, items)
    wall_s = time.perf_counter() - t0
    agg = smetrics.aggregate(reqs, ticks=engine.ticks,
                             util_history=engine.util_history)
    if tracer is not None:
        # archive before tick calibration, which replays extra requests
        # that are no part of the cell's workload
        os.makedirs(trace_dir, exist_ok=True)
        tracer.save(os.path.join(
            trace_dir, cell.name.replace("/", "_") + ".trace.json"))
    # wall-calibrated tick cost (engine is warm after the drive), mapping
    # the deterministic tick-domain latencies above to milliseconds
    tick_s = _calibrate_tick_seconds(engine, cfg.vocab_size, seed)
    out = {
        "name": cell.name,
        "arch": cell.arch,
        "family": cell.family,
        "max_batch": cell.max_batch,
        "rate": cell.rate,
        "duration": duration,
        "plan": plan_io.to_dict(plan.resolve()),  # the design point
        "metrics": agg,  # virtual-clock: deterministic for a fixed seed
        "wall": {  # host-dependent; excluded from the determinism contract
            "seconds": wall_s,
            "tokens_per_sec_wall": agg["tokens"] / wall_s if wall_s else 0.0,
            "calibrated": smetrics.scale_latencies(agg, tick_s),
        },
    }
    default_sched = (cell.policy == "fcfs" and not cell.preempt
                     and cell.prompt_dist == "uniform"
                     and cell.heavy_decode is None
                     and cell.deadline_slack is None)
    if not default_sched:
        s = engine.stats()
        out["sched"] = {  # deterministic, like metrics
            "policy": cell.policy,
            "preempt": cell.preempt,
            "prompt_dist": cell.prompt_dist,
            "heavy_decode": list(cell.heavy_decode)
            if cell.heavy_decode else None,
            "deadline_slack": cell.deadline_slack,
            "preemptions": int(s["preemptions"]),
            "resumes": int(s["resumes"]),
            "evicted_tokens": int(s["evicted_tokens"]),
            "shed": int(s["shed"]),
        }
    return out


def _slo_met_tokens(reqs) -> int:
    """Served tokens that landed inside their deadline (virtual clock, so
    tick_seconds == 1; same completion rule as the metrics ``slo`` block).
    The capacity-scaling acceptance metric: adding replicas must grow
    *useful* throughput, not just tokens."""
    return sum(len(r.output) for r in reqs
               if r.deadline is not None and r.t_done is not None
               and (r.t_done + 1) <= r.deadline)


def run_fleet_cell(cell: FleetLoadCell, *, duration: float = 32.0,
                   seed: int = 0, reduced: bool = True,
                   _built=None) -> Dict[str, object]:
    """One fleet cell: build the router fleet from the cell's FleetPlan,
    serve the cell's workload on one shared virtual clock, return
    {identity, fleet plan, pooled metrics, transit, wall}.

    The ``metrics`` block pools per-request samples across replicas
    (``metrics.aggregate_fleet``) and — like every single-replica cell —
    is a pure function of (cell, seed).  ``slo_met_tokens`` is the
    capacity-scaling acceptance metric; ``transit`` records the
    disaggregation hand-off economics (bytes, modeled DCN ticks)."""
    import dataclasses

    from repro.plan import io as fleet_io
    from repro.serving.router import Router, drive_fleet

    fleet = cell.fleet
    if fleet.replicas[0].reduced != reduced:
        fleet = dataclasses.replace(fleet, replicas=tuple(
            dataclasses.replace(p, reduced=reduced)
            for p in fleet.replicas))
    fleet.validate()
    cfg = _built[0] if _built else (
        reduced_config(fleet.replicas[0].arch) if reduced
        else get_config(fleet.replicas[0].arch))
    built = {(p.arch, p.reduced): _built[1:] for p in fleet.replicas} \
        if _built else None
    router = Router.from_plan(fleet, seed=seed, _built=built)
    duration = (cell.workload.duration
                if cell.workload.duration is not None else duration)
    items = profile_items(cell.workload, vocab_size=cfg.vocab_size,
                          seed=seed, duration=duration)
    t0 = time.perf_counter()
    reqs = drive_fleet(router, items)
    wall_s = time.perf_counter() - t0
    agg = router.fleet_aggregate()
    census = router.conservation_census()
    if census["total"] != len(reqs):   # keep the BENCH writer honest
        raise RuntimeError(f"fleet cell {cell.name}: request conservation "
                           f"violated: {census} vs {len(reqs)} submitted")
    return {
        "name": cell.name,
        "family": cell.family,
        "n_replicas": fleet.n_replicas,
        "n_prefill": fleet.n_prefill,
        "routing": fleet.routing,
        "rate": cell.workload.rate,
        "duration": duration,
        "fleet": fleet_io.fleet_to_dict(fleet.resolve()),
        "metrics": agg,  # pooled across replicas; deterministic per seed
        "slo_met_tokens": _slo_met_tokens(reqs),
        "transit": router.transit_stats(),
        "wall": {  # host-dependent; excluded from the determinism contract
            "seconds": wall_s,
            "tokens_per_sec_wall": agg["tokens"] / wall_s if wall_s else 0.0,
        },
    }


def autotuned_overload_cell(seed: int = 0) -> ServingLoadCell:
    """The planner's acceptance cell: autotune the committed overload /
    heavy-decode workload (the FCFS cell's profile) and serve it under
    the winning plan, tagged ``auto`` — the serving-level analogue of the
    paper's per-problem-size search, recorded in the trajectory next to
    the hand-picked design points it competes with."""
    from repro.plan import planner

    base = next(c for c in SERVING_LOAD_SWEEP
                if c.deadline_slack is not None and c.policy == "fcfs")
    plan = planner.autotune(base.arch, base.workload, seed=seed,
                            max_len=base.plan.max_len)
    return ServingLoadCell(family=base.family, plan=plan,
                           workload=base.workload, tag="auto")


# The drifting-workload scenario (observed-traffic re-autotune): a plan
# tuned on calm, *deadline-free* traffic keeps serving after the traffic
# drifts to a heavier, deadline-carrying, heavy-tailed mix.  Calm
# traffic is sparse enough (~1 request per 33 ticks, mean decode ~8
# ticks) that requests almost never overlap, so every batch size probes
# identically and the autotuner keeps the cheapest feasible design
# point: 2 slots.  The drifted mix offers ~8.3 slot-ticks/tick — 4x the
# stale capacity — so the stale plan queues unboundedly and misses most
# deadlines, while the replan sees the real rate, the heavy decode
# tail, and the deadlines in the trace, and re-provisions (8 slots,
# deadline-aware policy probed).
_DRIFT_ARCH = "rwkv6-1.6b"
_DRIFT_CALM = WorkloadProfile(
    kind="poisson", rate=0.03, duration=96.0,
    prompt_len=ServingLoadCell.PROMPT_LEN,
    max_new_tokens=ServingLoadCell.MAX_NEW,
    prompt_len_long=ServingLoadCell.MAX_LEN - 1)
_DRIFT_WORKLOAD = WorkloadProfile(
    kind="poisson", rate=0.9, duration=96.0,
    prompt_len=ServingLoadCell.PROMPT_LEN,
    max_new_tokens=ServingLoadCell.MAX_NEW,
    prompt_len_long=ServingLoadCell.MAX_LEN - 1,
    heavy_decode=(0.05, 24, 40), deadline_slack=3.0)


def drifting_workload_cells(seed: int = 0) -> List[ServingLoadCell]:
    """The observability acceptance scenario: two cells serving the same
    drifted workload, under (a) the *stale* plan — autotuned for the calm
    pre-drift profile — and (b) the *replanned* design point, autotuned
    from a structured trace recorded while the stale plan served the
    drifted traffic (``planner.autotune_from_trace``).  The replan sees
    the real arrival rate, the heavy decode tail, and the deadlines the
    stale declaration never mentioned, so it beats the stale plan on SLO
    attainment (asserted in tests/test_serving_load.py).  Deterministic
    for a fixed seed, like every other cell."""
    from repro.obs import Tracer
    from repro.plan import planner

    stale = planner.autotune(_DRIFT_ARCH, _DRIFT_CALM, seed=seed,
                             max_len=ServingLoadCell.MAX_LEN)
    # record the drifted traffic under the stale plan (the "production"
    # run an operator would have a trace of)
    cfg, model, params = _build(_DRIFT_ARCH, reduced=True)
    sharder = make_sharder(cfg, None, stale.shard_mode)
    tracer = Tracer()
    engine = ServingEngine.from_plan(stale, params, model=model,
                                     sharder=sharder, seed=seed,
                                     tracer=tracer)
    items = profile_items(_DRIFT_WORKLOAD, vocab_size=cfg.vocab_size,
                          seed=seed)
    drive(engine, items)
    replan = planner.autotune_from_trace(
        _DRIFT_ARCH, tracer, seed=seed, max_len=ServingLoadCell.MAX_LEN,
        duration=_DRIFT_WORKLOAD.duration)   # the known recording window
    return [
        ServingLoadCell(family="rwkv", plan=stale,
                        workload=_DRIFT_WORKLOAD, tag="drift-stale"),
        ServingLoadCell(family="rwkv", plan=replan,
                        workload=_DRIFT_WORKLOAD, tag="drift-replan"),
    ]


def sweep(fast: bool = True, *, seed: int = 0, reduced: bool = True,
          cells: Optional[Sequence[ServingLoadCell]] = None,
          duration: Optional[float] = None,
          autotune: bool = False,
          trace_dir: Optional[str] = None) -> Dict[str, object]:
    """The full sweep -> the BENCH_serving.json document.  With
    ``autotune=True`` (the real, BENCH-writing runs) the overload
    scenario additionally gets its autotuned cell appended, plus the
    drifting-workload pair (stale plan vs replan-from-observed-trace).
    ``trace_dir`` archives one trace file per cell."""
    cells = list(cells if cells is not None else SERVING_LOAD_SWEEP)
    fleet_cells: List[FleetLoadCell] = []
    if autotune:
        cells.append(autotuned_overload_cell(seed))
        cells.extend(drifting_workload_cells(seed))
        # the fleet grid rides the BENCH-writing runs only, under its own
        # document key: the single-replica `cells` history never reshapes
        fleet_cells = list(FLEET_SERVING_SWEEP)
    duration = duration if duration is not None else (32.0 if fast else 256.0)
    built: Dict[str, tuple] = {}  # one model build per arch, many cells
    out_cells: List[Dict[str, object]] = []
    for cell in cells:
        if cell.arch not in built:
            built[cell.arch] = _build(cell.arch, reduced)
        out_cells.append(run_cell(cell, duration=duration, seed=seed,
                                  reduced=reduced, trace_dir=trace_dir,
                                  _built=built[cell.arch]))
    out_fleet: List[Dict[str, object]] = []
    for fcell in fleet_cells:
        arch = fcell.fleet.replicas[0].arch
        if arch not in built:
            built[arch] = _build(arch, reduced)
        out_fleet.append(run_fleet_cell(fcell, duration=duration, seed=seed,
                                        reduced=reduced, _built=built[arch]))
    doc = {
        "schema": SCHEMA,
        "seed": seed,
        "mode": "fast" if fast else "full",
        "reduced": reduced,
        "duration": duration,
        "families": sorted({c.family for c in cells}),
        "cells": out_cells,
    }
    if out_fleet:
        doc["fleet"] = out_fleet
    return doc


def deterministic_view(doc: Dict[str, object]) -> Dict[str, object]:
    """The seed-determined subset of a sweep document (drops wall timings);
    two same-seed runs must agree on this exactly."""
    out = {
        **{k: v for k, v in doc.items() if k not in ("cells", "fleet")},
        "cells": [{k: v for k, v in c.items() if k != "wall"}
                  for c in doc["cells"]],
    }
    if "fleet" in doc:
        out["fleet"] = [{k: v for k, v in c.items() if k != "wall"}
                        for c in doc["fleet"]]
    return out


def write(doc: Dict[str, object], path: str = DEFAULT_OUT) -> None:
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def _check_policy_registry() -> None:
    """Fail loudly if the scheduler registry and the serve CLI's --policy
    choices drift apart (the smoke runs in tier-1 CI, so a policy added to
    one surface but not the other breaks the build, not production)."""
    from repro.launch.serve import build_parser
    from repro.serving.scheduler import SCHEDULERS

    choices = None
    for action in build_parser()._actions:
        if "--policy" in action.option_strings:
            choices = set(action.choices or ())
    if choices is None:
        raise RuntimeError("launch/serve.py no longer exposes --policy")
    if choices != set(SCHEDULERS):
        raise RuntimeError(
            f"--policy CLI choices {sorted(choices)} drifted from the "
            f"scheduler registry {sorted(SCHEDULERS)}; update "
            f"launch/serve.py or repro/serving/scheduler.py")
    swept = {(c.policy, c.preempt) for c in SERVING_LOAD_SWEEP}
    missing = set(SCHEDULERS) - {p for p, _ in swept}
    if missing - {"spf"}:   # spf is covered by decode_hotpath's tests
        raise RuntimeError(f"policies {sorted(missing)} are registered but "
                           f"never exercised by SERVING_LOAD_SWEEP")


def _check_plan_surface() -> None:
    """CI guard for the plan subsystem: the plan JSON schema must match
    the dataclass fields, and a tiny autotune run must return a plan that
    passes ``ServingPlan.validate()`` and round-trips through JSON —
    loudly, in tier-1, so the trajectory files can never embed a plan the
    code cannot read back."""
    from repro.plan import ServingPlan, WorkloadProfile, planner

    plan_io.check_schema()
    tiny = planner.autotune(
        "rwkv6-1.6b", WorkloadProfile(rate=0.5, duration=6.0),
        max_batches=(2,), sync_everys=(1, 2), probe_duration=6.0)
    tiny.validate()   # autotune validates too; fail loudly if that rots
    rt = plan_io.from_dict(plan_io.to_dict(tiny))
    if rt != tiny:
        raise RuntimeError("autotuned plan does not round-trip through "
                           "JSON; fix repro.plan.io coercions")
    if not isinstance(rt, ServingPlan) or rt.arch != "rwkv6-1.6b":
        raise RuntimeError("autotune returned a malformed plan")


def _check_trace_schema() -> None:
    """CI guard for the observability subsystem: serve a tiny workload
    with a tracer attached, twice with the same seed, and require (a) the
    exported documents to be byte-identical — the determinism contract
    that makes trace files diffable artifacts — (b) the schema validator
    to accept them, and (c) ``fit_profile`` to read a workload profile
    back out.  Loud in tier-1, so the trace schema, the engine's hook
    points, and the observed-traffic fit can never silently drift."""
    from repro.obs import Tracer, check_trace, fit_profile

    tiny = WorkloadProfile(kind="poisson", rate=0.5, duration=8.0,
                           deadline_slack=3.0)
    cfg, model, params = _build("rwkv6-1.6b", reduced=True)
    sharder = make_sharder(cfg, None, "decode")

    def one_run() -> Tracer:
        tracer = Tracer()
        plan = ServingPlan(arch="rwkv6-1.6b", max_batch=2, max_len=32)
        engine = ServingEngine.from_plan(plan, params, model=model,
                                         sharder=sharder, tracer=tracer)
        drive(engine, profile_items(tiny, vocab_size=cfg.vocab_size,
                                    seed=0))
        return tracer

    a, b = one_run(), one_run()
    if a.dumps() != b.dumps():
        raise RuntimeError("same-seed virtual-clock runs emitted "
                           "different trace bytes; repro.obs.trace has "
                           "lost determinism")
    check_trace(a.to_chrome())   # raises ValueError on schema drift
    prof = fit_profile(a, duration=tiny.duration)
    if not (0 < prof.rate < 10 and prof.prompt_len[0] >= 1):
        raise RuntimeError(f"fit_profile returned an implausible profile "
                           f"from the smoke trace: {prof}")


def _check_paged_surface() -> None:
    """CI guard for the paged cache layout: the serve CLI must expose
    ``--cache-layout``, ``parse_cache_layout`` must accept both spellings,
    the sweep must exercise a paged cell, and a tiny dense-vs-paged probe
    on a hybrid (attention + SSM) arch must produce identical schedules
    and metrics with clean pool invariants — loudly, in tier-1, so the
    bit-exactness contract can never silently rot."""
    from repro.launch.serve import build_parser
    from repro.plan.plan import parse_cache_layout

    if not any("--cache-layout" in a.option_strings
               for a in build_parser()._actions):
        raise RuntimeError("launch/serve.py no longer exposes "
                           "--cache-layout")
    if parse_cache_layout("paged:16") != 16 \
            or parse_cache_layout("dense") is not None:
        raise RuntimeError("repro.plan.parse_cache_layout drifted from "
                           "the dense / paged:<block_size> grammar")
    if not any(c.cache_layout != "dense" for c in SERVING_LOAD_SWEEP):
        raise RuntimeError("SERVING_LOAD_SWEEP no longer exercises a "
                           "paged cell; the layout has no trajectory "
                           "coverage")

    tiny = WorkloadProfile(kind="poisson", rate=0.6, duration=8.0)
    cfg, model, params = _build("hymba-1.5b", reduced=True)
    sharder = make_sharder(cfg, None, "decode")

    def one_run(layout: str):
        plan = ServingPlan(arch="hymba-1.5b", max_batch=2, max_len=32,
                           cache_layout=layout)
        engine = ServingEngine.from_plan(plan, params, model=model,
                                         sharder=sharder)
        reqs = drive(engine, profile_items(tiny, vocab_size=cfg.vocab_size,
                                           seed=0))
        agg = smetrics.aggregate(reqs, ticks=engine.ticks,
                                 util_history=engine.util_history)
        return engine, [(r.uid, tuple(r.output)) for r in reqs], agg

    _, sched_d, agg_d = one_run("dense")
    eng_p, sched_p, agg_p = one_run("paged:8")
    if sched_d != sched_p:
        raise RuntimeError("dense and paged:8 schedules diverged on the "
                           "hymba smoke probe; the paged manager broke "
                           "the bit-exactness contract")
    if json.dumps(agg_d, sort_keys=True) != json.dumps(agg_p, sort_keys=True):
        raise RuntimeError("dense and paged:8 metrics diverged on the "
                           "hymba smoke probe despite equal schedules")
    eng_p.sm.check_invariants()   # raises on any pool-accounting breach


def _check_router_surface() -> None:
    """CI guard for the multi-replica router: the serve CLI's --routing
    choices must match the router's policy registry, the FleetPlan JSON
    schema must round-trip (io.check_schema grew a fleet probe), and a
    tiny 2-replica live probe must serve a seeded workload with clean
    request conservation and a deterministic pooled metrics block —
    loudly, in tier-1, so the fleet surfaces can never silently drift."""
    from repro.launch.serve import build_parser
    from repro.plan.plan import FleetPlan, ServingPlan
    from repro.serving.router import ROUTER_POLICIES, Router, drive_fleet

    choices = None
    for action in build_parser()._actions:
        if "--routing" in action.option_strings:
            choices = set(action.choices or ())
    if choices is None:
        raise RuntimeError("launch/serve.py no longer exposes --routing")
    if choices != set(ROUTER_POLICIES):
        raise RuntimeError(
            f"--routing CLI choices {sorted(choices)} drifted from the "
            f"router registry {sorted(ROUTER_POLICIES)}; update "
            f"launch/serve.py or repro/serving/router.py")
    plan_io.check_schema()   # includes the fleet_plan/v1 probe

    tiny = WorkloadProfile(kind="poisson", rate=0.8, duration=8.0)
    cfg, model, params = _build("rwkv6-1.6b", reduced=True)
    fleet = FleetPlan.replicated(
        ServingPlan(arch="rwkv6-1.6b", max_batch=2, max_len=32), 2,
        routing="least_queue").validate()
    built = {("rwkv6-1.6b", True): (model, params)}

    def one_run():
        router = Router.from_plan(fleet, seed=0, _built=built)
        reqs = drive_fleet(router, profile_items(
            tiny, vocab_size=cfg.vocab_size, seed=0))
        return router, reqs

    ra, reqs_a = one_run()
    rb, reqs_b = one_run()
    census = ra.conservation_census()
    if census["total"] != len(reqs_a) or census["finished"] != len(reqs_a):
        raise RuntimeError(f"fleet smoke probe lost requests: {census}")
    a = json.dumps(ra.fleet_aggregate(), sort_keys=True)
    b = json.dumps(rb.fleet_aggregate(), sort_keys=True)
    if a != b:
        raise RuntimeError("same-seed fleet runs produced different pooled "
                           "metrics; the router has lost determinism")
    sched = [[(r.uid, tuple(r.output)) for r in rs]
             for rs in (reqs_a, reqs_b)]
    if sched[0] != sched[1]:
        raise RuntimeError("same-seed fleet runs produced different "
                           "schedules; the router has lost determinism")


def run(fast: bool = True, smoke: bool = False) -> Iterator[Row]:
    """benchmarks.run harness entry: emit one CSV row per cell and refresh
    BENCH_serving.json in the working directory.  ``smoke`` runs one tiny
    base cell plus the overload scenario (every policy in it, preemption
    included), checks the plan JSON schema, validates the trace schema +
    byte-determinism, probes the paged cache layout against dense, and
    autotunes one tiny cell — and does NOT touch
    BENCH_serving.json; it proves the scripts, the scheduler registry,
    the plan subsystem, and the observability layer still work (the
    tier-1 CI guard)."""
    if smoke:
        _check_policy_registry()
        _check_plan_surface()
        _check_trace_schema()
        _check_paged_surface()
        _check_router_surface()
        base = [c for c in SERVING_LOAD_SWEEP
                if c.family == "rwkv" and c.max_batch == 2
                and c.policy == "fcfs" and c.prompt_dist == "uniform"
                and c.heavy_decode is None and c.deadline_slack is None][-1:]
        overload = [c.with_duration(8.0)
                    for c in SERVING_LOAD_SWEEP
                    if c.deadline_slack is not None]
        if not base or not overload:  # keep the CI guard loud on reshapes
            raise RuntimeError("smoke filter matched no SERVING_LOAD_SWEEP "
                               "cell; update the filter")
        doc = sweep(fast=True, cells=base + overload, duration=8.0)
    else:
        doc = sweep(fast=fast, autotune=True)
        write(doc)
    for c in doc["cells"]:
        m, w = c["metrics"], c["wall"]
        us_per_tok = w["seconds"] / m["tokens"] * 1e6 if m["tokens"] else 0.0
        slo = (f" slo={m['slo']['attainment']:.2f}" if "slo" in m else "")
        yield Row(
            f"serving_load/{c['name']}",
            us_per_tok,
            f"ttft_p99={m['ttft']['p99']:.0f}t"
            f" tpot_p99={m['tpot']['p99']:.2f}t"
            f" qwait_p99={m['queue_wait']['p99']:.0f}t"
            f" tok_per_tick={m['tokens_per_sec']:.2f}"
            f" util={m['mean_util']:.2f}" + slo)
    for c in doc.get("fleet", ()):
        m, w = c["metrics"], c["wall"]
        us_per_tok = w["seconds"] / m["tokens"] * 1e6 if m["tokens"] else 0.0
        slo = (f" slo={m['slo']['attainment']:.2f}" if "slo" in m else "")
        yield Row(
            f"serving_load/{c['name']}",
            us_per_tok,
            f"ttft_p99={m['ttft']['p99']:.0f}t"
            f" tpot_p99={m['tpot']['p99']:.2f}t"
            f" slo_met_tok={c['slo_met_tokens']}"
            f" handoffs={c['transit']['handoffs']}" + slo)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="longer workloads (256 clock units vs 32)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--full-size", action="store_true",
                    help="full-size configs (default: reduced, CPU-friendly)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="archive one Perfetto-viewable trace file per "
                         "cell (repro.obs structured event traces; "
                         "byte-stable per seed)")
    args = ap.parse_args()
    # both BENCH-writing entries (this and benchmarks.run) include the
    # autotuned overload cell, so the committed document shape is the same
    # whichever path regenerated it
    doc = sweep(fast=not args.full, seed=args.seed,
                reduced=not args.full_size, autotune=True,
                trace_dir=args.trace_dir)
    write(doc, args.out)
    print(f"wrote {args.out}: {len(doc['cells'])} cells "
          f"+ {len(doc.get('fleet', ()))} fleet cells, "
          f"families={doc['families']}")
    for c in doc["cells"]:
        m = c["metrics"]
        slo = (f"  slo {m['slo']['attainment']:.2f}" if "slo" in m else "")
        print(f"  {c['name']:>36}"
              f" ttft p50/p95 = {m['ttft']['p50']:5.1f}/{m['ttft']['p95']:5.1f}t"
              f"  tpot p50/p99 = {m['tpot']['p50']:4.2f}/{m['tpot']['p99']:4.2f}t"
              f"  {m['tokens_per_sec']:5.2f} tok/tick"
              f"  util {m['mean_util']:.2f}" + slo)
    for c in doc.get("fleet", ()):
        m = c["metrics"]
        slo = (f"  slo {m['slo']['attainment']:.2f}" if "slo" in m else "")
        print(f"  {c['name']:>36}"
              f" ttft p50/p99 = {m['ttft']['p50']:5.1f}/{m['ttft']['p99']:5.1f}t"
              f"  tpot p50/p99 = {m['tpot']['p50']:4.2f}/{m['tpot']['p99']:4.2f}t"
              f"  slo-met tok {c['slo_met_tokens']:4d}"
              f"  handoffs {c['transit']['handoffs']}" + slo)


if __name__ == "__main__":
    main()
