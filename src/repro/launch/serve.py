"""Serving launcher: batched request serving through the continuous-
batching engine, optionally under an open-loop arrival process.

The CLI is *plan-centric*: every serving design parameter lives in a
:class:`repro.plan.ServingPlan`, and the engine is built from one.

  # legacy closed-loop mode: submit N requests up front, drain
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \\
      --requests 8 --max-new 16

  # the paper's real-time scenario: Poisson arrivals, latency percentiles
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \\
      --arrival poisson --rate 0.5 --duration 64 --seed 0

  # serve a recorded design point (e.g. one embedded in BENCH_serving.json)
  PYTHONPATH=src python -m repro.launch.serve --plan plan.json \\
      --arrival poisson --rate 0.8 --duration 64

  # search the design space for this workload, save + serve the winner
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \\
      --autotune --arrival poisson --rate 2.0 --duration 64 \\
      --deadline-slack 3.0 --save-plan tuned.json

``--plan`` loads a plan JSON (``repro.plan.io``); ``--autotune`` runs the
serving-level design-space search (``repro.plan.planner``) against the
CLI-described workload.  Any knob flag given *in addition* is an explicit
override of the plan and is recorded in ``plan.provenance`` — so a served
plan always says where each of its values came from.  Without either,
the flags resolve to the historical CLI defaults and build a plan
internally: the behavior (and the virtual-clock schedule) is unchanged.

``--arrival {poisson,mmpp,trace}`` replays a workload from
``repro.serving.workload`` and prints the TTFT/TPOT/queue-wait percentile
summary.  ``--clock virtual`` (default) is deterministic — the metrics are
a pure function of (workload, seed); ``--clock wall`` paces arrivals in
real time and additionally reports measured wall tokens/sec.

``--policy`` choices are generated from the scheduler registry
(``repro.serving.scheduler.SCHEDULERS``) so the CLI can never offer a
policy the engine does not implement; the benchmark smoke guard asserts
this stays true.  ``--deadline-slack S`` stamps every generated request
with the absolute deadline ``arrival + S * max_new`` clock units, and
``--shed-late`` turns on deadline-aware admission control (reject
provably-late requests at submit).

Observability (``repro.obs``): ``--trace-out trace.json`` records a
structured event trace — request lifecycle spans and engine events on
the virtual clock — as Chrome ``trace_event`` JSON, viewable at
https://ui.perfetto.dev; ``--live-metrics [N]`` prints a rolling
p95-TTFT/TPOT/SLO/utilization line over the last N ticks while serving.
A recorded trace feeds ``WorkloadProfile.from_trace`` /
``planner.autotune_from_trace`` to replan from observed traffic.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.dist.sharding import make_sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan, WorkloadProfile, io as plan_io
from repro.serving import ServingEngine
from repro.serving import metrics as smetrics
from repro.serving import workload as wl
from repro.serving.router import ROUTING_POLICIES
from repro.serving.scheduler import POLICIES
from repro.testing import reduced_config

# CLI flag -> plan field, for flags that map 1:1 (None = "not given";
# the plan's value stands unless the user typed the flag)
_PLAN_FLAGS = (
    ("arch", "arch"),
    ("reduced", "reduced"),
    ("max_batch", "max_batch"),
    ("max_len", "max_len"),
    ("cache_layout", "cache_layout"),
    ("temperature", "temperature"),
    ("sync_every", "sync_every"),
    ("policy", "policy"),
    ("preempt", "preempt"),
    ("shed_late", "shed_late"),
    ("truncate_prompts", "truncate_prompts"),
    ("retry_budget", "retry_budget"),
    ("watchdog_ticks", "watchdog_ticks"),
)

# the pre-plan CLI defaults, applied only when no plan file is loaded so
# a flagless invocation behaves exactly as it always has
_CLI_DEFAULT_MAX_LEN = 64


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface, as a factory so tools (and the benchmark smoke
    guard) can introspect it without running a model.

    Plan-covered knobs default to ``None`` ("not given"): their effective
    defaults live in :class:`repro.plan.ServingPlan`, and a given flag
    becomes a recorded override of whatever plan is in force."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (required unless --plan carries "
                         "one)")
    ap.add_argument("--reduced", action="store_true", default=None)
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="load a ServingPlan JSON (e.g. saved by "
                         "--save-plan, or the 'plan' dict of a committed "
                         "BENCH_serving.json cell); knob flags given as "
                         "well become recorded overrides")
    ap.add_argument("--autotune", action="store_true",
                    help="search the serving design space (bucket set x "
                         "sync_every x max_batch x policy) for the "
                         "CLI-described workload and serve the winning "
                         "plan (repro.plan.planner.autotune)")
    ap.add_argument("--save-plan", default=None, metavar="PATH",
                    help="write the resolved plan (explicit buckets, "
                         "provenance included) as JSON before serving")
    ap.add_argument("--hw-spec", default=None, metavar="NAME",
                    help="hardware spec the kernel tile plans are scored "
                         "against (repro.hw registry, e.g. tpu-v5e / "
                         "plasticine-rnn-variant); giving it recomputes "
                         "tile_plans even when a --plan file carries them")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="decode slots (plan default: 4)")
    ap.add_argument("--max-len", type=int, default=None,
                    help=f"cache length (CLI default: "
                         f"{_CLI_DEFAULT_MAX_LEN})")
    ap.add_argument("--cache-layout", default=None, metavar="LAYOUT",
                    help="cache backing layout: 'dense' (one fixed column "
                         "per slot) or 'paged:<block_size>' (block-table "
                         "pool along the length axis, bit-exact schedules "
                         "either way; plan default dense, autotune searches "
                         "both)")
    ap.add_argument("--temperature", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="workload + sampler seed")
    ap.add_argument("--sync-every", type=int, default=None,
                    help="decode ticks per host sync: the fused on-device "
                         "decode loop runs this many ticks between host "
                         "interventions (admission/retire); plan default 1")
    ap.add_argument("--policy", default=None, choices=POLICIES,
                    help="admission order: FCFS, shortest-prompt-first, or "
                         "earliest-deadline-first (choices come from the "
                         "scheduler registry; plan default fcfs)")
    ap.add_argument("--preempt", action="store_true", default=None,
                    help="allow the scheduler to evict a running request "
                         "to host memory when a strictly tighter deadline "
                         "waits (EDF only); evicted requests resume "
                         "bit-exactly once a slot frees")
    ap.add_argument("--shed-late", action="store_true", default=None,
                    help="deadline-aware admission control: reject "
                         "requests at submit when they provably cannot "
                         "meet their deadline even if admitted instantly")
    ap.add_argument("--no-bucketed-prefill", action="store_true",
                    default=None,
                    help="legacy exact-length batch-1 prefill per request "
                         "(compiles per distinct prompt length) instead of "
                         "length-bucketed batched prefill")
    ap.add_argument("--no-overlap-prefill", action="store_true",
                    default=None,
                    help="serialize admission with decode: block on the "
                         "prefill sample readback before launching the "
                         "decode chunk (the pre-overlap engine behaviour; "
                         "the schedule is identical either way)")
    # multi-replica serving tier (repro.serving.router)
    ap.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="serve through a fleet of N engine replicas "
                         "behind the router (homogeneous: each replica "
                         "gets the resolved plan); arrival process "
                         "required, virtual clock only")
    ap.add_argument("--routing", default=None, choices=ROUTING_POLICIES,
                    help="fleet routing policy (choices come from the "
                         "router registry; default round_robin)")
    ap.add_argument("--prefill-replicas", type=int, default=None,
                    metavar="K",
                    help="disaggregate: the first K replicas run "
                         "admission/prefill only and stream slot state "
                         "into the decode replicas over a modeled DCN "
                         "transit (requires --replicas > K)")
    # open-loop arrival process (the paper's asynchronous-serving scenario)
    ap.add_argument("--arrival", default="batch",
                    choices=("batch",) + wl.ARRIVAL_KINDS,
                    help="'batch' submits --requests up front (legacy); "
                         "poisson/mmpp/trace replay an arrival process")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="arrival rate, requests per clock unit")
    ap.add_argument("--duration", type=float, default=64.0,
                    help="workload span in clock units")
    ap.add_argument("--prompt-dist", default="uniform",
                    choices=wl.PROMPT_DISTS,
                    help="prompt-length distribution for generated "
                         "workloads (bimodal = long-tail prompts, the "
                         "regime where preemption pays)")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="stamp generated requests with the absolute "
                         "deadline arrival + SLACK * max_new clock units "
                         "(decode-proportional: slot occupancy is decode "
                         "length on the virtual clock); enables the SLO "
                         "block and gives EDF something to order by")
    ap.add_argument("--deadline-frac", type=float, default=1.0,
                    help="fraction of generated requests carrying a "
                         "deadline (rest are best-effort)")
    ap.add_argument("--trace-file", default=None,
                    help="JSONL trace for --arrival trace (see "
                         "repro.serving.workload.save_trace; traces carry "
                         "their own optional per-request deadlines)")
    ap.add_argument("--clock", default="virtual",
                    choices=("virtual", "wall"),
                    help="virtual: deterministic tick clock; wall: pace "
                         "arrivals in real time")
    ap.add_argument("--truncate-prompts", action="store_true", default=None,
                    help="warn + drop the tail of prompts longer than "
                         "max_len-1 instead of rejecting them (useful when "
                         "replaying traces recorded on a larger engine)")
    # fault tolerance (repro.serving.faults)
    ap.add_argument("--retry-budget", type=int, default=None,
                    help="recoveries per request before it is shed "
                         "(plan default 3)")
    ap.add_argument("--watchdog-ticks", type=int, default=None,
                    help="evict a slot after this many ticks without "
                         "progress (plan default 0 = watchdog off; "
                         "required to serve a fault plan with stall_slot)")
    ap.add_argument("--fault-spec", default=None, metavar="PATH",
                    help="inject faults from a FaultPlan JSON "
                         "(repro.serving.faults) and serve through the "
                         "crash-restartable driver; virtual clock only — "
                         "faults are tick-scheduled and restarts rewind "
                         "time")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="journal engine state here every "
                         "--checkpoint-every ticks while serving under "
                         "--fault-spec (required when the fault plan "
                         "contains kill_engine)")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    help="ticks between engine checkpoints under "
                         "--checkpoint-dir (default 8)")
    # observability (repro.obs)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a structured event trace (request "
                         "lifecycle spans + engine events on the virtual "
                         "clock) and write Chrome trace_event JSON here — "
                         "open it at https://ui.perfetto.dev; same-seed "
                         "virtual-clock runs write byte-identical files")
    ap.add_argument("--live-metrics", type=int, nargs="?", const=32,
                    default=None, metavar="N",
                    help="print a rolling serving line (p95 TTFT/TPOT, "
                         "SLO attainment, utilization over the last N "
                         "ticks) every N engine ticks (default N=32)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="DEBUG logging: per-tick engine utilization lines")
    return ap


def _workload_profile(args) -> WorkloadProfile:
    """The CLI-described workload as a declarative profile (drives both
    the autotuner and the replay loop)."""
    kind = args.arrival if args.arrival != "batch" else "poisson"
    return WorkloadProfile(
        kind=kind, rate=args.rate, duration=args.duration,
        max_new_tokens=(args.max_new, args.max_new),
        prompt_dist=args.prompt_dist,
        deadline_slack=args.deadline_slack,
        deadline_frac=args.deadline_frac,
        trace_path=args.trace_file)


def resolve_plan(args, parser: argparse.ArgumentParser) -> ServingPlan:
    """Turn the parsed CLI into one validated plan.

    Precedence: ``--plan`` file or ``--autotune`` result as the base
    (plain CLI defaults otherwise), then every explicitly-given knob flag
    overrides its plan field — and the override set is recorded under
    ``provenance["cli_overrides"]`` so the served design point is fully
    accounted for."""
    overrides = {}
    for flag, field in _PLAN_FLAGS:
        v = getattr(args, flag)
        if v is not None:
            overrides[field] = v
    if args.no_bucketed_prefill:
        overrides["bucketed_prefill"] = False
    if args.no_overlap_prefill:
        overrides["overlap_prefill"] = False

    if args.plan and args.autotune:
        parser.error("--plan and --autotune are mutually exclusive")
    if args.plan:
        base = plan_io.load_plan(args.plan)
        source = f"file:{args.plan}"
    elif args.autotune:
        if not args.arch:
            parser.error("--autotune requires --arch")
        from repro.plan import planner

        base = planner.autotune(
            args.arch, _workload_profile(args), seed=args.seed,
            reduced=bool(args.reduced),
            max_len=args.max_len or _CLI_DEFAULT_MAX_LEN)
        source = "autotune"
    else:
        if not args.arch:
            parser.error("--arch is required (or pass --plan)")
        base = ServingPlan(arch=args.arch, reduced=bool(args.reduced),
                           max_len=_CLI_DEFAULT_MAX_LEN)
        source = "cli"
    # a typed flag only *overrides* when it changes the base plan's value
    # (e.g. --autotune requires --arch, which the autotuned plan already
    # carries; recording it would misstate the plan's provenance)
    overrides = {k: v for k, v in overrides.items()
                 if getattr(base, k) != v}
    # a max_len override invalidates an explicit bucket set pinned to the
    # old max_len-1 (resolved plans — e.g. BENCH-embedded ones — always
    # carry one): reset it to the new default rather than failing
    # validation, and record the reset like any other override
    new_len = overrides.get("max_len")
    if (new_len is not None and base.buckets is not None
            and base.buckets[-1] != new_len - 1):
        overrides["buckets"] = None
    # tile plans are scored at (arch, max_batch, max_len, hardware) —
    # overriding any of those would leave a stale kernel design half, so
    # recompute them; an explicit --hw-spec always recomputes (the whole
    # point of the flag is rescoring the kernel half for other silicon).
    # On a TPU the spec is the attached chip's, and a plan without tile
    # plans gets the planner's, so the Pallas kernels serve.
    from repro import hw
    from repro.kernels.dispatch import on_tpu

    try:
        if args.hw_spec:
            hw_spec = hw.get_spec(args.hw_spec)
        elif on_tpu():
            hw_spec = hw.spec_for_device(jax.devices()[0])
        else:
            hw_spec = hw.DEFAULT
    except KeyError as e:
        parser.error(str(e))
    stale = {"arch", "max_batch", "max_len"} & set(overrides)
    if (args.hw_spec or (base.tile_plans and stale)
            or (on_tpu() and not base.tile_plans)):
        from repro.plan import planner

        tp = planner.tile_plans_for(
            overrides.get("arch", base.arch),
            overrides.get("max_batch", base.max_batch), hw_spec,
            max_len=overrides.get("max_len", base.max_len))
        if tp != dict(base.tile_plans):
            overrides["tile_plans"] = tp
    plan = dataclasses.replace(base, **overrides) if overrides else base
    prov = dict(plan.provenance)
    prov["source"] = source
    if overrides:
        prov["cli_overrides"] = dict(overrides)
    return dataclasses.replace(plan, provenance=prov).validate()


def _serve_fleet(args, parser, plan) -> None:
    """Serve through a multi-replica :class:`Router` fleet.

    Homogeneous: every replica runs the resolved plan.  The fleet shares
    one deterministic virtual clock, so this path is replay-exact — the
    same seed yields byte-identical fleet schedules."""
    from repro.plan.plan import FleetPlan
    from repro.serving.router import Router, drive_fleet

    n = int(args.replicas or 1)
    k = int(args.prefill_replicas or 0)
    if n < 1:
        parser.error("--replicas must be >= 1")
    if not 0 <= k < n:
        parser.error("--prefill-replicas must leave at least one decode "
                     "replica (need 0 <= K < --replicas)")
    if args.arrival == "batch":
        parser.error("the fleet router needs an arrival process "
                     "(--arrival poisson/mmpp/trace): requests are routed "
                     "on the shared replay clock")
    if args.clock != "virtual":
        parser.error("--replicas requires --clock virtual: the fleet "
                     "replicas share one deterministic clock")
    if args.fault_spec:
        parser.error("--fault-spec does not compose with --replicas: "
                     "fault injection drives a single engine")
    fleet = FleetPlan.replicated(
        plan, n, routing=args.routing or "round_robin", n_prefill=k,
        provenance={"source": "launch.serve"}).validate()
    print(f"fleet: {fleet.summary()}")

    cfg = reduced_config(plan.arch) if plan.reduced else get_config(plan.arch)
    tracers = None
    if args.trace_out:
        from repro.obs import Tracer

        tracers = [Tracer() for _ in range(n)]
    router = Router.from_plan(fleet, seed=args.seed, tracers=tracers)

    profile = _workload_profile(args)
    items = wl.profile_items(profile, vocab_size=cfg.vocab_size,
                             seed=args.seed)
    span = None if args.arrival == "trace" else args.duration
    shown = span if span is not None else max((it.t for it in items),
                                              default=0.0)
    print(f"replaying {len(items)} {args.arrival} arrivals over "
          f"{shown:g} virtual-clock units across {n} replicas "
          f"(offered {wl.offered_load(items, span):.2f} tok/unit)")
    clock = wl.VirtualClock()
    t0 = time.time()
    reqs = drive_fleet(router, items, clock)
    dt = time.time() - t0
    agg = router.fleet_aggregate()
    print(smetrics.format_summary(agg))
    for i, eng in enumerate(router.engines):
        role = "prefill" if i < k else "decode"
        s = eng.stats()
        print(f"  replica[{i}] ({role}): {len(router.assigned[i])} routed, "
              f"{s['ticks']} ticks, {s['prefill_calls']} prefill calls, "
              f"{s['host_syncs']} host syncs")
    if k:
        ts = router.transit_stats()
        print(f"transit: {ts['handoffs']} handoffs, {ts['delivered']} "
              f"delivered, {ts['bytes']} bytes over {ts['ticks']} transit "
              f"ticks (bytes/tick {ts['bytes_per_tick']})")
    census = router.conservation_census()
    if census["total"] != len(reqs):
        raise RuntimeError(f"request conservation violated: {census}")
    print(f"wall: {dt:.2f}s ({len(reqs)} requests conserved)")
    if tracers is not None:
        from repro.obs import dumps_trace_doc, merge_traces

        doc = dumps_trace_doc(merge_traces(tracers))
        with open(args.trace_out, "w") as f:
            f.write(doc)
        print(f"wrote merged fleet trace ({n} replicas) to "
              f"{args.trace_out} (open at https://ui.perfetto.dev)")


def kernel_banner(plan: ServingPlan) -> str:
    """Which kernel serves each layer kind on this backend, e.g.
    ``rwkv[bh2048] -> rwkv=pallas (tpu)``."""
    from repro.kernels.dispatch import kernel_impls
    from repro.plan.plan import tiles_summary

    backend = jax.default_backend()
    if not plan.tile_plans:
        return f"none -> jnp reference path ({backend})"
    impls = " ".join(f"{k}={v}" for k, v in
                     kernel_impls(plan.tile_plans).items())
    return f"{tiles_summary(plan.tile_plans)} -> {impls} ({backend})"


def main() -> None:
    enable_compile_cache()
    parser = build_parser()
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    if args.verbose:  # scope DEBUG to our loggers; root DEBUG floods w/ jax
        logging.getLogger("repro").setLevel(logging.DEBUG)

    plan = resolve_plan(args, parser)
    if args.replicas is not None or args.prefill_replicas:
        _serve_fleet(args, parser, plan)
        return
    if args.routing:
        parser.error("--routing only applies to a fleet; pass --replicas N")
    fault_plan = None
    if args.fault_spec:
        from repro.serving import FaultPlan

        if args.arrival == "batch":
            parser.error("--fault-spec needs an arrival process "
                         "(--arrival poisson/mmpp/trace): faults are "
                         "scheduled on the replay clock")
        if args.clock != "virtual":
            parser.error("--fault-spec requires --clock virtual: faults "
                         "are tick-scheduled and restarts rewind time")
        fault_plan = FaultPlan.load(args.fault_spec)
        if fault_plan.needs_watchdog() and plan.watchdog_ticks <= 0:
            parser.error("the fault plan stalls slots but the watchdog is "
                         "off; pass --watchdog-ticks N (stalled slots only "
                         "recover by watchdog eviction)")
        if fault_plan.needs_checkpoints() and not args.checkpoint_dir:
            parser.error("the fault plan kills the engine; pass "
                         "--checkpoint-dir DIR so it can restart from a "
                         "checkpoint")
    print(f"plan: {plan.summary()}")
    print(f"kernel tiles: {kernel_banner(plan)}")
    if args.save_plan:
        plan_io.save_plan(plan.resolve(), args.save_plan)
        print(f"wrote plan to {args.save_plan}")

    cfg = reduced_config(plan.arch) if plan.reduced else get_config(plan.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sharder = make_sharder(cfg, None, plan.shard_mode)
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        tracer = Tracer()
    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=sharder, seed=args.seed,
                                     tracer=tracer)
    live = (engine.enable_live_metrics(args.live_metrics)
            if args.live_metrics else None)

    def _save_trace() -> None:
        if tracer is not None:
            tracer.save(args.trace_out)
            print(f"wrote {len(tracer)} trace events to {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")

    if args.arrival == "batch":
        rng = np.random.default_rng(args.seed)
        reqs = []
        for _ in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=rng.integers(4, 12)).tolist()
            reqs.append(engine.submit(prompt, max_new_tokens=args.max_new))
        t0 = time.time()
        engine.run()
        dt = time.time() - t0
        total = sum(len(r.output) for r in reqs)
        print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
              f"({total/dt:.1f} tok/s)")
        print(f"engine stats: {engine.stats()}")
        for r in reqs[:3]:
            print(f"  req {r.uid}: prompt[:6]={r.prompt[:6]} -> {r.output[:8]}")
        assert all(r.done for r in reqs)
        if live is not None:
            print(live.line())
        _save_trace()
        return

    profile = _workload_profile(args)
    items = wl.profile_items(profile, vocab_size=cfg.vocab_size,
                             seed=args.seed)
    # declared span for generated workloads; a trace only knows its arrivals
    span = None if args.arrival == "trace" else args.duration
    shown = span if span is not None else max((it.t for it in items),
                                              default=0.0)
    print(f"replaying {len(items)} {args.arrival} arrivals over "
          f"{shown:g} {args.clock}-clock units "
          f"(offered {wl.offered_load(items, span):.2f} tok/unit)")
    if args.clock == "wall":
        # warm the fused decode chunk + the prefill jit cache (one compile
        # per length *bucket* the workload will hit) so tick_seconds
        # measures steady-state serving, not XLA compiles
        for n in sorted({engine.bucket(len(it.prompt)) for it in items}):
            engine.submit([1] * n, max_new_tokens=2)
        engine.run()
        engine.reset_telemetry()
    clock = wl.WallClock() if args.clock == "wall" else wl.VirtualClock()
    on_tick = None
    if live is not None:
        period = args.live_metrics
        last_print = [0]

        def on_tick(tick: int) -> None:
            if tick - last_print[0] >= period:
                last_print[0] = tick
                print(live.line())
    t0 = time.time()
    report = None
    if fault_plan is not None:
        from repro.checkpoint import CheckpointManager
        from repro.serving import FaultInjector, drive_resilient

        manager = (CheckpointManager(args.checkpoint_dir)
                   if args.checkpoint_dir else None)
        report = drive_resilient(engine, items, clock,
                                 injector=FaultInjector(fault_plan),
                                 manager=manager,
                                 checkpoint_every=args.checkpoint_every,
                                 on_tick=on_tick)
        engine = report.engine   # a kill_engine fault swaps the instance
        reqs = report.requests
    else:
        reqs = wl.drive(engine, items, clock, on_tick=on_tick)
    dt = time.time() - t0
    # per-tick cost from busy time only: at low rates most of dt is idle
    # sleep between arrivals, which must not inflate the latency scaling
    tick_s = (clock.busy_seconds / max(1, engine.ticks)
              if args.clock == "wall" else 1.0)
    agg = smetrics.aggregate(reqs, ticks=engine.ticks,
                             util_history=engine.util_history,
                             tick_seconds=tick_s)
    print(smetrics.format_summary(agg))
    s = engine.stats()
    print(f"hot path: {s['host_syncs']} host syncs / {s['ticks']} ticks "
          f"({s['host_syncs'] / max(1, s['ticks']):.2f}/tick, "
          f"sync_every={engine.sync_every}), "
          f"{s['prefill_calls']} prefill calls over "
          f"{s['prefill_compiles']} compiled shapes, "
          f"{s['instant_admits']} instant admits")
    if s["preemptions"] or s["shed"]:
        print(f"scheduler: {s['preemptions']} preemptions / "
              f"{s['resumes']} resumes, {s['evicted_tokens']} tokens "
              f"evicted to host, {s['shed']} requests shed at submit")
    if report is not None:
        fs = engine.fault_stats()
        print(f"faults: {fs['injected']:.0f} injected, "
              f"{fs['quarantined']:.0f} quarantined "
              f"({fs['watchdog_evictions']:.0f} by watchdog), "
              f"{fs['retries']:.0f} retries, {fs['shed']:.0f} shed; "
              f"{report.n_restarts} engine restarts "
              f"({report.restart_ticks_lost} ticks replayed)")
        lost = report.lost_uids()
        if lost:
            raise RuntimeError(f"lost requests (neither done nor shed): "
                               f"{lost}")
        print(f"recovery: {len(report.completed)} completed, "
              f"{len(report.shed_uids)} shed, 0 lost")
    if args.clock == "wall":
        print(f"wall: {dt:.2f}s, {agg['tokens'] / dt:.1f} tok/s measured")
    _save_trace()


if __name__ == "__main__":
    main()
