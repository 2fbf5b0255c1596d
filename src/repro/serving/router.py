"""Multi-replica serving tier: a router over N plan-driven engines.

One level above :class:`~repro.serving.engine.ServingEngine`, this module
scales the serving stack past a single engine the same way the engine
scaled past a single request: a :class:`Router` owns N replicas — each
built ``from_plan`` with its own (possibly heterogeneous) design point —
and load-balances arriving requests across them behind a pluggable
routing-policy registry (:data:`ROUTER_POLICIES`, mirroring how
scheduling policies live behind ``scheduler.SCHEDULERS``).

Two placement modes, selected by ``FleetPlan.n_prefill``:

* **colocated** (``n_prefill=0``) — every replica admits, prefills and
  decodes; the router only chooses *where* each request lands.
* **disaggregated** (``n_prefill=k``) — the first ``k`` replicas run
  admission/prefill only: after a replica's step, every slot holding a
  prefilled request is snapshotted (``SlotManager.snapshot_many`` — the
  batched eviction transport from the preemption path), released, and
  shipped to a decode replica as a :class:`TransitJob`.  The transit is
  charged a modeled DCN latency per snapshot byte (``hw.dcn_bw`` against
  the modeled decode-tick wall time), so disaggregation has a real cost
  axis: for transformer-style KV the snapshot grows with the prompt,
  while RNN/SSM archs ship one O(1) recurrent state column — the paper's
  cheap case — and round to the 1-tick floor.  On delivery the request
  is re-submitted to the decode replica carrying ``req.saved``; the
  engine's resume path restores it without a model call, so the decode
  replica never re-prefills.

The whole tier runs single-process over fake devices on one shared
virtual clock: :func:`drive_fleet` grows :func:`~repro.serving.workload.
drive`'s arrival-bounded loop with transit events, and for a fleet of one
colocated replica it reduces *exactly* to ``drive()`` — same skips, same
budgets, same submission ticks — which is what makes the single-replica
fleet bit-identical to the bare engine (schedule, outputs, metrics), the
anchor the fleet property tests pin.

Cross-replica tick domains: each engine's tick counter lags the clock
while idle (exactly as under ``drive()``).  Colocated replicas never
exchange timestamps, so their compressed per-engine domains stay
internally consistent.  Disaggregated fleets *do* exchange timestamps (a
request's TTFT stamps land on the prefill replica, its completion on the
decode replica), so ``step_all`` first aligns every engine's idle tick
counter to the shared clock (``ServingEngine.align_clock``) — all stamps
then live in one coherent clock domain and cross-replica latency math is
meaningful.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Type

from repro.serving.engine import Request, ServingEngine
from repro.serving.slotstate import SlotSnapshot
from repro.serving.workload import VirtualClock, WorkloadItem

# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------


class RoutingPolicy:
    """Chooses which replica an event goes to.  ``choose`` receives the
    eligible engines (admission set for fresh requests, decode set for
    disaggregated hand-offs — the router keeps one policy instance per
    role so round-robin cursors don't interleave) and returns an index
    into that list.  Implementations must be deterministic: same call
    sequence, same choices — fleet schedules are seed-exact."""

    name = "?"

    def choose(self, engines: Sequence[ServingEngine]) -> int:
        raise NotImplementedError


class RoundRobin(RoutingPolicy):
    """Cycle through the eligible replicas in order — the baseline that
    needs no replica state at all."""

    name = "round_robin"

    def __init__(self):
        self._next = 0

    def choose(self, engines: Sequence[ServingEngine]) -> int:
        k = self._next % len(engines)
        self._next += 1
        return k


def _queue_depth(e: ServingEngine) -> int:
    return len(e.scheduler) + e.sm.n_active()


class LeastQueue(RoutingPolicy):
    """Join the shortest queue: pending + in-slot requests, ties to the
    lowest replica index.  The classic supermarket rule; reacts to
    heterogeneous replica capacity where round-robin cannot."""

    name = "least_queue"

    def choose(self, engines: Sequence[ServingEngine]) -> int:
        return min(range(len(engines)),
                   key=lambda k: (_queue_depth(engines[k]), k))


class SLOFeedback(RoutingPolicy):
    """Route by each replica's *observed* serving quality: prefer the
    replica with the lowest rolling p95 TTFT from its ``LiveMetrics``
    window (``Router.from_plan`` enables the window when this policy is
    selected).  A replica with no completed requests in its window scores
    a TTFT of 0 — cold replicas attract traffic until they have a track
    record — and ties fall back to least-queue, then lowest index, so
    the choice stays deterministic."""

    name = "slo_feedback"

    def choose(self, engines: Sequence[ServingEngine]) -> int:
        def score(k: int):
            e = engines[k]
            ttft = 0.0
            if e.live is not None:
                s = e.live.snapshot()
                v = s["ttft_p95"]
                if s["completed"] and not math.isnan(v):
                    ttft = float(v)
            return (ttft, _queue_depth(e), k)

        return min(range(len(engines)), key=score)


ROUTER_POLICIES: Dict[str, Type[RoutingPolicy]] = {
    p.name: p for p in (RoundRobin, LeastQueue, SLOFeedback)}
ROUTING_POLICIES = tuple(ROUTER_POLICIES)   # CLI choices, registry order


def make_routing_policy(name: str,
                        registry: Optional[Dict[str, Type[RoutingPolicy]]]
                        = None) -> RoutingPolicy:
    registry = ROUTER_POLICIES if registry is None else registry
    if name not in registry:
        raise ValueError(f"unknown routing policy {name!r} "
                         f"(known: {sorted(registry)})")
    return registry[name]()


# ---------------------------------------------------------------------------
# transit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TransitJob:
    """One prefill→decode state hand-off in flight: the request, its slot
    snapshot, and when the modeled DCN transfer completes (absolute clock
    units)."""

    req: Request
    snap: SlotSnapshot
    src: int         # prefill replica index
    dst: int         # decode replica index
    due: float       # clock time the snapshot finishes arriving
    nbytes: int
    ticks: int       # charged transit latency in clock ticks


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


class Router:
    """Load balancer + transit broker over a fleet of serving engines.

    Owns the replica engines, the routing-policy instances (one for
    admission, one for disaggregated dispatch), and the in-flight
    :class:`TransitJob` queue.  Driven by :func:`drive_fleet`; the
    conservation invariant — every submitted request is in exactly one
    place (a replica's queue/slot/finished list, or one transit job) —
    is what the fleet property harness checks."""

    def __init__(self, fleet, engines: Sequence[ServingEngine]):
        from repro.plan.plan import FleetPlan

        if not isinstance(fleet, FleetPlan):
            raise TypeError(f"Router needs a FleetPlan, "
                            f"got {type(fleet).__name__}")
        fleet.validate()
        if len(engines) != len(fleet.replicas):
            raise ValueError(f"fleet names {len(fleet.replicas)} replicas "
                             f"but {len(engines)} engines were supplied")
        self.fleet = fleet
        self.engines: List[ServingEngine] = list(engines)
        self.policy = make_routing_policy(fleet.routing)
        self._dispatch = make_routing_policy(fleet.routing)
        self.requests: List[Request] = []        # arrival order
        self.assigned: List[List[Request]] = [[] for _ in self.engines]
        self.transits: List[TransitJob] = []     # sorted by due
        self.n_handoffs = 0
        self.n_delivered = 0
        self.transit_bytes_total = 0
        self.transit_ticks_total = 0
        self._bytes_per_tick: Optional[float] = None

    # ------------------------------------------------------------ construction
    @classmethod
    def from_plan(cls, fleet, *, seed: int = 0,
                  tracers: Optional[Sequence] = None,
                  _built=None) -> "Router":
        """Build the fleet from its plan.  Replica ``i`` gets engine seed
        ``seed + i`` (replica 0 keeps the caller's seed, so a one-replica
        fleet seeds identically to a bare engine).  Model/params builds
        are shared across replicas with the same ``(arch, reduced)``
        identity; pass ``_built`` (a ``{(arch, reduced): (model, params)}``
        dict) to reuse builds across fleets the way the benchmark sweeps
        do.  ``tracers`` optionally supplies one ``repro.obs.Tracer`` per
        replica (merge them with ``obs.trace.merge_traces``)."""
        fleet.validate()
        if tracers is not None and len(tracers) != len(fleet.replicas):
            raise ValueError(f"need one tracer per replica: got "
                             f"{len(tracers)} for {len(fleet.replicas)}")
        built = _built if _built is not None else {}
        engines = []
        for i, plan in enumerate(fleet.replicas):
            key = (plan.arch, plan.reduced)
            if key not in built:
                import jax

                from repro.configs import get_config
                from repro.models.lm import build_model
                from repro.testing import reduced_config

                cfg = (reduced_config(plan.arch) if plan.reduced
                       else get_config(plan.arch))
                model = build_model(cfg)
                # the replicas share one serving copy of the weights
                params, _ = model.serving_params(
                    model.init(jax.random.PRNGKey(0)))
                built[key] = (model, params)
            model, params = built[key]
            eng = ServingEngine.from_plan(
                plan, params, model=model, seed=seed + i,
                tracer=None if tracers is None else tracers[i])
            if fleet.routing == "slo_feedback":
                eng.enable_live_metrics()
            engines.append(eng)
        return cls(fleet, engines)

    # ------------------------------------------------------------ replica sets
    @property
    def n_prefill(self) -> int:
        return self.fleet.n_prefill

    def admit_set(self) -> List[int]:
        """Replica indices eligible for fresh submissions: the prefill
        replicas when disaggregated, everyone when colocated."""
        if self.n_prefill:
            return list(range(self.n_prefill))
        return list(range(len(self.engines)))

    def decode_set(self) -> List[int]:
        return list(range(self.n_prefill, len(self.engines)))

    def _route(self, policy: RoutingPolicy, idxs: Sequence[int]) -> int:
        cands = [self.engines[i] for i in idxs]
        return idxs[policy.choose(cands)]

    # -------------------------------------------------------------- admission
    def submit(self, item: WorkloadItem) -> Request:
        """Route one arrival to a replica and submit it there — the fleet
        analogue of ``engine.submit`` (same argument mapping as
        ``drive()``, so a one-replica fleet stamps identically)."""
        idx = self._route(self.policy, self.admit_set())
        req = self.engines[idx].submit(
            list(item.prompt), item.max_new_tokens, item.eos_id,
            deadline=item.deadline)
        self.requests.append(req)
        self.assigned[idx].append(req)
        return req

    # ---------------------------------------------------------------- driving
    def engines_have_work(self) -> bool:
        return any(e.has_work() for e in self.engines)

    def has_work(self) -> bool:
        return self.engines_have_work() or bool(self.transits)

    @property
    def ticks(self) -> int:
        return max(e.ticks for e in self.engines)

    def step_all(self, budget: Optional[int], now: Optional[float] = None
                 ) -> int:
        """One fleet scheduling round: step every replica with the tick
        budget (prefill replicas are capped at 1 tick — they exist to
        admit, not to decode) and return the widest per-replica tick
        advance, which is how far the shared clock moves.  In
        disaggregated mode the engines' idle tick counters are first
        aligned to the shared clock so cross-replica stamps cohere."""
        if self.n_prefill and now is not None:
            for e in self.engines:
                e.align_clock(int(now))
        delta = 0
        for i, e in enumerate(self.engines):
            cap = 1 if i < self.n_prefill else budget
            before = e.ticks
            e.step(max_ticks=cap)
            delta = max(delta, e.ticks - before)
        return delta

    # ----------------------------------------------------------- transit side
    @property
    def bytes_per_tick(self) -> float:
        """Modeled DCN transit bandwidth per virtual-clock tick:
        ``hw.dcn_bw × modeled_tick_seconds`` for the fleet's reference
        replica (the planner's roofline decode-tick wall time), unless
        the plan pins ``transit_bytes_per_tick`` directly.  A spec with
        no DCN (``dcn_bw <= 0``) yields ``inf`` — transits then take the
        1-tick floor."""
        if self._bytes_per_tick is None:
            if self.fleet.transit_bytes_per_tick is not None:
                self._bytes_per_tick = float(
                    self.fleet.transit_bytes_per_tick)
            else:
                from repro import hw
                from repro.plan.planner import modeled_tick_seconds

                spec = hw.get_spec(self.fleet.hw)
                ref = self.fleet.replicas[0]
                if spec.dcn_bw > 0:
                    self._bytes_per_tick = spec.dcn_bw * \
                        modeled_tick_seconds(ref.arch, ref.max_batch, spec)
                else:
                    self._bytes_per_tick = math.inf
        return self._bytes_per_tick

    def transit_ticks(self, nbytes: int) -> int:
        """Clock ticks charged to ship one snapshot: ceil over the
        modeled per-tick DCN bytes, floored at one tick (nothing arrives
        the instant it leaves).  O(1) RNN/SSM state columns round to the
        floor — the paper's cheap hand-off."""
        bpt = self.bytes_per_tick
        if not math.isfinite(bpt) or bpt <= 0:
            return 1
        return max(1, int(math.ceil(nbytes / bpt)))

    def collect_handoffs(self, now: float) -> int:
        """Sweep prefill replicas for finished prefill state: every
        occupied slot whose request already has its first token is
        snapshotted (one batched gather per replica), released, and put
        in transit to a policy-chosen decode replica.  Slots whose
        overlapped first token is still on device are left for the next
        sweep; requests that completed inside the prefill step finished
        there and never transit.  Compatibility is checked against the
        destination *before* the job is queued, so an impossible
        hand-off fails at the source with a field-naming error."""
        if not self.n_prefill:
            return 0
        moved = 0
        for src in range(self.n_prefill):
            eng = self.engines[src]
            ready = [(slot, req) for slot, req in eng.sm.running()
                     if len(req.output) >= 1]
            if not ready:
                continue
            snaps = eng.sm.snapshot_many([slot for slot, _ in ready])
            for (slot, req), snap in zip(ready, snaps):
                eng.sm.release(slot)
                dst = self._route(self._dispatch, self.decode_set())
                self.engines[dst].sm.check_snapshot_compat(snap)
                nbytes = snap.nbytes()
                ticks = self.transit_ticks(nbytes)
                self.transits.append(TransitJob(
                    req=req, snap=snap, src=src, dst=dst,
                    due=now + ticks, nbytes=nbytes, ticks=ticks))
                self.n_handoffs += 1
                self.transit_bytes_total += nbytes
                self.transit_ticks_total += ticks
                moved += 1
        if moved:
            self.transits.sort(key=lambda t: t.due)   # stable: FIFO on ties
        return moved

    def next_transit_due(self) -> float:
        return self.transits[0].due

    def deliver_due(self, now: float) -> int:
        """Deliver every transit whose modeled transfer has completed:
        re-check compatibility, attach the snapshot as ``req.saved`` and
        submit to the decode replica's scheduler — the engine's resume
        path restores it into a slot with no model call, no re-prefill."""
        n = 0
        while self.transits and self.transits[0].due <= now + 1e-9:
            job = self.transits.pop(0)
            dst = self.engines[job.dst]
            dst.sm.check_snapshot_compat(job.snap)
            job.req.saved = job.snap
            dst.scheduler.submit(job.req)
            self.n_delivered += 1
            n += 1
        return n

    # -------------------------------------------------------------- reporting
    def parts(self) -> List[Tuple[List[Request], int, List[float]]]:
        """Per-replica ``(requests, ticks, util_history)`` triples for
        :func:`repro.serving.metrics.aggregate_fleet` — requests are
        attributed to the replica that *admitted* them (for a one-replica
        fleet this is exactly the bare ``aggregate`` input)."""
        return [(list(self.assigned[i]), e.ticks, list(e.util_history))
                for i, e in enumerate(self.engines)]

    def fleet_aggregate(self, *, tick_seconds: float = 1.0
                        ) -> Dict[str, object]:
        from repro.serving.metrics import aggregate_fleet

        return aggregate_fleet(self.parts(), tick_seconds=tick_seconds)

    def transit_stats(self) -> Dict[str, object]:
        bpt = self.bytes_per_tick if self.n_handoffs else None
        return {
            "handoffs": int(self.n_handoffs),
            "delivered": int(self.n_delivered),
            "in_flight": len(self.transits),
            "bytes": int(self.transit_bytes_total),
            "ticks": int(self.transit_ticks_total),
            "bytes_per_tick": (float(bpt) if bpt is not None
                               and math.isfinite(bpt) else None),
        }

    def conservation_census(self) -> Dict[str, int]:
        """Where every submitted request currently lives — the property
        harness asserts the sum equals the number of arrivals, with no
        request counted twice."""
        queued = sum(len(e.scheduler) for e in self.engines)
        in_slot = sum(e.sm.n_active() for e in self.engines)
        finished = sum(len(e.finished) for e in self.engines)
        shed = sum(1 for r in self.requests if r.shed)
        return {"queued": queued, "in_slot": in_slot,
                "in_transit": len(self.transits), "finished": finished,
                "shed": shed,
                "total": queued + in_slot + len(self.transits)
                + finished + shed}


# ---------------------------------------------------------------------------
# the fleet drive loop
# ---------------------------------------------------------------------------


def drive_fleet(router: Router, items: Sequence[WorkloadItem],
                clock=None, max_ticks: int = 1_000_000,
                sync_every: Optional[int] = None,
                on_tick=None) -> List[Request]:
    """Replay a workload against a fleet on one shared clock — the
    fleet-aware growth of :func:`repro.serving.workload.drive`, event for
    event: idle skips jump to the next arrival *or* transit completion
    (whichever lands first), per-round tick budgets never step the fleet
    past either event, and the clock advances by the widest per-replica
    tick delta each round.  For a one-replica colocated fleet every
    branch degenerates to ``drive()``'s — same skips, budgets and
    submission ticks — so the single-replica fleet schedule is
    bit-identical to the bare engine's.

    Returns the submitted :class:`Request` objects in arrival order (all
    done or shed once the fleet drains), exactly like ``drive()``."""
    if clock is None:
        clock = VirtualClock()
    pending = sorted(items, key=lambda it: it.t)
    i = 0
    busy = 0.0
    for _ in range(max_ticks):
        if not router.engines_have_work():
            horizons = []
            if i < len(pending):
                horizons.append(pending[i].t)
            if router.transits:
                horizons.append(router.next_transit_due())
            if horizons:
                clock.skip_to(min(horizons))   # idle: jump to next event
        router.deliver_due(clock.now)
        while i < len(pending) and pending[i].t <= clock.now:
            router.submit(pending[i])
            i += 1
        if not router.has_work() and i >= len(pending):
            clock.busy_seconds = busy
            return list(router.requests)
        budget = sync_every
        if isinstance(clock, VirtualClock):
            # never step past the next arrival or transit completion
            horizons = []
            if i < len(pending):
                horizons.append(pending[i].t)
            if router.transits:
                horizons.append(router.next_transit_due())
            if horizons:
                gap = min(horizons) - clock.now
                due = max(1, math.ceil(gap / clock.tick_cost)) \
                    if gap > 0 else 1
                budget = due if budget is None else min(budget, due)
        t0 = time.perf_counter()
        delta = router.step_all(budget, now=clock.now)
        busy += time.perf_counter() - t0
        for _ in range(delta):
            clock.tick()
        router.collect_handoffs(clock.now)
        if on_tick is not None and delta:
            on_tick(router.ticks)
    raise RuntimeError(f"fleet workload did not drain within {max_ticks} "
                       f"rounds ({i}/{len(pending)} submitted, "
                       f"{len(router.transits)} transits in flight)")


__all__ = ["ROUTER_POLICIES", "ROUTING_POLICIES", "RoutingPolicy",
           "RoundRobin", "LeastQueue", "SLOFeedback",
           "make_routing_policy", "Router", "TransitJob", "drive_fleet"]
