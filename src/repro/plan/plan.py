"""`ServingPlan`: every serving design parameter behind one frozen object.

The paper's central claim is that a spatial accelerator stays efficient
across problem sizes because the design parameters (tiling, unrolling,
residency) live behind a general abstraction that a per-problem-size
search can optimize — `repro.core.dse` already does this at the kernel
level (`Plan`, `search`, `best_plan`).  The serving layer had grown the
opposite way: the same ~10 knobs (max_batch, bucket set, sync_every,
policy, preemption, overlap, sampler, sharding mode) threaded by hand
through `ServingEngine.__init__`, 20+ `launch/serve.py` flags, and the
benchmark's `ServingLoadCell`.  This module promotes the design-space
idea to the whole serving stack:

* :class:`ServingPlan` — a frozen, JSON-serializable dataclass that is
  the *single source of truth* for every serving design parameter.  The
  engine is constructed from it (`ServingEngine.from_plan`), the CLI
  loads/saves it (`--plan` / `--save-plan`), and every committed BENCH
  cell embeds the resolved plan dict so the perf trajectory records
  *which* design point produced each number.
* :class:`WorkloadProfile` — the workload half of a serving cell (arrival
  process, prompt/decode length distributions, deadlines): the "problem
  size" the planner searches against.
* :func:`repro.plan.planner.autotune` — the serving-level analogue of
  `core.dse.best_plan`: searches (bucket set x sync_every x max_batch x
  policy) against the `repro.hw` cost model plus a short virtual-clock
  probe run and returns the best plan per (arch, workload).

Defaults resolve to the engine's historical behavior exactly: a default
plan produces a bit-identical schedule to the pre-plan engine, which is
what keeps the committed ``BENCH_serving.json`` metrics blocks stable.

This module is dependency-light on purpose (stdlib only): it is imported
by ``repro.configs`` (cells embed plans) and ``repro.serving.engine``
without dragging jax or the model stack in.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Tuple

MIN_BUCKET = 8   # smallest prefill length bucket (pow2 upward, cap max_len-1)
# padded prompt tokens one bucketed prefill call holds (rows x bucket),
# beyond one row: bounds a call's activations and cache output, and the
# padding rows a lone admission pays for (24 rows of a 2048 bucket would
# be a 4.8 GB cache output a chip for qwen2.5-14b over four chips)
PREFILL_TOKENS = 1024


def parse_cache_layout(layout: str) -> Optional[int]:
    """Parse a ``ServingPlan.cache_layout`` string.

    ``"dense"`` → None (fixed per-slot cache columns);
    ``"paged:<block_size>"`` → the positive int block size (block-table
    pool, KV rings paged along the length axis).  Raises ``ValueError``
    on anything else — this is the single validation point shared by
    ``ServingPlan.validate`` and the slot-manager factory."""
    if layout == "dense":
        return None
    if isinstance(layout, str) and layout.startswith("paged:"):
        tail = layout[len("paged:"):]
        try:
            block = int(tail)
        except ValueError:
            block = 0
        if block >= 1 and str(block) == tail:
            return block
    raise ValueError(
        f"cache_layout must be 'dense' or 'paged:<block_size>' with a "
        f"positive integer block size, got {layout!r}")


def default_buckets(max_len: int) -> Tuple[int, ...]:
    """The historical pow2 bucket set: MIN_BUCKET doubling up to, and
    capped at, ``max_len - 1`` (the engine's prefill compile ceiling)."""
    limit = max_len - 1
    out: List[int] = []
    b = MIN_BUCKET
    while b < limit:
        out.append(b)
        b *= 2
    out.append(limit)
    return tuple(out)


def _jsonify(x):
    """Canonicalize nested containers to plain JSON types so a plan that
    round-trips through JSON compares equal to the original."""
    if isinstance(x, Mapping):
        return {str(k): _jsonify(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonify(v) for v in x]
    if isinstance(x, bool) or x is None or isinstance(x, (int, float, str)):
        return x
    return str(x)


@dataclasses.dataclass(frozen=True)
class WorkloadProfile:
    """The workload side of a serving cell: what arrives, how long it is,
    and what SLO it carries.  A pure description — materialize it with
    :func:`repro.serving.workload.profile_items` (seeded, deterministic).

    ``duration=None`` means "caller decides" (the benchmark sweep's fast /
    full switch); every other field mirrors the corresponding
    :func:`repro.serving.workload.make_workload` argument.
    """

    kind: str = "poisson"                    # workload.ARRIVAL_KINDS
    rate: float = 0.5                        # requests per clock unit
    duration: Optional[float] = None         # span in clock units
    prompt_len: Tuple[int, int] = (4, 12)
    max_new_tokens: Tuple[int, int] = (8, 16)
    prompt_dist: str = "uniform"             # workload.PROMPT_DISTS
    prompt_len_long: Optional[int] = None    # long-tail cap
    heavy_decode: Optional[Tuple[float, int, int]] = None
    deadline_slack: Optional[float] = None   # decode-proportional SLO
    deadline_frac: float = 1.0
    burst_factor: float = 4.0                # mmpp only
    dwell: Tuple[float, float] = (16.0, 4.0)  # mmpp only
    trace_path: Optional[str] = None         # kind == "trace"

    def __post_init__(self):
        object.__setattr__(self, "prompt_len", tuple(self.prompt_len))
        object.__setattr__(self, "max_new_tokens",
                           tuple(self.max_new_tokens))
        object.__setattr__(self, "dwell", tuple(self.dwell))
        if self.heavy_decode is not None:
            f, lo, hi = self.heavy_decode
            object.__setattr__(self, "heavy_decode",
                               (float(f), int(lo), int(hi)))

    @property
    def has_deadlines(self) -> bool:
        return self.deadline_slack is not None and self.deadline_frac > 0

    def mean_decode(self) -> float:
        """Expected decode length per request (slot-occupancy ticks on the
        virtual clock) — the planner's service-time estimate."""
        lo, hi = self.max_new_tokens
        mean = (lo + hi) / 2.0
        if self.heavy_decode is not None:
            f, hlo, hhi = self.heavy_decode
            mean = (1 - f) * mean + f * (hlo + hhi) / 2.0
        return mean

    def to_json(self) -> Dict[str, object]:
        return _jsonify(dataclasses.asdict(self))

    @staticmethod
    def from_json(d: Mapping[str, object]) -> "WorkloadProfile":
        # list -> tuple coercion happens in __post_init__
        return WorkloadProfile(**dict(d))

    @staticmethod
    def from_trace(trace, *, kind: str = "poisson",
                   duration: Optional[float] = None) -> "WorkloadProfile":
        """Fit a profile from *observed* traffic: a recorded
        :class:`repro.obs.Tracer` (live object, exported Chrome-trace
        document, or file path).  See :func:`repro.obs.observe.fit_profile`
        for the estimators; ``autotune(WorkloadProfile.from_trace(t))``
        replans from what actually arrived instead of what was declared."""
        from repro.obs.observe import fit_profile

        return fit_profile(trace, kind=kind, duration=duration)


@dataclasses.dataclass(frozen=True)
class ServingPlan:
    """One serving design point: everything the stack needs to decide *how*
    to serve, in one frozen, JSON-round-trippable object.

    Field groups, in order:

    * model identity — ``arch`` (the ``repro.configs`` id), ``reduced``
      (CPU-sized config), ``shard_mode`` (the ``repro.dist`` rules key);
    * capacity — ``max_batch`` decode slots over a ``max_len`` cache,
      backed dense (fixed per-slot columns) or paged (``cache_layout =
      "paged:<block_size>"``: a block-table pool, see
      :mod:`repro.serving.paged`);
    * admission — ``bucketed_prefill`` plus the explicit ``buckets`` set
      (``None`` = the historical pow2 set, see :func:`default_buckets`);
    * decode hot path — ``sync_every`` on-device ticks per host sync,
      ``overlap_prefill`` admission/decode overlap;
    * scheduling — ``policy`` (scheduler-registry key), ``preempt``,
      ``shed_late`` (deadline-aware admission control: reject provably-
      late requests at submit);
    * sampling — ``temperature`` / ``top_k``
      (= :class:`repro.serving.sampler.SamplerConfig`);
    * ``tile_plans`` — per-kernel tile plans: one embedded
      ``core.dse.Plan`` dict per recurrent layer kind, scored at this
      plan's ``max_batch`` (the kernel-level half of the design point);
    * ``provenance`` — where the plan came from (CLI overrides, autotune
      search record); never affects behavior, always recorded.
    """

    # --- model identity --------------------------------------------------
    arch: str
    reduced: bool = True
    shard_mode: str = "decode"
    # --- capacity --------------------------------------------------------
    max_batch: int = 4
    max_len: int = 128
    cache_layout: str = "dense"   # or "paged:<block_size>"
    # --- admission -------------------------------------------------------
    bucketed_prefill: bool = True
    buckets: Optional[Tuple[int, ...]] = None
    # --- decode hot path -------------------------------------------------
    sync_every: int = 1
    overlap_prefill: bool = True
    # --- scheduling ------------------------------------------------------
    policy: str = "fcfs"
    preempt: bool = False
    shed_late: bool = False
    # --- sampling --------------------------------------------------------
    temperature: float = 0.0
    top_k: int = 0
    # --- misc engine behavior -------------------------------------------
    truncate_prompts: bool = False
    # --- fault tolerance -------------------------------------------------
    # retry_budget: recoveries (rollback / re-prefill) a request may
    # consume before it is shed; watchdog_ticks: evict a slot that made no
    # progress for this many ticks (0 = watchdog off).  Both only matter
    # when faults fire — serialization omits them at their defaults, so
    # existing plan dicts and BENCH cells are unchanged (see plan.io).
    retry_budget: int = 3
    watchdog_ticks: int = 0
    # --- per-kernel tile plans + provenance ------------------------------
    tile_plans: Mapping[str, Mapping[str, object]] = dataclasses.field(
        default_factory=dict)
    provenance: Mapping[str, object] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        if self.buckets is not None:
            object.__setattr__(self, "buckets",
                               tuple(int(b) for b in self.buckets))
        object.__setattr__(self, "tile_plans", _jsonify(self.tile_plans))
        object.__setattr__(self, "provenance", _jsonify(self.provenance))

    # ------------------------------------------------------------ validation
    def validate(self) -> "ServingPlan":
        """Structural validation; raises ``ValueError`` on the first
        problem, returns ``self`` so construction can chain.  Policy names
        are checked against the live scheduler registry so a plan can
        never name a policy the engine does not implement."""
        if not self.arch or not isinstance(self.arch, str):
            raise ValueError(f"plan.arch must be a non-empty string, "
                             f"got {self.arch!r}")
        if self.max_batch < 1:
            raise ValueError(f"plan.max_batch must be >= 1, "
                             f"got {self.max_batch}")
        if self.max_len < 2:
            raise ValueError(f"plan.max_len must be >= 2 (one prompt token "
                             f"+ one generated), got {self.max_len}")
        block = parse_cache_layout(self.cache_layout)  # raises on bad form
        if block is not None and block > self.max_len:
            raise ValueError(
                f"plan.cache_layout block size {block} exceeds max_len "
                f"{self.max_len}: a block never covers more than one ring")
        if self.sync_every < 1:
            raise ValueError(f"plan.sync_every must be >= 1, "
                             f"got {self.sync_every}")
        if self.temperature < 0:
            raise ValueError(f"plan.temperature must be >= 0, "
                             f"got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"plan.top_k must be >= 0, got {self.top_k}")
        if self.retry_budget < 0:
            raise ValueError(f"plan.retry_budget must be >= 0, "
                             f"got {self.retry_budget}")
        if self.watchdog_ticks < 0:
            raise ValueError(f"plan.watchdog_ticks must be >= 0 "
                             f"(0 disables the watchdog), "
                             f"got {self.watchdog_ticks}")
        from repro.serving.scheduler import SCHEDULERS, make_scheduler
        if self.policy not in SCHEDULERS:
            raise ValueError(f"plan.policy {self.policy!r} is not in the "
                             f"scheduler registry {sorted(SCHEDULERS)}")
        make_scheduler(self.policy, preempt=self.preempt)  # preempt support
        if self.buckets is not None:
            bs = self.buckets
            if not bs:
                raise ValueError("plan.buckets must be non-empty or None")
            if list(bs) != sorted(set(bs)):
                raise ValueError(f"plan.buckets must be strictly "
                                 f"increasing, got {bs}")
            if bs[0] < 1:
                raise ValueError(f"plan.buckets must be >= 1, got {bs}")
            if bs[-1] != self.max_len - 1:
                raise ValueError(
                    f"plan.buckets must end at max_len-1 = "
                    f"{self.max_len - 1} so every admissible prompt has a "
                    f"bucket, got {bs}")
        _validate_tile_plans(self.tile_plans)
        return self

    # ------------------------------------------------------------ resolution
    def prefill_rows(self, bucket: int) -> int:
        """Rows of one bucketed prefill call at ``bucket`` tokens: as
        many as ``PREFILL_TOKENS`` holds, at least one and at most
        ``max_batch``."""
        return max(1, min(self.max_batch, PREFILL_TOKENS // bucket))

    def resolved_buckets(self) -> Tuple[int, ...]:
        """The explicit bucket set this plan serves with (the pow2 default
        when ``buckets`` is None).  In non-bucketed mode prefill pads to
        the exact prompt length; the returned set is then only the
        compile-ceiling bound of bucketed mode."""
        if self.buckets is not None:
            return self.buckets
        return default_buckets(self.max_len)

    def resolve(self) -> "ServingPlan":
        """A copy with every defaulted design choice made explicit
        (currently: the bucket set) — what the BENCH files embed, so a
        committed cell is re-runnable without knowing the defaults of the
        code that produced it."""
        if not self.bucketed_prefill or self.buckets is not None:
            return self
        return dataclasses.replace(self, buckets=self.resolved_buckets())

    def summary(self) -> str:
        """One-line human identity for CLI banners and logs."""
        b = ("exact" if not self.bucketed_prefill
             else "pow2" if self.buckets is None
             else ",".join(map(str, self.buckets)))
        bits = [self.arch + ("(reduced)" if self.reduced else ""),
                f"b{self.max_batch}", f"len{self.max_len}",
                f"sync{self.sync_every}",
                self.policy + ("+p" if self.preempt else ""),
                f"buckets={b}"]
        if self.cache_layout != "dense":
            bits.append(self.cache_layout)
        if self.shed_late:
            bits.append("shed")
        if not self.overlap_prefill:
            bits.append("no-overlap")
        if self.temperature > 0:
            bits.append(f"T={self.temperature:g}")
        if self.retry_budget != 3:
            bits.append(f"retry{self.retry_budget}")
        if self.watchdog_ticks > 0:
            bits.append(f"wd{self.watchdog_ticks}")
        return " ".join(bits)


@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """One multi-replica serving design point: N per-replica
    :class:`ServingPlan`\\ s (possibly heterogeneous), a routing policy
    from the router registry, and the prefill/decode disaggregation
    split.  The fleet-level analogue of :class:`ServingPlan` — the router
    is constructed from it (``Router.from_plan``), ``planner.
    autotune_fleet`` searches over it coarsely, and fleet BENCH cells
    embed the resolved dict.

    ``n_prefill = 0`` is the colocated mode: every replica admits,
    prefills and decodes.  ``n_prefill = k > 0`` disaggregates: the first
    ``k`` replicas run admission/prefill only and stream finished slot
    state into the remaining decode replicas over a modeled DCN transit
    (cost per snapshot byte from :mod:`repro.hw` — ``hw`` names the
    spec; ``transit_bytes_per_tick`` overrides the derived rate, mostly
    for tests).
    """

    replicas: Tuple[ServingPlan, ...]
    routing: str = "round_robin"
    n_prefill: int = 0
    transit_bytes_per_tick: Optional[float] = None
    hw: str = "tpu-v5e"
    provenance: Mapping[str, object] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "replicas", tuple(self.replicas))
        object.__setattr__(self, "provenance", _jsonify(self.provenance))

    @staticmethod
    def replicated(plan: ServingPlan, n: int, *,
                   routing: str = "round_robin", n_prefill: int = 0,
                   **kw) -> "FleetPlan":
        """Homogeneous fleet: ``n`` copies of one replica plan."""
        return FleetPlan(replicas=(plan,) * int(n), routing=routing,
                         n_prefill=n_prefill, **kw)

    @property
    def n_replicas(self) -> int:
        return len(self.replicas)

    def validate(self) -> "FleetPlan":
        """Structural validation; raises ``ValueError`` on the first
        problem, returns ``self``.  Routing names are checked against the
        live router registry (lazy import, mirroring how per-replica
        plans check the scheduler registry); disaggregation additionally
        pins the snapshot-compat invariants — every replica must share
        arch/reduced/max_len or a prefill→decode transit could never
        restore (``SlotManager.check_snapshot_compat`` would reject it)."""
        if not self.replicas:
            raise ValueError("fleet.replicas must name at least one replica")
        if not (0 <= self.n_prefill < len(self.replicas)):
            raise ValueError(
                f"fleet.n_prefill must leave at least one decode replica: "
                f"got n_prefill={self.n_prefill} of "
                f"{len(self.replicas)} replicas")
        if self.transit_bytes_per_tick is not None \
                and self.transit_bytes_per_tick <= 0:
            raise ValueError(
                f"fleet.transit_bytes_per_tick must be > 0 when set, "
                f"got {self.transit_bytes_per_tick}")
        from repro import hw
        if self.hw not in hw.SPECS:
            raise ValueError(f"fleet.hw {self.hw!r} is not a known "
                             f"hardware spec {sorted(hw.SPECS)}")
        from repro.serving.router import ROUTER_POLICIES
        if self.routing not in ROUTER_POLICIES:
            raise ValueError(
                f"fleet.routing {self.routing!r} is not in the router "
                f"registry {sorted(ROUTER_POLICIES)}")
        for i, plan in enumerate(self.replicas):
            if not isinstance(plan, ServingPlan):
                raise ValueError(f"fleet.replicas[{i}] must be a "
                                 f"ServingPlan, got {type(plan).__name__}")
            try:
                plan.validate()
            except ValueError as e:
                raise ValueError(f"fleet.replicas[{i}]: {e}") from e
        if self.n_prefill > 0:
            ref = self.replicas[0]
            for i, plan in enumerate(self.replicas):
                for field in ("arch", "reduced", "max_len"):
                    if getattr(plan, field) != getattr(ref, field):
                        raise ValueError(
                            f"disaggregated fleets need snapshot-compatible "
                            f"replicas: replicas[{i}].{field}="
                            f"{getattr(plan, field)!r} differs from "
                            f"replicas[0].{field}={getattr(ref, field)!r}")
        return self

    def resolve(self) -> "FleetPlan":
        """A copy with every replica plan resolved (explicit buckets) —
        what fleet BENCH cells embed."""
        return dataclasses.replace(
            self, replicas=tuple(p.resolve() for p in self.replicas))

    def summary(self) -> str:
        # plans hold dict fields (tile_plans, provenance) so they are not
        # hashable; collapse homogeneous fleets by equality instead
        homogeneous = all(p == self.replicas[0] for p in self.replicas[1:])
        parts = [f"{len(self.replicas)}x[{self.replicas[0].summary()}]"
                 if homogeneous else
                 " | ".join(p.summary() for p in self.replicas),
                 f"routing={self.routing}"]
        if self.n_prefill:
            parts.append(f"prefill={self.n_prefill}/"
                         f"{len(self.replicas)}")
        return " ".join(parts)


# ---------------------------------------------------------------------------
# tile_plans validation
# ---------------------------------------------------------------------------

# kernel kinds a tile_plans entry may target: the model's layer kinds plus
# the two standalone kernels (fused_rnn cell serving, W8A16 matmul)
TILE_PLAN_KINDS = ("rwkv", "swa_ssm", "attn", "local",
                   "fused_rnn", "matmul_int8")
_TILE_FIELDS = ("bh", "bq", "bk", "bm", "bn")
_META_FIELDS = ("n_tiles", "vmem_bytes", "resident", "step_latency_s",
                "util", "bound")


def _validate_tile_plans(tile_plans) -> None:
    """Structural validation of ``ServingPlan.tile_plans`` — these dicts
    parameterize real Pallas BlockSpecs, so a malformed entry must fail at
    plan time, not as a Mosaic error mid-serving."""
    from repro.kernels.dispatch import VALID_IMPLS

    for kind, entry in (tile_plans or {}).items():
        if kind not in TILE_PLAN_KINDS:
            raise ValueError(
                f"plan.tile_plans[{kind!r}]: unknown kernel kind "
                f"(known: {sorted(TILE_PLAN_KINDS)})")
        if not isinstance(entry, Mapping):
            raise ValueError(
                f"plan.tile_plans[{kind!r}] must be a dict, got "
                f"{type(entry).__name__}")
        for field, value in entry.items():
            if field in _TILE_FIELDS:
                if isinstance(value, bool) or not isinstance(value, int) \
                        or value < 1:
                    raise ValueError(
                        f"plan.tile_plans[{kind!r}][{field!r}] must be a "
                        f"positive int tile size, got {value!r}")
            elif field == "persistent":
                if not isinstance(value, bool):
                    raise ValueError(
                        f"plan.tile_plans[{kind!r}]['persistent'] must be "
                        f"a bool, got {value!r}")
            elif field == "impl":
                if value not in VALID_IMPLS:
                    raise ValueError(
                        f"plan.tile_plans[{kind!r}]['impl'] must be one of "
                        f"{VALID_IMPLS}, got {value!r}")
            elif field not in _META_FIELDS:
                raise ValueError(
                    f"plan.tile_plans[{kind!r}][{field!r}]: unknown field "
                    f"(tiles: {_TILE_FIELDS}; metadata: {_META_FIELDS}; "
                    f"plus 'persistent'/'impl')")
        if entry.get("persistent"):
            # persistent pins the whole weight set in VMEM for the entire
            # token loop — only admissible with recorded DSE residency
            # evidence, and never past the VMEM budget
            if not entry.get("resident"):
                raise ValueError(
                    f"plan.tile_plans[{kind!r}]: persistent=true requires "
                    f"resident=true (DSE evidence the weights fit in VMEM)")
            vmem = entry.get("vmem_bytes")
            if vmem is not None:
                from repro import hw
                budget = hw.vmem_budget()
                if int(vmem) > budget:
                    raise ValueError(
                        f"plan.tile_plans[{kind!r}]: persistent=true but "
                        f"vmem_bytes={vmem} exceeds the VMEM budget "
                        f"{budget}")


def tiles_summary(tile_plans) -> str:
    """Compact hot-path banner fragment: ``rwkv[bh512] attn[bq256,bk1024]``."""
    bits = []
    for kind in sorted(tile_plans or {}):
        entry = tile_plans[kind]
        tiles = [f"{f}{entry[f]}" for f in _TILE_FIELDS if entry.get(f)]
        if entry.get("persistent"):
            tiles.append("persist")
        if entry.get("impl"):
            tiles.append(str(entry["impl"]))
        bits.append(f"{kind}[{','.join(tiles)}]" if tiles else kind)
    return " ".join(bits)


__all__ = ["ServingPlan", "FleetPlan", "WorkloadProfile", "MIN_BUCKET",
           "TILE_PLAN_KINDS", "default_buckets", "parse_cache_layout",
           "tiles_summary"]
