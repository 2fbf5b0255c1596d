"""Serving-level design-space search: `autotune(arch, workload, hw_spec)`.

The serving analogue of :func:`repro.core.dse.best_plan`.  The kernel DSE
searches tile geometry per problem size against an analytic latency
model; this planner searches the *serving* design space

    bucket set x sync_every x max_batch x (policy, preempt)

per (architecture, workload profile) against two complementary oracles:

* the **roofline cost model** (`repro.hw.HardwareSpec`) scores the
  dimensions the deterministic virtual clock cannot see — host-sync
  amortization (``sync_every``), prefill padding waste and compile count
  (bucket set), and HBM feasibility of the slot count (weights + cache
  must fit, estimated from the *full-size* config's param/cache specs
  even when the probe runs reduced);
* a short seeded **virtual-clock probe run** scores the dimensions the
  cost model cannot see — queueing: for each feasible (max_batch,
  policy, preempt) candidate the workload is replayed through a real
  engine and ranked by (SLO attainment, p95 TTFT, p95 queue-wait,
  tokens/tick).

Everything is deterministic for a fixed (hw_spec, seed): the probe uses
the virtual clock and seeded workloads, candidate enumeration order is
fixed, and ties break toward the earlier candidate — so `autotune` is a
pure function, and the winning plan's ``provenance`` records the search.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import hw
from repro.plan.plan import (
    MIN_BUCKET,
    ServingPlan,
    WorkloadProfile,
    default_buckets,
    parse_cache_layout,
)

log = logging.getLogger("repro.plan")

# cost-model constants: one blocking host<->device round-trip, and one XLA
# prefill compile (amortized over the workload's admissions).  Order of
# magnitude only — they steer *relative* choices, never absolute claims.
HOST_SYNC_S = 50e-6
COMPILE_S = 2.0
HBM_FRACTION = 0.9        # usable HBM after runtime/fragmentation slack
SYNC_GAIN_MIN = 0.01      # keep growing the chunk while gain >= 1%
# paged-layout gather/launch overhead, expressed as extra tokens' worth of
# bytes per allocated page: smaller blocks fragment less but cost more
# table indirection, so the layout search has a real block-size trade-off
# instead of degenerating to "smallest block always wins"
PAGE_OVERHEAD_TOKENS = 2.0

# ---------------------------------------------------------------------------
# Memory + per-tick cost model (full-size config: the deployment target)
# ---------------------------------------------------------------------------


def _spec_bytes(specs) -> int:
    import jax

    from repro.models.params import is_spec

    return int(sum(
        s.size * np.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(specs, is_leaf=is_spec) if is_spec(s)))


@functools.lru_cache(maxsize=None)
def _full_model(arch: str):
    """The full-size (deployment-target) model wrapper — cached: the cost
    model consults it once per max_batch candidate plus per bucket-set
    candidate within a single autotune call."""
    from repro.configs import get_config
    from repro.models.lm import build_model

    return build_model(get_config(arch))


@functools.lru_cache(maxsize=None)
def serving_memory_bytes(arch: str, max_batch: int,
                         max_len: int) -> Tuple[int, int]:
    """(weight_bytes, cache_bytes) of the *full-size* config at the given
    slot count — from the parameter/cache spec trees, no allocation."""
    model = _full_model(arch)
    weights = _spec_bytes(model.param_specs())
    cache = _spec_bytes(model.cache_specs(max_batch, max_len))
    return weights, cache


@functools.lru_cache(maxsize=None)
def _full_param_count(arch: str) -> int:
    import jax

    from repro.models.params import is_spec

    specs = _full_model(arch).param_specs()
    return int(sum(s.size for s in jax.tree.leaves(specs, is_leaf=is_spec)
                   if is_spec(s)))


def modeled_tick_seconds(arch: str, max_batch: int,
                         spec: hw.HardwareSpec) -> float:
    """Roofline cost of one batched decode tick on the target chip: a
    decode step touches every weight once (the paper's compute:memory
    argument — small-batch decode is weight-streaming-bound) and does
    ~2 FLOPs per (param, slot)."""
    n_params = _full_param_count(arch)
    weight_bytes = 2 * n_params  # bf16 deployment weights
    t_compute = spec.matmul_time(2.0 * n_params * max_batch)
    t_stream = spec.hbm_time(weight_bytes)
    return max(t_compute, t_stream)


def pick_sync_every(arch: str, max_batch: int, spec: hw.HardwareSpec,
                    candidates: Sequence[int], preempt: bool) -> int:
    """Largest chunk whose modeled throughput still gains >= 1% over the
    previous candidate.  Preemptive plans pin ``sync_every=1``: eviction
    happens at host syncs, so a victim would wait out the whole chunk
    (in-chunk preemption is a ROADMAP item, not a current mechanism)."""
    if preempt:
        return 1
    t_tick = modeled_tick_seconds(arch, max_batch, spec)
    cands = sorted(set(int(c) for c in candidates))
    best = cands[0]
    best_thr = 1.0 / (t_tick + HOST_SYNC_S / best)
    for c in cands[1:]:
        thr = 1.0 / (t_tick + HOST_SYNC_S / c)
        if thr < best_thr * (1.0 + SYNC_GAIN_MIN):
            break
        best, best_thr = c, thr
    return best


def _pad_bucket(n: int, limit: int) -> int:
    return min(limit, -(-n // MIN_BUCKET) * MIN_BUCKET)


def candidate_bucket_sets(prompt_lengths: Sequence[int], max_len: int
                          ) -> List[Optional[Tuple[int, ...]]]:
    """Bucket-set candidates: the historical pow2 default plus a quantile
    set fitted to the workload's observed prompt lengths (p50/p90/max,
    padded to MIN_BUCKET granularity, always ending at max_len-1)."""
    limit = max_len - 1
    out: List[Optional[Tuple[int, ...]]] = [None]
    if prompt_lengths:
        ls = sorted(prompt_lengths)
        qs = {ls[min(len(ls) - 1, math.ceil(q * len(ls)) - 1)]
              for q in (0.5, 0.9, 1.0)}
        fitted = tuple(sorted({_pad_bucket(q, limit) for q in qs} | {limit}))
        if fitted != default_buckets(max_len):
            out.append(fitted)
    return out


def bucket_set_cost(buckets: Optional[Tuple[int, ...]],
                    prompt_lengths: Sequence[int], max_len: int,
                    arch: str, spec: hw.HardwareSpec) -> float:
    """Modeled prefill seconds per admitted request: padded-token compute
    plus the XLA compile bill amortized over the workload's admissions."""
    bs = buckets if buckets is not None else default_buckets(max_len)
    limit = max_len - 1

    def pad(n: int) -> int:
        for b in bs:
            if b >= n:
                return b
        return bs[-1]

    n_params = _full_param_count(arch)
    t_tok = spec.matmul_time(2.0 * n_params)
    mean_padded = (sum(pad(min(n, limit)) for n in prompt_lengths)
                   / max(1, len(prompt_lengths)))
    return mean_padded * t_tok + COMPILE_S * len(bs) / max(
        1, len(prompt_lengths))


# ---------------------------------------------------------------------------
# Cache layout (dense vs. paged block pool)
# ---------------------------------------------------------------------------


def expected_tokens_per_slot(items, max_len: int) -> float:
    """Conservative resident-token estimate per occupied slot: the p95 of
    each request's full footprint (prompt + decode budget, capped at the
    cache length).  p95 rather than the mean because a paged pool is
    provisioned for the tokens actually in flight — undershooting the
    tail is what fragmentation-free layouts must *not* do."""
    if not items:
        return float(max_len)
    toks = sorted(min(max_len, len(it.prompt) + it.max_new_tokens)
                  for it in items)
    return float(toks[min(len(toks) - 1, math.ceil(0.95 * len(toks)) - 1)])


@functools.lru_cache(maxsize=None)
def cache_layout_bytes(arch: str, max_batch: int, max_len: int,
                       layout: str, tokens_per_slot: float) -> int:
    """Modeled resident cache bytes of the *full-size* config under a
    cache layout at the expected per-slot token load.  Dense commits the
    whole ``max_batch x max_len`` cache; paged commits per-slot state
    plus expected tokens rounded up to block granularity (see
    :func:`repro.serving.paged.paged_cache_bytes`) plus a per-page
    overhead charge (:data:`PAGE_OVERHEAD_TOKENS`) standing in for the
    block-table gather cost."""
    block = parse_cache_layout(layout)
    if block is None:
        return serving_memory_bytes(arch, max_batch, max_len)[1]
    from repro.serving.paged import paged_cache_bytes

    model = _full_model(arch)
    base = paged_cache_bytes(model, max_batch, max_len, block,
                             tokens_per_slot)
    n_pages = math.ceil(min(max_len, tokens_per_slot) / block)
    # ring bytes per covered token (per-slot recurrent state excluded:
    # paging it costs nothing, so a pool-less arch carries no overhead
    # and ties with dense)
    floor = paged_cache_bytes(model, max_batch, max_len, block, 0.0)
    one_page = paged_cache_bytes(model, max_batch, max_len, block,
                                 float(block))
    per_tok = (one_page - floor) // max(1, max_batch * block)
    overhead = int(PAGE_OVERHEAD_TOKENS * per_tok * n_pages * max_batch)
    return base + overhead


def candidate_cache_layouts(max_len: int,
                            block_sizes: Sequence[int]) -> List[str]:
    """Layout candidates: dense first (the tie-break winner), then one
    paged candidate per admissible block size."""
    return ["dense"] + [f"paged:{b}" for b in sorted(set(int(b)
                        for b in block_sizes)) if 1 <= b <= max_len]


# ---------------------------------------------------------------------------
# Per-kernel tile plans
# ---------------------------------------------------------------------------


def tile_plans_for(arch: str, max_batch: int, spec: hw.HardwareSpec,
                   max_len: int = 2048) -> Dict[str, Dict[str, object]]:
    """Embed a ``core.dse`` tile plan per layer kind, scored at the
    serving batch (the kernel-level half of the design point).

    ``rwkv`` gets the rwkv6_step head-tile search (the (B, bh, K, V)
    state block is what fills VMEM); ``swa_ssm`` runs the paper's RNN-cell
    tile search (3-gate cell at the model width), and a plan whose chosen
    tile keeps the weights VMEM-resident in a single tile is additionally
    marked ``persistent``.  Attention kinds (attn/local) get a bq/bk flash
    tile plan scored at the config's max sequence.  Every dict is the
    compact ``dse.plan_dict`` form so unset tile fields never reach the
    plan (keeps committed plans/BENCH rows byte-stable)."""
    from repro.core import dse
    from repro.core.cells import RNNCellConfig

    cfg = _full_model(arch).cfg
    out: Dict[str, Dict[str, object]] = {}
    for kind in sorted(set(cfg.layer_pattern)):
        if kind == "rwkv":
            hd = cfg.rwkv.head_dim
            out[kind] = dse.plan_dict(dse.best_rwkv_plan(
                cfg.d_model // hd, hd, max_batch, spec))
        elif kind == "swa_ssm":
            cell = RNNCellConfig("gru", hidden=cfg.d_model,
                                 features=cfg.d_model,
                                 batch=1, precision="bf16")
            best = dse.best_plan(cell, spec, max_batch=max_batch)
            entry = dse.plan_dict(best)
            if best.resident and best.n_tiles == 1:
                entry["persistent"] = True
            out[kind] = entry
        elif kind in ("attn", "local"):
            seq = max(int(max_len), dse.SUBLANE)
            window = cfg.local_window if kind == "local" else 0
            seq_kv = min(seq, window) if window else seq
            best = dse.best_attn_plan(seq, seq_kv, cfg.head_dim_, spec,
                                      n_heads=cfg.n_heads, batch=max_batch)
            out[kind] = dse.plan_dict(best)
    return out


# ---------------------------------------------------------------------------
# The probe + search
# ---------------------------------------------------------------------------


def _probe_metrics(plan: ServingPlan, model, params, sharder,
                   items, seed: int) -> Dict[str, object]:
    from repro.serving import metrics as smetrics
    from repro.serving.engine import ServingEngine
    from repro.serving.workload import drive

    engine = ServingEngine.from_plan(plan, params, model=model,
                                     sharder=sharder, seed=seed)
    reqs = drive(engine, items)
    return smetrics.aggregate(reqs, ticks=engine.ticks,
                              util_history=engine.util_history)


def _score(agg: Dict[str, object]) -> Tuple[float, float, float, float]:
    """Rank key, larger is better: (SLO attainment, -p95 TTFT,
    -p95 queue-wait, tokens/tick).  NaN percentiles (nothing completed)
    rank worst."""

    def neg(x: float) -> float:
        return -1e18 if (x is None or math.isnan(x)) else -float(x)

    slo = agg.get("slo", {}).get("attainment", 0.0)
    return (float(slo), neg(agg["ttft"]["p95"]),
            neg(agg["queue_wait"]["p95"]), float(agg["tokens_per_sec"]))


def autotune(arch: str, workload: WorkloadProfile,
             hw_spec: hw.HardwareSpec = hw.DEFAULT, *,
             seed: int = 0, reduced: bool = True, max_len: int = 64,
             max_batches: Sequence[int] = (2, 4, 8),
             sync_everys: Sequence[int] = (1, 2, 4, 8),
             block_sizes: Sequence[int] = (8, 16, 32),
             probe_duration: float = 32.0) -> ServingPlan:
    """Search the serving design space for one (arch, workload) cell.

    Returns the winning validated :class:`ServingPlan` with the search
    recorded under ``provenance["autotune"]``.  Deterministic for a fixed
    (hw_spec, seed): same inputs, same plan.

    The cache layout (dense vs. ``paged:<block_size>``) is chosen *after*
    the scheduling probe: virtual-clock schedules are layout-invariant by
    construction (the paged manager is bit-exact behind the SlotManager
    seam), so the probe plane does not grow — only the HBM feasibility
    check and the final bytes-resident comparison see the layouts.  A
    slot count is feasible when *any* candidate layout fits, which is how
    paging raises admission capacity under heavy-tail workloads: the
    expected tokens in flight, not ``max_batch x max_len``, is what has
    to fit."""
    import jax

    from repro.configs import get_config
    from repro.dist.sharding import make_sharder
    from repro.models.lm import build_model
    from repro.serving.workload import profile_items
    from repro.testing import reduced_config

    cfg = reduced_config(arch) if reduced else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sharder = make_sharder(cfg, None, "decode")

    span = workload.duration if workload.duration is not None \
        else probe_duration
    # probe on a capped span: replace the profile's own duration, because
    # profile_items prefers it over the duration argument
    probe_span = min(span, probe_duration)
    probe_wl = dataclasses.replace(workload, duration=probe_span)
    items = profile_items(probe_wl, vocab_size=cfg.vocab_size, seed=seed)
    deadlines = any(it.deadline is not None for it in items)

    # --- candidate slot counts: HBM feasibility on the full-size config.
    # A slot count qualifies when its cheapest candidate layout fits, so
    # paged layouts can admit batch sizes the dense cache could not.
    budget = hw_spec.hbm_bytes * HBM_FRACTION
    layouts = candidate_cache_layouts(max_len, block_sizes)
    t_slot = expected_tokens_per_slot(items, max_len)
    feasible, overcommitted = [], False
    for mb in sorted(set(int(b) for b in max_batches)):
        weights, _ = serving_memory_bytes(arch, mb, max_len)
        cheapest = min(cache_layout_bytes(arch, mb, max_len, lay, t_slot)
                       for lay in layouts)
        if weights + cheapest <= budget:
            feasible.append(mb)
    if not feasible:   # weights alone exceed one chip: rank anyway, flag it
        overcommitted = True
        feasible = sorted(set(int(b) for b in max_batches))

    policies = ([("fcfs", False), ("edf", False), ("edf", True)]
                if deadlines else [("fcfs", False), ("spf", False)])

    # --- probe: queueing behavior per (max_batch, policy) on the virtual
    # clock (sync_every / buckets do not move virtual-clock schedules, so
    # one probe per scheduling candidate covers the whole plane)
    best_key, best, probed = None, None, []
    for mb in feasible:
        for policy, preempt in policies:
            cand = ServingPlan(arch=arch, reduced=reduced, max_len=max_len,
                               max_batch=mb, policy=policy, preempt=preempt)
            agg = _probe_metrics(cand, model, params, sharder, items, seed)
            key = _score(agg)
            probed.append({"max_batch": mb, "policy": policy,
                           "preempt": preempt, "score": list(key)})
            log.debug("probe b%d %s%s -> %s", mb, policy,
                      "+p" if preempt else "", key)
            if best_key is None or key > best_key:
                best_key, best = key, cand

    # --- cost-model dimensions the virtual clock cannot see
    sync = pick_sync_every(arch, best.max_batch, hw_spec, sync_everys,
                           best.preempt)
    lengths = [len(it.prompt) for it in items]
    bsets = candidate_bucket_sets(lengths, max_len)
    costs = [bucket_set_cost(bs, lengths, max_len, arch, hw_spec)
             for bs in bsets]
    buckets = bsets[int(np.argmin(costs))]

    # --- cache layout: schedules are layout-invariant, so pick by modeled
    # resident bytes at the winning slot count; dense is enumerated first
    # and wins ties, so paging has to actually save memory to be chosen
    layout_bytes = [(lay, cache_layout_bytes(arch, best.max_batch, max_len,
                                             lay, t_slot))
                    for lay in layouts]
    cache_layout = min(layout_bytes, key=lambda kv: kv[1])[0]

    plan = dataclasses.replace(
        best, sync_every=sync, buckets=buckets, cache_layout=cache_layout,
        tile_plans=tile_plans_for(arch, best.max_batch, hw_spec,
                                  max_len=max_len),
        provenance={"autotune": {
            "hw": hw_spec.name, "seed": seed,
            "probe_duration": probe_span,
            "workload": workload.to_json(),
            "memory_overcommitted": overcommitted,
            "probes": probed,
            "best_score": list(best_key),
            "expected_tokens_per_slot": t_slot,
            "cache_layouts": [
                {"layout": lay, "modeled_bytes": b}
                for lay, b in layout_bytes],
            "bucket_costs": [
                {"buckets": None if b is None else list(b), "cost_s": c}
                for b, c in zip(bsets, costs)],
        }})
    return plan.validate()


# ---------------------------------------------------------------------------
# fleet-level search
# ---------------------------------------------------------------------------

BENCH_COLLECTIVES = "BENCH_collectives.json"
# the dry-run grid records these serve shapes; keys into the trajectory
_PREFILL_SHAPE = "prefill_32k"
_DECODE_SHAPE = "decode_32k"


def load_collectives(path: str = BENCH_COLLECTIVES
                     ) -> Dict[Tuple[str, str], Dict[str, object]]:
    """Read the committed collective-volume trajectory
    (``benchmarks/collectives.py`` → ``BENCH_collectives.json``):
    ``{(arch, shape): collectives-summary}`` with the summary carrying
    ``n_ops`` / ``operand_bytes`` / ``ici_bytes`` / ``by_kind`` exactly as
    ``repro.launch.hlo.collective_summary`` emits them.  Returns ``{}``
    when the file is absent — the planner then falls back to defaults
    and records that no evidence was consulted."""
    import json
    import os

    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    out: Dict[Tuple[str, str], Dict[str, object]] = {}
    for cell in doc.get("cells", []):
        out[(str(cell["arch"]), str(cell["shape"]))] = cell["collectives"]
    return out


def fleet_shard_modes(arch: str, n_replicas: int, n_prefill: int,
                      collectives: Dict[Tuple[str, str], Dict[str, object]]
                      ) -> Tuple[List[str], Dict[str, object]]:
    """Per-replica ``shard_mode`` choices scored against the recorded
    collective volumes.  Decode (and colocated) replicas keep the serving
    default ``"decode"``; a dedicated prefill replica switches to
    ``"prefill"`` sharding only when the trajectory actually recorded the
    arch's prefill-shape program (evidence the sharded compile exists and
    what it moves over ICI) — with no evidence the planner refuses to
    guess and leaves the default.  Returns the mode list plus a
    provenance record of exactly what was consulted."""
    dec = collectives.get((arch, _DECODE_SHAPE))
    pre = collectives.get((arch, _PREFILL_SHAPE))
    record: Dict[str, object] = {
        "source": BENCH_COLLECTIVES,
        "decode_ici_bytes": None if dec is None else dec.get("ici_bytes"),
        "prefill_ici_bytes": None if pre is None else pre.get("ici_bytes"),
        "consulted": dec is not None or pre is not None,
    }
    modes = []
    for i in range(n_replicas):
        if i < n_prefill and pre is not None:
            modes.append("prefill")
        else:
            modes.append("decode")
    record["modes"] = list(modes)
    return modes, record


def autotune_fleet(arch: str, workload: WorkloadProfile,
                   hw_spec: hw.HardwareSpec = hw.DEFAULT, *,
                   seed: int = 0, reduced: bool = True, max_len: int = 64,
                   replica_counts: Sequence[int] = (1, 2, 4),
                   routings: Sequence[str] = ("round_robin", "least_queue"),
                   prefill_splits: Sequence[int] = (0, 1),
                   base_plan: Optional[ServingPlan] = None,
                   probe_duration: float = 32.0,
                   collectives: Optional[Dict] = None,
                   collectives_path: str = BENCH_COLLECTIVES) -> "FleetPlan":
    """Coarse fleet-level design-space search: replica count × routing
    policy × prefill:decode split, each candidate ranked by a seeded
    fleet probe (``drive_fleet`` on the capped workload, scored by the
    same (SLO, p95 TTFT, p95 queue-wait, tokens/tick) key as the
    per-engine :func:`autotune`, ties toward the smaller fleet).  The
    replica design point itself is not re-searched here — pass
    ``base_plan`` (e.g. an :func:`autotune` winner) to fleet-ify a tuned
    replica; the default replica is the plan's defaults at this arch.

    Per-replica ``shard_mode`` is then scored against the committed
    collective-volume trajectory (:func:`load_collectives` — the
    ``BENCH_collectives.json`` file the tier2 dry-run grid maintains):
    dedicated prefill replicas get ``"prefill"`` sharding when the
    trajectory holds evidence for this arch, everything else keeps
    ``"decode"``.  What was consulted is recorded under
    ``provenance["autotune_fleet"]["collectives"]``.

    Deterministic for fixed (hw_spec, seed): seeded probes on the virtual
    clock, fixed enumeration order, ties to the earlier candidate."""
    from repro.plan.plan import FleetPlan
    from repro.serving.router import Router, drive_fleet
    from repro.serving.workload import profile_items
    from repro.testing import reduced_config

    from repro.configs import get_config

    base = base_plan if base_plan is not None else ServingPlan(
        arch=arch, reduced=reduced, max_len=max_len)
    base.validate()

    cfg = reduced_config(arch) if reduced else get_config(arch)
    span = workload.duration if workload.duration is not None \
        else probe_duration
    probe_wl = dataclasses.replace(workload,
                                   duration=min(span, probe_duration))
    items = profile_items(probe_wl, vocab_size=cfg.vocab_size, seed=seed)

    built: Dict = {}
    best_key, best_cand, probed = None, None, []
    for n in sorted(set(int(n) for n in replica_counts)):
        for routing in routings:
            for split in sorted(set(int(s) for s in prefill_splits)):
                if not 0 <= split < n:
                    continue
                cand = FleetPlan(replicas=(base,) * n, routing=routing,
                                 n_prefill=split, hw=hw_spec.name)
                router = Router.from_plan(cand, seed=seed, _built=built)
                drive_fleet(router, items)
                agg = router.fleet_aggregate()
                key = (_score(agg), -n)
                probed.append({"replicas": n, "routing": routing,
                               "n_prefill": split, "score": list(key[0]),
                               "completed": agg["completed"]})
                log.debug("fleet probe n%d %s split%d -> %s", n, routing,
                          split, key)
                if best_key is None or key > best_key:
                    best_key, best_cand = key, cand

    coll = collectives if collectives is not None \
        else load_collectives(collectives_path)
    modes, coll_record = fleet_shard_modes(
        arch, best_cand.n_replicas, best_cand.n_prefill, coll)
    replicas = tuple(
        dataclasses.replace(p, shard_mode=mode)
        for p, mode in zip(best_cand.replicas, modes))
    fleet = dataclasses.replace(
        best_cand, replicas=replicas,
        provenance={"autotune_fleet": {
            "hw": hw_spec.name, "seed": seed,
            "probe_duration": probe_wl.duration,
            "workload": workload.to_json(),
            "probes": probed,
            "best_score": list(best_key[0]),
            "collectives": coll_record,
        }})
    return fleet.validate()


def autotune_from_trace(arch: str, trace,
                        hw_spec: hw.HardwareSpec = hw.DEFAULT, *,
                        duration: Optional[float] = None,
                        **kwargs) -> ServingPlan:
    """Re-autotune from *observed* traffic: fit a
    :class:`WorkloadProfile` from a recorded :class:`repro.obs.Tracer`
    trace (live object, Chrome-trace document, or file path) and search
    the design space against it.  This is the drift-recovery loop — when
    traffic no longer matches the profile a deployed plan was tuned on,
    replan from what actually arrived instead of the stale declaration.

    Accepts every :func:`autotune` keyword; the fit's inputs and result
    are recorded under ``provenance["observed_traffic"]`` alongside the
    usual ``provenance["autotune"]`` search record.
    """
    from repro.obs.observe import fit_profile, summarize

    profile = fit_profile(trace, duration=duration)
    plan = autotune(arch, profile, hw_spec, **kwargs)
    prov = dict(plan.provenance)
    prov["observed_traffic"] = {
        "fitted_profile": profile.to_json(),
        "trace_summary": summarize(trace),
    }
    return dataclasses.replace(plan, provenance=prov)


__all__ = ["autotune", "autotune_fleet", "autotune_from_trace",
           "load_collectives", "fleet_shard_modes", "BENCH_COLLECTIVES",
           "serving_memory_bytes",
           "modeled_tick_seconds", "pick_sync_every",
           "candidate_bucket_sets", "bucket_set_cost",
           "cache_layout_bytes", "candidate_cache_layouts",
           "expected_tokens_per_slot",
           "tile_plans_for", "HOST_SYNC_S", "COMPILE_S",
           "PAGE_OVERHEAD_TOKENS"]
