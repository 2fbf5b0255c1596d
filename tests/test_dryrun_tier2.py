"""tier2: 512-fake-device dry-run smoke over the full (arch x shape) grid.

Every applicable cell of the assignment grid must lower + compile against
the 2x16x16 multi-pod production mesh (512 fake host devices) — the
full-scale analogue of the 8-device smoke in tests/test_sharding.py, and
the ROADMAP's "dry-run at 512 fake devices across the whole grid in CI"
item.  Each cell runs in its own subprocess because jax locks the device
count at first initialization (see repro.launch.dryrun).

Deselected by default (pytest.ini: ``-m "not tier2"``); the scheduled /
manually-dispatched job in .github/workflows/tier2.yml runs it with
``-m tier2``.  One cell can take minutes: full-size models, CPU XLA.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.configs import grid

GRID = [(cfg.name, shape.name) for cfg, shape, runs, _ in grid() if runs]

CELL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
from repro.launch.dryrun import run_cell

cell = run_cell(sys.argv[1], sys.argv[2], multi_pod=True, pieces=False)
cell.pop("traceback", None)
print("CELL_JSON=" + json.dumps(
    {k: cell.get(k) for k in ("ok", "skip", "error", "chips", "wall_s")}))
"""


# ---------------------------------------------------------------------------
# tier2 paged-layout grid: dense ≡ paged across every serving-capable arch
# the tier-1 paged tests do NOT already cover, times block sizes.  tier-1
# pins rwkv6/qwen2.5/hymba (tests/test_paged_slotstate.py); this grid
# sweeps the rest — sliding-window rings (gemma2/gemma3 pool by *two* ring
# lengths), MoE routing, and starcoder2's GQA — under a longer open-loop
# workload, asserting bit-identical schedules + clean pool invariants.
# ---------------------------------------------------------------------------

PAGED_TIER2_GRID = [
    (arch, block)
    for arch in ("gemma2-9b", "gemma3-12b", "starcoder2-15b",
                 "granite-moe-1b-a400m", "qwen3-moe-30b-a3b")
    for block in (4, 16)
]


@pytest.mark.tier2
@pytest.mark.parametrize("arch,block", PAGED_TIER2_GRID,
                         ids=[f"{a}-b{b}" for a, b in PAGED_TIER2_GRID])
def test_paged_dense_equivalence_grid(arch, block):
    import jax

    from repro.dist.sharding import Sharder
    from repro.models.lm import build_model
    from repro.plan import ServingPlan
    from repro.serving import ServingEngine, VirtualClock, drive, \
        make_workload
    from repro.testing import reduced_config

    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sharder = Sharder(None, {})
    items = make_workload("poisson", rate=0.6, duration=24.0, seed=4,
                          vocab_size=cfg.vocab_size, prompt_len=(2, 12),
                          max_new_tokens=(2, 8))

    def serve(layout):
        plan = ServingPlan(arch=arch, max_batch=3, max_len=32,
                           cache_layout=layout)
        eng = ServingEngine.from_plan(plan, params, model=model,
                                      sharder=sharder, seed=13)
        reqs = drive(eng, [i for i in items], VirtualClock())
        return eng, [(r.uid, r.output, r.t_admit, r.t_first, r.t_done)
                     for r in reqs]

    eng_d, sched_d = serve("dense")
    eng_p, sched_p = serve(f"paged:{block}")
    assert sched_d == sched_p
    assert eng_d.stats() == eng_p.stats()
    eng_p.sm.check_invariants()


@pytest.mark.tier2
@pytest.mark.parametrize("arch,shape", GRID,
                         ids=[f"{a}-{s}" for a, s in GRID])
def test_dryrun_grid_cell_512_devices(arch, shape):
    r = subprocess.run(
        [sys.executable, "-c", CELL, arch, shape],
        capture_output=True, text=True, timeout=3600,
        env={**os.environ, "PYTHONPATH": "src",
             "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(l for l in r.stdout.splitlines() if l.startswith("CELL_JSON="))
    cell = json.loads(line[len("CELL_JSON="):])
    assert cell["ok"] is True, cell
    assert cell["chips"] == 512


# ---------------------------------------------------------------------------
# tier2 chaos grid (PR 8): a seeded fault storm against every tier-1-
# pinned serving arch, dense and paged — each run must end with zero
# lost requests (every uid completes or is accountably shed) and clean
# pool invariants.  MoE archs are excluded on purpose: expert routing
# shares capacity across the batch, so a poisoned lane can contaminate
# co-tenants (see benchmarks/README.md, "Fault model & recovery").
# ---------------------------------------------------------------------------

CHAOS_GRID = [
    (arch, layout)
    for arch in ("rwkv6-1.6b", "qwen2.5-14b", "hymba-1.5b")
    for layout in ("dense", "paged:8")
]


@pytest.mark.tier2
@pytest.mark.parametrize("arch,layout", CHAOS_GRID,
                         ids=[f"{a}-{l}" for a, l in CHAOS_GRID])
def test_chaos_grid_zero_lost_requests(arch, layout, tmp_path):
    import jax

    from repro.checkpoint import CheckpointManager
    from repro.dist.sharding import Sharder
    from repro.models.lm import build_model
    from repro.plan.plan import ServingPlan
    from repro.serving import (FaultInjector, ServingEngine, VirtualClock,
                               drive_resilient, make_workload)
    from repro.serving.faults import make_storm
    from repro.testing import reduced_config

    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = ServingPlan(arch=arch, reduced=True, max_batch=3, max_len=32,
                       cache_layout=layout, retry_budget=3,
                       watchdog_ticks=4,
                       provenance={"source": "tier2-chaos"}).resolve()
    items = make_workload("poisson", rate=0.6, duration=24.0, seed=4,
                          vocab_size=cfg.vocab_size, prompt_len=(2, 12),
                          max_new_tokens=(2, 8))
    storm = make_storm(duration=30, seed=17, n_faults=6, max_batch=3)
    eng = ServingEngine.from_plan(plan, params, model=model,
                                  sharder=Sharder(None, {}))
    rep = drive_resilient(eng, items, VirtualClock(),
                          injector=FaultInjector(storm),
                          manager=CheckpointManager(str(tmp_path)),
                          checkpoint_every=4)
    assert rep.lost_uids() == [], \
        f"{arch}/{layout}: lost requests {rep.lost_uids()}"
    assert len(rep.requests) == len(items)
    assert rep.engine.fault_stats()["injected"] >= 1
    if layout != "dense":
        rep.engine.sm.check_invariants()


# ---------------------------------------------------------------------------
# tier2 fleet grid: the multi-replica router over every tier-1-pinned
# serving arch x {2, 4} replicas x {colocated, disaggregated}.  Each cell
# drives a fleet of reduced in-process engines on one virtual clock and
# asserts the router's conservation invariant (every arrival finishes or
# is accountably shed; transits all deliver), plus run-to-run determinism
# of the pooled fleet metrics.  tier-1 pins the same properties for
# rwkv6 only (tests/test_router.py); this grid sweeps the archs whose
# slot state is NOT an O(1) column — dense-attention KV and the hybrid
# SSM — so prefill->decode snapshot transit is exercised across every
# cache pytree family.
# ---------------------------------------------------------------------------

FLEET_TIER2_GRID = [
    (arch, n, n_prefill)
    for arch in ("rwkv6-1.6b", "qwen2.5-14b", "hymba-1.5b")
    for n in (2, 4)
    for n_prefill in (0, 1)
]

_FLEET_BUILT = {}   # arch -> (cfg, model, params); shared across cells


def _fleet_built(arch):
    if arch not in _FLEET_BUILT:
        import jax

        from repro.models.lm import build_model
        from repro.testing import reduced_config

        cfg = reduced_config(arch)
        model = build_model(cfg)
        _FLEET_BUILT[arch] = (cfg, model,
                              model.init(jax.random.PRNGKey(0)))
    return _FLEET_BUILT[arch]


@pytest.mark.tier2
@pytest.mark.parametrize(
    "arch,n,n_prefill", FLEET_TIER2_GRID,
    ids=[f"{a}-x{n}-{'disagg' if k else 'colo'}"
         for a, n, k in FLEET_TIER2_GRID])
def test_fleet_grid_conservation(arch, n, n_prefill):
    from repro.plan.plan import FleetPlan, ServingPlan, WorkloadProfile
    from repro.serving import profile_items
    from repro.serving.router import Router, drive_fleet

    cfg, model, params = _fleet_built(arch)
    plan = ServingPlan(arch=arch, max_batch=2, max_len=32)
    fleet = FleetPlan.replicated(plan, n, routing="least_queue",
                                 n_prefill=n_prefill).validate()
    built = {(arch, True): (model, params)}
    items = profile_items(
        WorkloadProfile(kind="poisson", rate=1.2, duration=16.0),
        vocab_size=cfg.vocab_size, seed=7)

    router = Router.from_plan(fleet, seed=0, _built=built)
    reqs = drive_fleet(router, items)

    census = router.conservation_census()
    assert census["total"] == len(items), census
    assert census["finished"] + census["shed"] == len(items), census
    for r in reqs:
        assert r.shed or r.done, f"{arch}: request {r.uid} lost"
    ts = router.transit_stats()
    assert ts["delivered"] == ts["handoffs"] and ts["in_flight"] == 0, ts
    if n_prefill:
        assert ts["handoffs"] > 0, "disaggregated cell never handed off"
    agg = router.fleet_aggregate()
    assert agg["submitted"] == len(items)

    router2 = Router.from_plan(fleet, seed=0, _built=built)
    drive_fleet(router2, items)
    assert json.dumps(router2.fleet_aggregate(), sort_keys=True) == \
        json.dumps(agg, sort_keys=True), f"{arch}: fleet run not " \
        f"deterministic"
