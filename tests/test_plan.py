"""repro.plan: ServingPlan round-trips, the autotuner's determinism,
deadline-aware shedding, batched eviction, and the batch-aware kernel
tile search."""

import json

import jax
import numpy as np
import pytest

from repro import hw
from repro.core import dse
from repro.core.cells import RNNCellConfig
from repro.dist.sharding import make_sharder
from repro.models.lm import build_model
from repro.plan import (
    ServingPlan,
    WorkloadProfile,
    default_buckets,
    from_dict,
    load_plan,
    save_plan,
    to_dict,
)
from repro.plan import io as plan_io
from repro.serving import ServingEngine, drive, profile_items
from repro.testing import reduced_config

ARCH = "rwkv6-1.6b"


@pytest.fixture(scope="module")
def built():
    cfg = reduced_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sharder = make_sharder(cfg, None, "decode")
    return cfg, model, params, sharder


def _engine(built, seed=0, **knobs):
    cfg, model, params, sharder = built
    return ServingEngine.from_plan(ServingPlan(arch=ARCH, **knobs), params,
                                   model=model, sharder=sharder, seed=seed)


# ---------------------------------------------------------------------------
# Round trips
# ---------------------------------------------------------------------------


def test_plan_json_round_trip_identity(tmp_path):
    plan = ServingPlan(
        arch=ARCH, max_batch=8, max_len=64, buckets=(8, 16, 63),
        sync_every=4, policy="edf", preempt=True, shed_late=True,
        temperature=0.7, top_k=40,
        tile_plans={"rwkv": {"bh": 128, "resident": True}},
        provenance={"source": "test", "cli_overrides": {"policy": "edf"}})
    plan.validate()
    rt = from_dict(json.loads(json.dumps(to_dict(plan))))
    assert rt == plan
    # and through a file
    path = str(tmp_path / "plan.json")
    save_plan(plan, path)
    assert load_plan(path) == plan


def test_plan_default_resolves_to_historical_buckets():
    plan = ServingPlan(arch=ARCH, max_len=64)
    assert plan.resolved_buckets() == (8, 16, 32, 63)
    assert default_buckets(128) == (8, 16, 32, 64, 127)
    resolved = plan.resolve()
    assert resolved.buckets == (8, 16, 32, 63)
    assert from_dict(to_dict(resolved)) == resolved


def test_plan_validate_rejects_bad_values():
    good = ServingPlan(arch=ARCH, max_len=64)
    good.validate()
    bad = [
        dict(max_batch=0),
        dict(sync_every=0),
        dict(max_len=1),
        dict(policy="nope"),
        dict(policy="fcfs", preempt=True),       # non-preemptive policy
        dict(buckets=(16, 8, 63)),               # not increasing
        dict(buckets=(8, 16, 32)),               # does not end at max_len-1
        dict(temperature=-1.0),
        dict(cache_layout="sparse"),             # unknown layout
        dict(cache_layout="paged:0"),            # block must be >= 1
        dict(cache_layout="paged:65"),           # block exceeds max_len
    ]
    import dataclasses
    for kw in bad:
        with pytest.raises(ValueError):
            dataclasses.replace(good, **kw).validate()


def test_plan_schema_guard_passes():
    plan_io.check_schema()


def test_workload_profile_round_trip():
    wp = WorkloadProfile(kind="poisson", rate=0.8, duration=128.0,
                         max_new_tokens=(6, 10), heavy_decode=(0.03, 32, 48),
                         deadline_slack=3.0)
    assert WorkloadProfile.from_json(
        json.loads(json.dumps(wp.to_json()))) == wp


# ---------------------------------------------------------------------------
# Engine: the plan drives it
# ---------------------------------------------------------------------------


def test_explicit_bucket_set_drives_prefill_shapes(built):
    cfg, model, params, sharder = built
    plan = ServingPlan(arch=ARCH, max_len=64, max_batch=2,
                       buckets=(16, 63))
    eng = ServingEngine.from_plan(plan, params, model=model,
                                  sharder=sharder, seed=0)
    assert eng.bucket_lengths == [16, 63]
    assert eng.bucket(3) == 16 and eng.bucket(17) == 63
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()
    assert eng.prefill_shapes == {(2, 16)}


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_autotune_deterministic_and_valid():
    from repro.plan import planner

    wp = WorkloadProfile(rate=0.8, duration=10.0, max_new_tokens=(6, 10),
                         deadline_slack=2.0)
    kw = dict(seed=3, max_len=64, max_batches=(2, 4), sync_everys=(1, 2, 4),
              probe_duration=10.0)
    a = planner.autotune(ARCH, wp, hw.DEFAULT, **kw)
    b = planner.autotune(ARCH, wp, hw.DEFAULT, **kw)
    assert a == b
    a.validate()
    assert from_dict(json.loads(json.dumps(to_dict(a)))) == a
    assert a.provenance["autotune"]["hw"] == hw.DEFAULT.name
    assert len(a.provenance["autotune"]["probes"]) >= 4
    # the recurrent arch embeds a batch-aware kernel tile plan
    assert "rwkv" in a.tile_plans and a.tile_plans["rwkv"]["bh"] >= 8


def test_pick_sync_every_pins_preemptive_plans_to_one():
    from repro.plan import planner

    assert planner.pick_sync_every(ARCH, 4, hw.DEFAULT, (1, 2, 4, 8),
                                   preempt=True) == 1


def test_candidate_bucket_sets_fit_workload():
    from repro.plan import planner

    sets = planner.candidate_bucket_sets([4, 5, 6, 30], max_len=64)
    assert sets[0] is None                       # pow2 default always there
    for bs in sets[1:]:
        assert bs[-1] == 63 and list(bs) == sorted(set(bs))


# ---------------------------------------------------------------------------
# Cache-layout search (dense vs. paged)
# ---------------------------------------------------------------------------


def test_parse_cache_layout_grammar():
    from repro.plan.plan import parse_cache_layout

    assert parse_cache_layout("dense") is None
    assert parse_cache_layout("paged:16") == 16
    assert parse_cache_layout("paged:1") == 1
    for bad in ("sparse", "paged", "paged:", "paged:x", "paged:0",
                "paged:-4", "paged:016", "PAGED:16"):
        with pytest.raises(ValueError):
            parse_cache_layout(bad)


def test_candidate_cache_layouts_dense_first_deduped():
    from repro.plan import planner

    lays = planner.candidate_cache_layouts(64, (32, 8, 8, 100, 0))
    assert lays[0] == "dense"                    # tie-break winner
    assert lays[1:] == ["paged:8", "paged:32"]   # sorted, deduped, in-range


def test_cache_layout_bytes_paged_tracks_load():
    """For an attention arch, paged bytes are far below dense at light
    per-slot load and above dense at saturation (the per-page overhead
    charge) — so the layout search has a real trade-off, and dense wins
    once every ring would be fully allocated anyway."""
    from repro.plan import planner

    arch, mb, ml = "qwen2.5-14b", 4, 64
    dense = planner.cache_layout_bytes(arch, mb, ml, "dense", 8.0)
    light = planner.cache_layout_bytes(arch, mb, ml, "paged:8", 8.0)
    full = planner.cache_layout_bytes(arch, mb, ml, "paged:8", float(ml))
    assert light < dense < full
    # a pure-recurrent arch has nothing to page: both layouts cost the
    # per-slot state, so dense (enumerated first) wins the tie
    d = planner.cache_layout_bytes("rwkv6-1.6b", mb, ml, "dense", 8.0)
    p = planner.cache_layout_bytes("rwkv6-1.6b", mb, ml, "paged:8", 8.0)
    assert p == d


@pytest.mark.slow
def test_autotune_layout_choice_and_provenance():
    """The autotuner records the layout comparison in provenance and
    picks paged for an attention arch under a light-tailed workload
    (expected tokens far below max_len), dense for a pure-recurrent
    arch (nothing to page — tie goes to dense)."""
    from repro.plan import planner

    wp = WorkloadProfile(rate=0.3, duration=6.0, prompt_len=(2, 6),
                         max_new_tokens=(2, 4))
    kw = dict(seed=1, max_len=64, max_batches=(2,), sync_everys=(1,),
              probe_duration=6.0)
    qwen = planner.autotune("qwen2.5-14b", wp, hw.DEFAULT, **kw)
    assert qwen.cache_layout.startswith("paged:")
    prov = qwen.provenance["autotune"]
    assert prov["expected_tokens_per_slot"] <= 10.0
    recorded = {e["layout"]: e["modeled_bytes"] for e in
                prov["cache_layouts"]}
    assert qwen.cache_layout == min(recorded, key=recorded.get)
    assert recorded[qwen.cache_layout] < recorded["dense"]

    rwkv = planner.autotune(ARCH, wp, hw.DEFAULT, **kw)
    assert rwkv.cache_layout == "dense"


def test_expected_tokens_per_slot_p95():
    from repro.plan import planner
    from repro.serving.workload import WorkloadItem

    items = [WorkloadItem(t=0.0, prompt=[1] * p, max_new_tokens=4,
                          eos_id=None, deadline=None)
             for p in list(range(1, 20)) + [60]]
    t = planner.expected_tokens_per_slot(items, max_len=32)
    assert t == 23.0                     # p95 of prompt+4 capped at 32
    assert planner.expected_tokens_per_slot([], max_len=32) == 32.0


# ---------------------------------------------------------------------------
# Deadline-aware admission control (shed_late)
# ---------------------------------------------------------------------------


def test_shed_late_rejects_provably_late_only(built):
    eng = _engine(built, max_batch=2, max_len=32, shed_late=True,
                  policy="edf")
    # needs 8 ticks minimum; deadline 3 is provably late at tick 0
    late = eng.submit([1, 2, 3], max_new_tokens=8, deadline=3.0)
    assert late.shed and not late.done
    # deadline exactly at the earliest completion (tick 0 + 8) is feasible
    tight = eng.submit([1, 2, 3], max_new_tokens=8, deadline=8.0)
    assert not tight.shed
    # no deadline -> never shed
    free = eng.submit([1, 2, 3], max_new_tokens=8)
    assert not free.shed
    eng.run()
    assert tight.done and free.done and not late.done
    assert eng.stats()["shed"] == 1
    # the SLO block reports the shed count; shed requests count as misses
    from repro.serving import metrics as smetrics
    agg = smetrics.aggregate([late, tight, free], ticks=eng.ticks,
                             util_history=eng.util_history)
    assert agg["slo"]["shed"] == 1
    assert agg["slo"]["n"] == 2 and agg["slo"]["met"] == 1


def test_shed_disabled_by_default(built):
    eng = _engine(built, max_batch=2, max_len=32)
    r = eng.submit([1, 2, 3], max_new_tokens=8, deadline=1.0)
    assert not r.shed            # admission control is opt-in
    eng.run()
    assert r.done and eng.stats()["shed"] == 0


def test_shed_eos_requests_use_conservative_bound(built):
    eng = _engine(built, max_batch=2, max_len=32, shed_late=True)
    # an eos_id request could retire at its prefill token, so only a
    # deadline earlier than one tick from now is provably late
    ok = eng.submit([1, 2, 3], max_new_tokens=8, eos_id=0, deadline=1.0)
    assert not ok.shed
    late = eng.submit([1, 2, 3], max_new_tokens=8, eos_id=0, deadline=0.5)
    assert late.shed


# ---------------------------------------------------------------------------
# Batched eviction
# ---------------------------------------------------------------------------


def test_snapshot_many_is_one_transfer_and_bit_exact(built, monkeypatch):
    eng = _engine(built, max_batch=3, max_len=32)
    for i in range(3):
        eng.submit([5 + i, 6, 7 + i], max_new_tokens=10)
    eng.step()
    eng.step()
    seq = [eng.sm.snapshot(i) for i in range(3)]

    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    batch = eng.sm.snapshot_many([0, 1, 2])
    assert len(calls) == 1                  # one transfer for all victims
    monkeypatch.undo()
    for s, b in zip(seq, batch):
        assert s.next_token == b.next_token
        for x, y in zip(jax.tree.leaves(s.cache_col),
                        jax.tree.leaves(b.cache_col)):
            assert np.asarray(x).dtype == np.asarray(y).dtype
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_preempt_many_matches_sequential_schedule(built):
    def run(batched):
        eng = _engine(built, max_batch=3, max_len=32)
        reqs = [eng.submit([5 + i, 6, 7], max_new_tokens=12)
                for i in range(3)]
        eng.step()
        if batched:
            eng.preempt_many([0, 2])
        else:
            # the pre-batching behavior: one snapshot per victim
            for slot in (0, 2):
                req = eng.sm.slots[slot]
                req.saved = eng.sm.snapshot(slot)
                req.n_preempts += 1
                req.t_preempts.append(eng.ticks)
                eng.metrics["engine.preemptions"].inc()
                eng.metrics["engine.evicted_tokens"].inc(len(req.output))
                eng.sm.release(slot)
                eng.scheduler.requeue_front(req)
        eng.run()
        assert all(r.done for r in reqs)
        return [(r.t_admit, r.t_done, tuple(r.output), r.n_preempts)
                for r in reqs]

    assert run(batched=True) == run(batched=False)


# ---------------------------------------------------------------------------
# dse: the serving batch dimension reaches the tile search
# ---------------------------------------------------------------------------


def test_tile_search_scores_serving_batch():
    cfg = RNNCellConfig("lstm", 4096, precision="bf16")
    # regression pin: at batch 1 the big 512-lane tile is VMEM-resident;
    # at the serving batch the h/c state squeezes it out and the search
    # correctly drops to 256-lane tiles
    assert dse.best_plan(cfg).bh == 512
    assert dse.best_plan(cfg, max_batch=256).bh == 256
    # vmem accounting actually moved
    assert dse.tile_vmem_bytes(cfg, 128, max_batch=256) > \
        dse.tile_vmem_bytes(cfg, 128)
    # default path unchanged (max_batch=None == cfg.batch)
    assert dse.plan_metrics(cfg, 128) == \
        dse.plan_metrics(cfg, 128, max_batch=cfg.batch)


def test_batched_decode_compute_bound_scales():
    cfg = RNNCellConfig("lstm", 1024, precision="bf16")
    p1 = dse.plan_metrics(cfg, 1024, max_batch=1)
    p256 = dse.plan_metrics(cfg, 1024, max_batch=256)
    assert p256.step_latency_s > p1.step_latency_s


# ---------------------------------------------------------------------------
# Benchmark surface
# ---------------------------------------------------------------------------


def test_serving_load_cell_converter_and_plan():
    from repro.configs import SERVING_LOAD_SWEEP, ServingLoadCell

    old = ServingLoadCell("rwkv6-1.6b", "rwkv", 2, 0.5)
    assert old.arch == "rwkv6-1.6b" and old.max_batch == 2
    assert old.rate == 0.5 and old.policy == "fcfs"
    assert old.plan.max_len == ServingLoadCell.MAX_LEN
    assert old.workload.max_new_tokens == ServingLoadCell.MAX_NEW
    # plan-first construction with a tag
    new = ServingLoadCell(family="rwkv", plan=old.plan,
                          workload=old.workload, tag="auto")
    assert new.name == old.name + "/auto"
    # every sweep cell carries a valid plan + workload
    for c in SERVING_LOAD_SWEEP:
        c.plan.validate()
        assert c.workload.rate > 0


@pytest.mark.slow
def test_run_cell_embeds_resolved_plan():
    from benchmarks import serving_load as sl
    from repro.configs import ServingLoadCell

    cell = ServingLoadCell("rwkv6-1.6b", "rwkv", 2, 0.5)
    out = sl.run_cell(cell, duration=8.0, seed=0)
    plan = plan_io.from_dict(out["plan"])
    plan.validate()
    assert plan.buckets is not None          # resolved: buckets explicit
    assert plan.arch == cell.arch and plan.max_batch == cell.max_batch
    # a cell re-run from its recorded plan reproduces the metrics
    recell = ServingLoadCell(family=cell.family, plan=plan,
                             workload=cell.workload)
    again = sl.run_cell(recell, duration=8.0, seed=0)
    assert again["metrics"] == out["metrics"]


@pytest.mark.parametrize("max_batch,bucket,rows", [
    (16, 64, 16), (24, 256, 4), (24, 512, 2), (24, 1024, 1), (24, 2048, 1),
    (2, 8, 2)])
def test_prefill_rows_hold_the_token_budget(max_batch, bucket, rows):
    """A bucketed prefill call holds PREFILL_TOKENS padded tokens, at least
    one row and at most max_batch: short buckets keep max_batch rows,
    long ones take fewer."""
    from repro.plan.plan import PREFILL_TOKENS

    plan = ServingPlan(arch=ARCH, max_batch=max_batch, max_len=4096)
    assert PREFILL_TOKENS == 1024
    assert plan.prefill_rows(bucket) == rows
