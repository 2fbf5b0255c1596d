"""Fault injection + recovery (PR 8).

The contract under test: every fault class in
:mod:`repro.serving.faults` is (a) survivable — each submitted request
either completes or is accountably shed, never silently lost or
silently wrong — and (b) deterministic — the same seeded workload under
the same :class:`FaultPlan` replays byte-identically, and a
killed-and-restored engine finishes with a schedule bit-identical to an
uninterrupted run.  Recovery must also be *clean*: when every faulted
request survives its retries, the final outputs match a fault-free run
of the same workload token-for-token (rollback restores the exact
pre-fault state; greedy decode then reproduces the same tokens).
"""

import inspect
import json

import jax
import pytest

from repro.checkpoint import CheckpointManager
from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan.plan import ServingPlan
from repro.serving import (FaultInjector, FaultPlan, FaultReport, FaultSpec,
                           ServingEngine, VirtualClock, drive,
                           drive_resilient, make_workload)
from repro.serving.faults import make_storm
from repro.serving.recovery import Recovery
from repro.testing import reduced_config


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, Sharder(None, {})


def _plan(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 32)
    return ServingPlan(arch="rwkv6-1.6b", reduced=True, **kw).resolve()


def _engine(setup, **kw):
    cfg, model, params, sharder = setup
    return ServingEngine.from_plan(_plan(**kw), params, model=model,
                                   sharder=sharder)


def _items(setup, *, rate=0.8, duration=20.0, seed=7):
    cfg = setup[0]
    return make_workload("poisson", rate=rate, duration=duration, seed=seed,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 8),
                         max_new_tokens=(4, 10))


def _schedule(reqs):
    return {r.uid: (tuple(r.output), r.t_admit, r.t_first, r.t_done)
            for r in reqs}


def _outputs(reqs):
    return {r.uid: tuple(r.output) for r in reqs}


def _baseline(setup, items):
    """The fault-free run every clean recovery must reproduce exactly."""
    return _schedule(drive(_engine(setup), items, VirtualClock()))


# ---------------------------------------------------------------------------
# FaultSpec / FaultPlan: schema discipline
# ---------------------------------------------------------------------------


def test_spec_roundtrip():
    s = FaultSpec("poison_slot", tick=7, slot=2, mode="garbage", seed=3)
    assert FaultSpec.from_json(json.loads(json.dumps(s.to_json()))) == s


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("melt_tpu", tick=1).validate()
    with pytest.raises(ValueError, match="tick must be >= 0"):
        FaultSpec("poison_slot", tick=-1).validate()
    with pytest.raises(ValueError, match="unknown poison mode"):
        FaultSpec("poison_slot", tick=1, mode="gremlins").validate()
    with pytest.raises(ValueError, match="unknown FaultSpec fields"):
        FaultSpec.from_json({"kind": "poison_slot", "tick": 1, "wat": 2})
    with pytest.raises(ValueError, match="needs at least"):
        FaultSpec.from_json({"kind": "poison_slot"})


def test_plan_roundtrip_and_save_load(tmp_path):
    p = FaultPlan((FaultSpec("kill_engine", tick=9),
                   FaultSpec("stall_slot", tick=3, slot=1)))
    assert FaultPlan.from_dict(json.loads(json.dumps(p.to_dict()))) == p
    assert p.needs_watchdog() and p.needs_checkpoints()
    assert p.kinds == ("kill_engine", "stall_slot")
    path = str(tmp_path / "fp.json")
    p.save(path)
    assert FaultPlan.load(path) == p
    with pytest.raises(ValueError, match="unsupported fault-plan schema"):
        FaultPlan.from_dict({"schema": "fault_plan/v9", "faults": []})
    with pytest.raises(ValueError, match="unknown FaultPlan fields"):
        FaultPlan.from_dict({"faults": [], "extra": 1})


def test_injector_one_shot():
    inj = FaultInjector(FaultPlan((FaultSpec("poison_slot", tick=2),)))
    assert inj.due(1) == []
    (idx, spec), = inj.due(5)
    inj.fire(idx, 5)
    assert inj.due(5) == [] and inj.pending() == 0
    assert inj.log[0]["fired_at"] == 5
    with pytest.raises(ValueError, match="already fired"):
        inj.fire(idx, 6)


def test_make_storm_deterministic():
    a, b = make_storm(duration=30, seed=5), make_storm(duration=30, seed=5)
    assert a == b
    assert sum(s.kind == "kill_engine" for s in a.faults) <= 1
    with pytest.raises(ValueError, match="unknown fault kind"):
        make_storm(duration=10, kinds=("melt_tpu",))


# ---------------------------------------------------------------------------
# Recovery: each fault class, clean runs reproduce the fault-free outputs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ("nan", "garbage"))
def test_poison_quarantine_retry_complete(setup, mode):
    items = _items(setup)
    base = _outputs(drive(_engine(setup), items, VirtualClock()))
    eng = _engine(setup)
    inj = FaultInjector(FaultPlan(
        (FaultSpec("poison_slot", tick=4, slot=0, mode=mode, seed=9),)))
    rep = drive_resilient(eng, items, VirtualClock(), injector=inj)
    fs = eng.fault_stats()
    assert fs == {"injected": 1, "quarantined": 1, "retries": 1,
                  "shed": 0, "watchdog_evictions": 0}
    assert not rep.lost_uids() and not rep.shed_uids
    # recovery costs ticks (timings shift) but never tokens: outputs are
    # token-for-token the fault-free run's
    assert _outputs(rep.completed) == base
    ev, = rep.fault_events
    assert ev["kind"] == "poison" and ev["recovered_at"] is not None


def test_retry_budget_exhaustion_sheds(setup):
    items = _items(setup)
    base = _outputs(drive(_engine(setup), items, VirtualClock()))
    eng = _engine(setup, retry_budget=0)
    inj = FaultInjector(FaultPlan(
        (FaultSpec("poison_slot", tick=4, slot=0),)))
    rep = drive_resilient(eng, items, VirtualClock(), injector=inj)
    fs = eng.fault_stats()
    assert fs["shed"] == 1 and fs["retries"] == 0
    assert len(rep.shed_uids) == 1
    assert not rep.lost_uids()              # shed is accounted, not lost
    shed = next(r for r in rep.requests if r.shed)
    assert not shed.done
    # whatever it emitted before the fault is genuine: a prefix of the
    # fault-free run's tokens — suspect (post-poison) tokens never land
    assert tuple(shed.output) == base[shed.uid][:len(shed.output)]


def test_stall_watchdog_recovers(setup):
    items = _items(setup)
    base = _outputs(drive(_engine(setup), items, VirtualClock()))
    eng = _engine(setup, watchdog_ticks=3)
    inj = FaultInjector(FaultPlan((FaultSpec("stall_slot", tick=5, slot=1),)))
    rep = drive_resilient(eng, items, VirtualClock(), injector=inj)
    fs = eng.fault_stats()
    assert fs["watchdog_evictions"] == 1 and fs["quarantined"] == 1
    assert not rep.lost_uids() and not rep.shed_uids
    assert _outputs(rep.completed) == base


def test_stall_without_watchdog_rejected(setup):
    eng = _engine(setup)   # watchdog_ticks=0
    inj = FaultInjector(FaultPlan((FaultSpec("stall_slot", tick=5),)))
    with pytest.raises(ValueError, match="watchdog"):
        eng.attach_injector(inj)


def test_fail_prefill_retries(setup):
    items = _items(setup)
    base = _outputs(drive(_engine(setup), items, VirtualClock()))
    eng = _engine(setup)
    inj = FaultInjector(FaultPlan((FaultSpec("fail_prefill", tick=2),)))
    rep = drive_resilient(eng, items, VirtualClock(), injector=inj)
    fs = eng.fault_stats()
    assert fs["injected"] == 1 and fs["retries"] >= 1
    assert not rep.lost_uids() and not rep.shed_uids
    assert _outputs(rep.completed) == base


def test_drop_readback_rolls_back(setup):
    items = _items(setup)
    base = _outputs(drive(_engine(setup), items, VirtualClock()))
    eng = _engine(setup)
    inj = FaultInjector(FaultPlan((FaultSpec("drop_readback", tick=6),)))
    rep = drive_resilient(eng, items, VirtualClock(), injector=inj)
    fs = eng.fault_stats()
    assert fs["injected"] == 1 and fs["quarantined"] >= 1
    assert not rep.lost_uids() and not rep.shed_uids
    assert _outputs(rep.completed) == base


def test_fault_free_stats_surface_unchanged(setup):
    """Byte-stability guard: a no-fault engine exposes no fault keys in
    stats() and emits no fault events — the committed BENCH blocks and
    traces cannot shift."""
    eng = _engine(setup)
    drive(eng, _items(setup), VirtualClock())
    assert not any(k.startswith("fault") for k in eng.stats())
    assert eng.recovery is None
    assert eng.fault_stats() == {"injected": 0, "quarantined": 0,
                                 "retries": 0, "shed": 0,
                                 "watchdog_evictions": 0}


# ---------------------------------------------------------------------------
# Crash-restart: the checkpoint/restore proof
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stalled", (False, True),
                         ids=("plain", "stalled-under-watchdog"))
def test_crash_restart_bit_identical(setup, tmp_path, stalled):
    """THE tentpole proof: kill the engine mid-run; the restored run loses
    zero requests and finishes with a schedule bit-identical to a run
    that was never killed.  ``stalled``: the restored checkpoint was
    taken while a slot was wedged under the watchdog, so the recovery's
    journal fields (stalled slots, last progress, fault counters) must
    round-trip for the schedules to match."""
    items = _items(setup)
    if stalled:
        knobs = {"watchdog_ticks": 6}
        faults = (FaultSpec("stall_slot", tick=5, slot=1),)
        base = _schedule(drive_resilient(
            _engine(setup, **knobs), items, VirtualClock(),
            injector=FaultInjector(FaultPlan(faults))).requests)
    else:
        knobs, faults = {}, ()
        base = _baseline(setup, items)
    mgr = CheckpointManager(str(tmp_path), keep=1000)
    inj = FaultInjector(FaultPlan(faults + (FaultSpec("kill_engine",
                                                      tick=9),)))
    rep = drive_resilient(_engine(setup, **knobs), items, VirtualClock(),
                          injector=inj, manager=mgr, checkpoint_every=4)
    assert rep.n_restarts == 1
    journal = mgr.manifest(rep.engine.restored_from["step"])["extra"]
    assert bool(journal["engine"]["stalled"]) == stalled
    assert not rep.lost_uids() and not rep.shed_uids
    assert sorted(_schedule(rep.requests)) == sorted(base)   # no dup uids
    assert _schedule(rep.requests) == base
    assert rep.engine.fault_stats()["injected"] == 1 + len(faults)
    kill_evs = [e for e in rep.fault_events if e["kind"] == "kill_engine"]
    assert len(kill_evs) == 1   # the consumed kill did not re-fire


def test_plain_engine_never_enters_recovery(setup, monkeypatch):
    """A plan with no watchdog and no injector builds no recovery, and
    the hot path never calls into it: with every Recovery method patched
    to raise, the engine serves a workload to the end with the same
    schedule."""
    items = _items(setup)
    base = _baseline(setup, items)

    def entered(*args, **kwargs):
        raise AssertionError("a plain engine entered its recovery")

    for name, fn in inspect.getmembers(Recovery, inspect.isfunction):
        monkeypatch.setattr(Recovery, name, entered)
    eng = _engine(setup)
    assert eng.recovery is None
    reqs = drive(eng, items, VirtualClock())
    assert all(r.done for r in reqs)
    assert _schedule(reqs) == base
    assert eng.fault_stats() == dict.fromkeys(eng.fault_stats(), 0)


def test_kill_without_manager_rejected(setup):
    inj = FaultInjector(FaultPlan((FaultSpec("kill_engine", tick=3),)))
    with pytest.raises(ValueError, match="CheckpointManager"):
        drive_resilient(_engine(setup), _items(setup), VirtualClock(),
                        injector=inj)


def test_resilient_driver_requires_virtual_clock(setup):
    from repro.serving import WallClock
    with pytest.raises(ValueError, match="VirtualClock"):
        drive_resilient(_engine(setup), _items(setup), WallClock())


def test_resilient_no_faults_matches_drive(setup):
    """drive_resilient with no injector and no manager is drive()."""
    items = _items(setup)
    base = _baseline(setup, items)
    rep = drive_resilient(_engine(setup), items, VirtualClock())
    assert isinstance(rep, FaultReport) and rep.n_restarts == 0
    assert _schedule(rep.requests) == base


# ---------------------------------------------------------------------------
# Determinism: same seed + same FaultPlan -> byte-identical chaos runs
# ---------------------------------------------------------------------------


def test_chaos_runs_byte_identical(setup, tmp_path):
    items = _items(setup, duration=24.0)
    storm = make_storm(duration=20, seed=2, max_batch=2,
                       kinds=("poison_slot", "fail_prefill", "kill_engine",
                              "drop_readback"))

    def run(d):
        mgr = CheckpointManager(str(tmp_path / d))
        rep = drive_resilient(_engine(setup), items, VirtualClock(),
                              injector=FaultInjector(storm), manager=mgr,
                              checkpoint_every=4)
        assert not rep.lost_uids()
        return json.dumps({
            "schedule": sorted(_schedule(rep.requests).items()),
            "events": rep.fault_events,
            "stats": rep.engine.fault_stats(),
            "restarts": rep.n_restarts,
        }, sort_keys=True)

    assert run("a") == run("b")
