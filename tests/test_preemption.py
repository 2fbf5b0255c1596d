"""Preempt → evict-to-host → resume: the slot-state manager contract.

The acceptance property: an evicted request resumes the exact token
trajectory (and, when the slot is re-granted without delay, the exact
tick stamps) it would have produced uninterrupted — across KV-ring
(dense), rwkv-recurrent, and hybrid ssd/conv (hymba) cache pytrees.
Plus the EDF end-to-end behaviour: a tighter deadline evicts a running
request, runs, and the victim still completes bit-exactly."""

import jax
import pytest

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.testing import reduced_config

NOSH = Sharder(None, {})


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(setup, seed=0, **knobs):
    cfg, model, params = setup
    knobs.setdefault("max_batch", 2)
    knobs.setdefault("max_len", 32)
    return ServingEngine.from_plan(ServingPlan(arch=cfg.name, **knobs),
                                   params, model=model, sharder=NOSH,
                                   seed=seed)


def _solo_output(model, params, prompt, max_new, max_len=32):
    eng = _engine((model.cfg, model, params), max_batch=1, max_len=max_len)
    r = eng.submit(list(prompt), max_new_tokens=max_new)
    eng.run()
    return r.output


# ------------------------------------------------- bit-exact resume property


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "qwen2.5-14b", "hymba-1.5b"])
def test_preempt_evict_resume_bit_exact(arch):
    """Evict a mid-decode request to host, serve an unrelated request
    through the same slot (clobbering the device state the victim used),
    resume — the victim's tokens are bit-identical to an uninterrupted
    run.  Covers KV rings, rwkv wkv/shift state, and hymba's ssd/conv
    hybrid via the same gather/scatter contract."""
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [5, 9, 3, 7, 2]
    base = _solo_output(model, params, prompt, 10)

    eng = _engine((cfg, model, params), max_batch=1)
    a = eng.submit(list(prompt), max_new_tokens=10)
    for _ in range(3):
        eng.step()
    assert not a.done and len(a.output) >= 3
    n_at_evict = len(a.output)
    eng.preempt(0)
    assert a.saved is not None and a.n_preempts == 1
    held = eng.scheduler.queue.popleft()     # keep A aside while B runs
    assert held is a
    b = eng.submit([2, 4, 6, 8], max_new_tokens=6)
    eng.run()
    assert b.done and not a.done             # B used (and clobbered) slot 0
    eng.scheduler.requeue_front(a)
    eng.run()
    assert a.done and a.saved is None
    assert a.output == base                  # bit-exact across the round trip
    assert len(a.t_resumes) == 1
    assert eng.stats()["preemptions"] == 1
    assert eng.stats()["resumes"] == 1
    assert eng.stats()["evicted_tokens"] == n_at_evict


def test_immediate_resume_is_schedule_noop(setup):
    """Preempt between steps and let the scheduler re-grant the slot on
    the very next step: tokens AND tick stamps of every request match the
    uninterrupted run exactly (stochastic sampling included — same slot,
    same tick sequence, same key stream)."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8]]
    def serve(preempt_at):
        eng = _engine(setup, seed=7, temperature=0.8, top_k=5)
        reqs = [eng.submit(list(p), max_new_tokens=8) for p in prompts]
        for k in range(3):
            eng.step()
            if k == preempt_at:
                eng.preempt(0)
        eng.run()
        return [(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done)
                for r in reqs], eng.util_history

    uninterrupted = serve(preempt_at=None)
    interrupted = serve(preempt_at=1)
    assert interrupted == uninterrupted


def test_resume_lands_in_a_different_slot(setup):
    """Slot identity is not part of the saved state: a request evicted
    from slot 0 resumes bit-exactly from whichever slot frees first."""
    cfg, model, params = setup
    prompt = [3, 1, 4, 1, 5]
    base = _solo_output(model, params, prompt, 12)

    eng = _engine(setup)                       # max_batch=2, greedy
    a = eng.submit(list(prompt), max_new_tokens=12)
    b = eng.submit([2, 7, 1, 8], max_new_tokens=6)
    for _ in range(2):
        eng.step()                             # a -> slot 0, b -> slot 1
    assert eng.sm.slots[0] is a and eng.sm.slots[1] is b
    eng.preempt(0)
    held = eng.scheduler.queue.popleft()       # hold A; C takes slot 0
    c = eng.submit([9, 9, 2], max_new_tokens=12)
    while not b.done:
        eng.step()
    eng.scheduler.requeue_front(held)
    eng.step()
    assert eng.sm.slots[1] is a                # resumed into B's old slot
    eng.run()
    assert a.done and c.done
    assert a.output == base


def test_preempt_validates_slot(setup):
    eng = _engine(setup)
    with pytest.raises(ValueError, match="empty"):
        eng.preempt(0)


# ----------------------------------------------------------- EDF end-to-end


def test_edf_preempts_running_for_tighter_deadline(setup):
    """max_batch=1 under preemptive EDF: a late-deadline request is
    evicted the moment a strictly tighter deadline arrives, the urgent
    request runs to completion first, and the victim still finishes
    bit-exactly."""
    cfg, model, params = setup
    slow_prompt, fast_prompt = [5, 9, 3, 7, 2], [8, 6, 4]
    base_slow = _solo_output(model, params, slow_prompt, 10)
    base_fast = _solo_output(model, params, fast_prompt, 4)

    eng = _engine(setup, max_batch=1, policy="edf", preempt=True)
    slow = eng.submit(list(slow_prompt), max_new_tokens=10, deadline=500.0)
    for _ in range(3):
        eng.step()
    urgent = eng.submit(list(fast_prompt), max_new_tokens=4, deadline=10.0)
    eng.run()
    assert slow.done and urgent.done
    assert slow.n_preempts == 1 and urgent.n_preempts == 0
    assert urgent.t_done < slow.t_done       # the tight deadline went first
    assert urgent.t_admit is not None and urgent.t_admit <= urgent.t_submit + 1
    assert slow.output == base_slow          # bit-exact despite the eviction
    assert urgent.output == base_fast
    s = eng.stats()
    assert s["preemptions"] == 1 and s["resumes"] == 1


def test_edf_without_preempt_never_evicts(setup):
    cfg, model, params = setup
    eng = _engine(setup, max_batch=1, policy="edf", preempt=False)
    slow = eng.submit([5, 9, 3], max_new_tokens=8, deadline=500.0)
    eng.step()
    urgent = eng.submit([8, 6], max_new_tokens=2, deadline=5.0)
    eng.run()
    assert slow.done and urgent.done
    assert eng.stats()["preemptions"] == 0
    assert slow.t_done < urgent.t_done       # ran to completion undisturbed


def test_deadline_flows_from_submit_and_reset_clears_counters(setup):
    eng = _engine(setup)
    r = eng.submit([1, 2, 3], max_new_tokens=2, deadline=42.0)
    assert r.deadline == 42.0
    eng.run()
    eng.metrics["engine.preemptions"].inc(3)   # simulate history, then reset
    eng.reset_telemetry()
    s = eng.stats()
    assert s["preemptions"] == 0 and s["resumes"] == 0
    assert s["evicted_tokens"] == 0
