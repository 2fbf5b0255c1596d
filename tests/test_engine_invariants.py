"""ServingEngine invariants: conservation, output bounds, slot isolation,
and bit-reproducibility (the engine half of the serving-load contract —
the workload half lives in tests/test_workload.py)."""

import jax
import pytest

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine, VirtualClock, drive, make_workload
from repro.testing import reduced_config


# Every invariant in this module runs against BOTH cache layouts by
# construction: the module fixture is parameterized over cache_layout, and
# _engine() threads it into every engine it builds (PR 7 — the paged
# backing store promises dense-identical behaviour, so the whole file is
# its regression net).
@pytest.fixture(scope="module", params=("dense", "paged:8"),
                ids=("dense", "paged8"))
def setup(request):
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, Sharder(None, {}), request.param


def _engine(setup, seed=0, **knobs):
    cfg, model, params, sharder, layout = setup
    knobs.setdefault("max_batch", 2)
    knobs.setdefault("max_len", 32)
    knobs.setdefault("cache_layout", layout)
    return ServingEngine.from_plan(ServingPlan(arch=cfg.name, **knobs),
                                   params, model=model, sharder=sharder,
                                   seed=seed)


def test_drained_run_conserves_requests(setup):
    """submitted == completed == finished after a full drain; no request is
    lost or duplicated, and output lengths never exceed max_new_tokens
    (including the max_new_tokens=1 admit-tick completion edge)."""
    eng = _engine(setup)
    reqs = [eng.submit([1, 2, 3 + i], max_new_tokens=1 + i % 5)
            for i in range(7)]
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.stats()["completed"] == len(reqs) == len(eng.finished)
    assert sorted(r.uid for r in eng.finished) == [r.uid for r in reqs]
    assert not eng.has_work()
    assert eng.stats()["active"] == 0 and eng.stats()["queued"] == 0
    for r in reqs:
        assert 1 <= len(r.output) <= r.max_new_tokens


def test_output_bound_under_open_loop_arrivals(setup):
    """The length invariant holds under asynchronous (Poisson) arrivals
    too, where admits and completions interleave arbitrarily."""
    cfg = setup[0]
    eng = _engine(setup)
    items = make_workload("poisson", rate=0.8, duration=16.0, seed=2,
                          vocab_size=cfg.vocab_size, prompt_len=(2, 6),
                          max_new_tokens=(1, 6))
    reqs = drive(eng, items, VirtualClock())
    assert len(reqs) == eng.stats()["completed"]
    for r in reqs:
        assert r.done and 1 <= len(r.output) <= r.max_new_tokens


def test_prefill_only_ticks_advance_time(setup):
    """An all-max_new_tokens=1 workload finishes every request at its
    prefill token; time must still advance (no frozen stamps, no NaN
    throughput) and a freed slot admits the next request in the same
    tick rather than idling it."""
    from repro.serving import aggregate

    eng = _engine(setup, max_batch=1)
    reqs = [eng.submit([1, 2, 3 + i], max_new_tokens=1) for i in range(3)]
    eng.run()
    assert all(r.done and len(r.output) == 1 for r in reqs)
    assert eng.ticks >= 1
    assert [r.t_done for r in reqs] == [0, 0, 0]  # same-tick slot reuse
    agg = aggregate(reqs, ticks=eng.ticks, util_history=eng.util_history)
    assert agg["tokens_per_sec"] > 0
    # util reports the TRUE ratio: 3 instant admits through 1 slot in one
    # tick -> 3.0, not clamped to 1.0; the clamp used to hide over-unity
    # instant-admit ticks.  stats() counts them explicitly.
    assert agg["mean_util"] == 3.0
    assert eng.stats()["instant_admits"] == 3


def test_reset_telemetry_requires_drained_engine(setup):
    eng = _engine(setup)
    r = eng.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(RuntimeError):
        eng.reset_telemetry()
    eng.run()
    eng.reset_telemetry()
    assert eng.ticks == 0 and eng.stats()["completed"] == 0
    assert not eng.finished
    assert r.done  # the drained request itself is untouched


def test_eos_stops_slot_without_disturbing_neighbors(setup):
    """Forcing an early EOS on one slot must not change what the other
    slot generates (greedy decoding)."""
    prompt_a, prompt_b = [5, 9, 3, 7], [2, 4, 6, 8, 10]

    solo_a = _engine(setup, max_batch=1)
    ra = solo_a.submit(list(prompt_a), max_new_tokens=8)
    solo_a.run()
    solo_b = _engine(setup, max_batch=1)
    rb = solo_b.submit(list(prompt_b), max_new_tokens=8)
    solo_b.run()

    # pick B's 3rd token as EOS: B must stop at its first emission of it
    eos = rb.output[2]
    stop_at = rb.output.index(eos) + 1
    multi = _engine(setup)
    ma = multi.submit(list(prompt_a), max_new_tokens=8)
    mb = multi.submit(list(prompt_b), max_new_tokens=8, eos_id=eos)
    multi.run()
    assert mb.output == rb.output[:stop_at]          # stopped by EOS
    assert ma.output == ra.output                    # neighbor undisturbed
    assert ma.t_done is not None and mb.t_done is not None
    assert mb.t_done < ma.t_done                     # B's slot freed early


def test_fixed_seed_bit_reproducible_across_constructions(setup):
    """Two engines built with the same seed replay a stochastic-sampling
    workload identically: same tokens, same tick stamps, same stats."""
    cfg = setup[0]

    def one():
        eng = _engine(setup, seed=123, temperature=0.8, top_k=5)
        items = make_workload("mmpp", rate=0.4, duration=12.0, seed=9,
                              vocab_size=cfg.vocab_size, prompt_len=(2, 5),
                              max_new_tokens=(2, 5))
        reqs = drive(eng, items, VirtualClock())
        return ([(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done)
                 for r in reqs], eng.stats(), eng.util_history)

    assert one() == one()
