"""repro.obs: metrics registry, structured tracing, live metrics, and the
observed-traffic workload fit.

Pure units first (no model build: registry semantics, tracer event
schema, fit_profile estimators on synthetic traces), then engine
integration on the shared reduced model (trace byte-determinism across
same-seed runs, windowed-live == end-of-run-aggregate, and the
one-call ``reset_telemetry`` covering scheduler + slot counters)."""

import json

import jax
import pytest

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.obs import (LiveMetrics, MetricsRegistry, Tracer, check_trace,
                       fit_profile)
from repro.obs.observe import observed_span_ticks, summarize
from repro.obs.trace import TICK_US, TRACE_SCHEMA
from repro.serving import ServingEngine, VirtualClock, drive
from repro.serving.engine import Request
from repro.serving.workload import profile_items
from repro.plan.plan import ServingPlan, WorkloadProfile
from repro.testing import reduced_config


# ---------------------------------------------------------------------------
# registry units (no model)
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    c.inc()
    c.inc(4)
    g = reg.gauge("a.level")
    g.set(2.5)
    h = reg.histogram("a.lat")
    for v in (1.0, 2.0, 3.0, 10.0):
        h.observe(v)
    assert reg.snapshot() == {"a.count": 5, "a.lat": 4, "a.level": 2.5}
    assert h.summary()["p50"] == 2.0 and h.summary()["n"] == 4
    reg.reset()
    assert reg.snapshot() == {"a.count": 0, "a.lat": 0, "a.level": 0.0}


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")   # idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    assert "x" in reg and reg["x"].kind == "counter"


def test_derived_gauge_is_live_and_unsettable():
    reg = MetricsRegistry()
    state = {"v": 1.0}
    g = reg.gauge("d", fn=lambda: state["v"])
    assert g.value == 1.0
    state["v"] = 7.0
    assert g.value == 7.0
    with pytest.raises(ValueError, match="derived"):
        g.set(0.0)
    reg.reset()                     # derived gauges ignore reset
    assert g.value == 7.0


def test_registry_view_preserves_caller_key_order():
    reg = MetricsRegistry()
    reg.counter("m.b").inc(2)
    reg.counter("m.a").inc(1)
    view = reg.view({"bee": "m.b", "ay": "m.a"})
    assert list(view) == ["bee", "ay"] and view == {"bee": 2, "ay": 1}


# ---------------------------------------------------------------------------
# tracer units (synthetic requests, no model)
# ---------------------------------------------------------------------------


def _fake_done_request(uid=0, t_submit=0, t_admit=1, t_first=1, t_done=4,
                       n_tokens=4, deadline=None):
    r = Request(uid, [1, 2, 3], max_new_tokens=n_tokens, deadline=deadline,
                t_submit=t_submit)
    r.t_admit, r.t_first, r.t_done = t_admit, t_first, t_done
    r.output = list(range(n_tokens))
    r.done = True
    return r


def test_tracer_lifecycle_events_validate_and_roundtrip(tmp_path):
    tr = Tracer()
    req = _fake_done_request(uid=3, deadline=9.0)
    tr.request_submit(req, 0)
    tr.prefill(1, bucket=4, rows=2, n_reqs=1, overlap=True)
    tr.compile(1, "prefill", rows=2, length=4)
    tr.decode_chunk(1, n_ticks=3, n_slots=1)
    tr.host_sync(4)
    tr.counter(2, "util", 0.5)
    tr.counter(2, "queue_depth", 0)
    tr.request_done(req, 4)
    doc = tr.to_chrome()
    check_trace(doc)
    assert doc["otherData"]["schema"] == TRACE_SCHEMA
    # ticks scale to TICK_US in the export
    sub = next(e for e in doc["traceEvents"] if e["name"] == "submit")
    assert sub["ts"] == 0 and sub["args"]["deadline"] == 9.0
    run = next(e for e in doc["traceEvents"] if e["name"] == "run")
    assert run["ts"] == 1 * TICK_US and run["dur"] == 4 * TICK_US
    # canonical file round-trips through json and still validates
    p = tmp_path / "t.json"
    tr.save(str(p))
    check_trace(json.loads(p.read_text()))
    assert p.read_text() == tr.dumps()


def test_check_trace_rejects_schema_drift():
    tr = Tracer()
    tr.host_sync(1)
    doc = tr.to_chrome()
    bad = dict(doc)
    bad["otherData"] = {"schema": "nope", "tick_us": TICK_US}
    with pytest.raises(ValueError, match="schema"):
        check_trace(bad)
    tr2 = Tracer()
    tr2._add("mystery", "engine", "i", 0, 0)
    with pytest.raises(ValueError, match="unknown event"):
        check_trace(tr2.to_chrome())
    # non-tick-aligned timestamp
    from repro.obs.trace import TraceEvent
    tr3 = Tracer()
    tr3.events.append(TraceEvent("host_sync", "engine", "i", 1, 1, 0))
    with pytest.raises(ValueError, match="tick-aligned"):
        check_trace(tr3.to_chrome())


def test_tracer_reset_empties_event_log():
    tr = Tracer()
    tr.host_sync(0)
    assert len(tr) == 1
    tr.reset()
    assert len(tr) == 0 and tr.dumps() == Tracer().dumps()


# ---------------------------------------------------------------------------
# fit_profile units (synthetic traces)
# ---------------------------------------------------------------------------


def _trace_with_submits(specs):
    """specs: (tick, prompt_len, max_new, deadline) tuples."""
    tr = Tracer()
    for uid, (t, plen, mnew, dl) in enumerate(specs):
        r = Request(uid, list(range(plen)), max_new_tokens=mnew,
                    deadline=dl, t_submit=t)
        tr.request_submit(r, t)
    return tr


def test_fit_profile_recovers_rate_ranges_and_slack():
    specs = [(t, 4 + t % 8, 6 + t % 5, float(t + 3 * (6 + t % 5)))
             for t in range(0, 40, 2)]                 # one every 2 ticks
    tr = _trace_with_submits(specs)
    p = fit_profile(tr)
    assert isinstance(p, WorkloadProfile)
    assert p.rate == pytest.approx(len(specs) / 39.0)  # span = last + 1
    assert p.prompt_len == (4, 10)   # t is even, so t%8 tops out at 6
    assert p.max_new_tokens == (6, 10)
    assert p.heavy_decode is None
    assert p.deadline_slack == pytest.approx(3.0)
    assert p.deadline_frac == 1.0
    assert observed_span_ticks(tr) == 39
    # the explicit recording window overrides the observed span
    assert fit_profile(tr, duration=100.0).rate \
        == pytest.approx(len(specs) / 100.0)


def test_fit_profile_splits_heavy_decode_tail():
    base = [(t, 8, 6 + t % 5, None) for t in range(40)]
    heavy = [(t, 8, 30 + t % 11, None) for t in range(0, 40, 10)]
    p = fit_profile(_trace_with_submits(base + heavy))
    assert p.max_new_tokens == (6, 10)
    frac, lo, hi = p.heavy_decode
    assert frac == pytest.approx(len(heavy) / (len(base) + len(heavy)))
    assert 30 <= lo <= hi <= 40
    # deadline-less traffic fits a deadline-less profile
    assert p.deadline_slack is None and not p.has_deadlines


def test_fit_profile_fits_workload_profile_from_trace_classmethod():
    tr = _trace_with_submits([(0, 4, 8, None), (4, 6, 8, None)])
    p = WorkloadProfile.from_trace(tr, duration=8.0)
    assert p.rate == pytest.approx(2 / 8.0)
    assert summarize(tr)["submits"] == 2


def test_fit_profile_empty_trace_raises():
    with pytest.raises(ValueError, match="no request submit"):
        fit_profile(Tracer())


# ---------------------------------------------------------------------------
# LiveMetrics units
# ---------------------------------------------------------------------------


def test_live_metrics_window_eviction():
    lm = LiveMetrics(window=4)
    lm.observe_request(_fake_done_request(t_done=0), 0)
    for t in range(8):
        lm.observe_tick(t, 1.0)
    s = lm.snapshot()
    # the request retired at tick 0 left the window (edge = 7 - 4 = 3)
    assert s["completed"] == 0 and s["tick"] == 7
    lm.observe_request(_fake_done_request(t_done=7), 7)
    assert lm.snapshot()["completed"] == 1
    with pytest.raises(ValueError, match="window"):
        LiveMetrics(window=0)


def test_live_metrics_slo_and_reset():
    lm = LiveMetrics(window=100)
    lm.observe_request(_fake_done_request(t_done=4, deadline=10.0), 4)  # met
    lm.observe_request(_fake_done_request(t_done=4, deadline=2.0), 4)  # miss
    lm.observe_request(_fake_done_request(t_done=4), 4)       # no deadline
    s = lm.snapshot()
    assert s["slo_attainment"] == pytest.approx(0.5)
    assert s["completed"] == 3
    assert "slo=" in lm.line()
    lm.reset()
    assert lm.snapshot()["completed"] == 0
    assert lm.snapshot()["slo_attainment"] is None


# ---------------------------------------------------------------------------
# engine integration (shared reduced model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, Sharder(None, {})


def _engine(setup, tracer=None, **knobs):
    cfg, model, params, sharder = setup
    knobs.setdefault("max_batch", 2)
    knobs.setdefault("max_len", 32)
    return ServingEngine.from_plan(ServingPlan(arch=cfg.name, **knobs),
                                   params, model=model, sharder=sharder,
                                   tracer=tracer)


_PROFILE = WorkloadProfile(kind="poisson", rate=0.6, duration=24.0,
                           deadline_slack=3.0)


def _traced_run(setup, **kw):
    cfg = setup[0]
    tracer = Tracer()
    eng = _engine(setup, tracer=tracer, **kw)
    live = eng.enable_live_metrics(window=100_000)
    items = profile_items(_PROFILE, vocab_size=cfg.vocab_size, seed=0)
    reqs = drive(eng, items, VirtualClock())
    return tracer, eng, live, reqs


def test_same_seed_traces_are_byte_identical_and_valid(setup):
    tr1, eng, _, _ = _traced_run(setup, policy="edf", preempt=True)
    tr2, _, _, _ = _traced_run(setup, policy="edf", preempt=True)
    assert tr1.dumps() == tr2.dumps()
    check_trace(tr1.to_chrome())
    names = {e.name for e in tr1.events}
    assert {"submit", "queued", "run", "first_token", "prefill",
            "decode_chunk", "host_sync", "compile", "util",
            "queue_depth"} <= names
    # every completed request emitted its full lifecycle
    dones = [e for e in tr1.events if e.name == "run"]
    assert len(dones) == eng.stats()["completed"]
    # span durations line up with the request stamps
    for ev in dones:
        req = next(r for r in eng.finished
                   if r.uid == ev.args["uid"])
        assert ev.ts == req.t_admit * TICK_US
        assert ev.dur == (req.t_done + 1 - req.t_admit) * TICK_US


def test_windowed_live_metrics_match_end_of_run_aggregate(setup):
    """The property the ISSUE names: a window at least the run length
    evicts nothing, so the live snapshot must equal the end-of-run
    aggregate exactly (same request_metrics conventions)."""
    from repro.serving import metrics as smetrics

    _, eng, live, reqs = _traced_run(setup)
    agg = smetrics.aggregate(reqs, ticks=eng.ticks,
                             util_history=eng.util_history)
    snap = live.snapshot()
    assert snap["completed"] == agg["completed"]
    assert snap["ttft_p95"] == agg["ttft"]["p95"]
    assert snap["tpot_p95"] == agg["tpot"]["p95"]
    assert snap["mean_util"] == pytest.approx(agg["mean_util"])
    assert snap["slo_attainment"] == pytest.approx(
        agg["slo"]["attainment"])


def test_reset_telemetry_covers_the_whole_registry(setup):
    """One reset call zeroes engine + scheduler + slot counters by
    construction, while prefill_compiles (the jit-cache mirror) survives
    — the satellite fix for the per-attribute reset drift."""
    eng = _traced_run(setup, policy="edf", preempt=True)[1]
    s = eng.stats()
    assert s["completed"] > 0 and s["prefill_compiles"] > 0
    reg = eng.metrics.snapshot()
    assert reg["scheduler.submitted"] > 0
    assert reg["slots.prefill_inserts"] > 0
    compiles_before = s["prefill_compiles"]
    eng.reset_telemetry()
    s2 = eng.stats()
    zeroed = {k: v for k, v in s2.items()
              if k not in ("prefill_compiles", "mean_util")}
    assert all(v == 0 for v in zeroed.values()), s2
    assert s2["prefill_compiles"] == compiles_before
    reg2 = eng.metrics.snapshot()
    assert reg2["scheduler.submitted"] == 0
    assert reg2["scheduler.picked"] == 0
    assert reg2["slots.prefill_inserts"] == 0
    assert reg2["slots.snapshots"] == 0
    assert eng.tracer is not None and len(eng.tracer) == 0
    assert eng.live.snapshot()["completed"] == 0


_FRAG_COUNTERS = {"blocks_free", "bytes_resident", "padding_waste"}


def test_dense_traces_carry_no_fragmentation_counters(setup):
    """Byte-stability half of the PR-7 gauge wiring: dense engines emit
    exactly the pre-paged event vocabulary, so every previously-committed
    trace file's bytes are untouched by the new counter tracks."""
    tr, _, _, _ = _traced_run(setup)
    assert not {e.name for e in tr.events} & _FRAG_COUNTERS
    check_trace(tr.to_chrome())


def test_paged_traces_add_fragmentation_counters_deterministically(setup):
    """Paged engines emit the three fragmentation counter tracks, the
    schema validator accepts them, and same-seed runs stay
    byte-identical (the determinism contract extends to the new
    tracks)."""
    tr1, eng, _, _ = _traced_run(setup, cache_layout="paged:8")
    tr2, _, _, _ = _traced_run(setup, cache_layout="paged:8")
    assert tr1.dumps() == tr2.dumps()
    check_trace(tr1.to_chrome())
    assert _FRAG_COUNTERS <= {e.name for e in tr1.events}
    # the tracks carry the gauge values the registry serves
    assert "slots.bytes_resident" in eng.metrics
    resident = [e.args["bytes_resident"] for e in tr1.events
                if e.name == "bytes_resident"]
    assert resident and all(v >= 0 for v in resident)


@pytest.mark.parametrize("layout", ("dense", "paged:8"))
def test_fragmentation_gauges_registered_and_consistent(setup, layout):
    """The three slots.* fragmentation gauges are registered in the
    engine's shared MetricsRegistry under both layouts and satisfy
    resident = useful + waste; dense resident is the constant worst-case
    commitment, paged resident tracks occupancy."""
    eng = _engine(setup, cache_layout=layout)
    snap = eng.metrics.snapshot()
    for name in ("slots.blocks_free", "slots.bytes_resident",
                 "slots.padding_waste"):
        assert name in snap
    empty_resident = eng.sm.bytes_resident()
    eng.submit([1, 2, 3, 4], max_new_tokens=4)
    eng.step()
    assert eng.sm.bytes_resident() == \
        eng.sm.useful_bytes() + eng.sm.padding_waste()
    if layout == "dense":
        assert eng.sm.bytes_resident() == empty_resident  # constant
    else:
        assert eng.sm.bytes_resident() > empty_resident   # tracks load
    eng.run()


def test_aggregate_metrics_block_untouched_by_gauges(setup):
    """The committed BENCH ``metrics`` blocks never mention the gauges:
    aggregate() output is a pure function of the request set, identical
    whether the serving engine was dense or paged."""
    from repro.serving import metrics as smetrics

    def one(layout):
        cfg = setup[0]
        eng = _engine(setup, cache_layout=layout)
        items = profile_items(_PROFILE, vocab_size=cfg.vocab_size, seed=0)
        reqs = drive(eng, items, VirtualClock())
        return smetrics.aggregate(reqs, ticks=eng.ticks,
                                  util_history=eng.util_history)

    agg_d, agg_p = one("dense"), one("paged:8")
    assert agg_d == agg_p
    flat = json.dumps(agg_d)
    assert "blocks_free" not in flat and "bytes_resident" not in flat


def test_fit_profile_from_engine_trace_matches_offered_traffic(setup):
    tracer, _, _, reqs = _traced_run(setup)
    p = fit_profile(tracer, duration=_PROFILE.duration)
    assert p.rate == pytest.approx(len(reqs) / _PROFILE.duration)
    assert p.prompt_len[0] >= _PROFILE.prompt_len[0]
    assert p.prompt_len[1] <= _PROFILE.prompt_len[1]
    assert p.deadline_slack == pytest.approx(3.0, abs=0.35)
    assert p.deadline_frac == 1.0


# ---------------------------------------------------------------------------
# program spans on the profiler's clock
# ---------------------------------------------------------------------------


def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; return the host spans it emitted
    whose names look like program spans, as (start, end, name) sorted by
    start."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if e.name.startswith(("engine.", "rnn."))]
    return sorted(out)


def test_span_names_are_unique_and_plain():
    from repro.obs import SPANS

    assert len(set(SPANS)) == len(SPANS) == 9
    assert all(n.split(".")[0] in ("engine", "rnn") for n in SPANS)


def test_engine_step_emits_its_phases_in_order(setup, tmp_path):
    from repro.obs import SPANS

    eng = _engine(setup)
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()                            # compiles prefill and decode
    eng.submit([4, 5, 6, 7], max_new_tokens=3)
    spans = _profiled(tmp_path, eng.step)
    assert {n for _, _, n in spans} <= set(SPANS)
    assert [n for _, _, n in spans] == [
        "engine.step", "engine.schedule", "engine.prefill",
        "engine.launch", "engine.readback", "engine.bookkeep"]
    (s0, s1, _), (c0, c1, _), (p0, p1, _) = spans[:3]
    assert s0 <= c0 <= p0 and p1 <= c1 <= s1       # step > schedule > prefill
    phases = spans[3:]
    assert c1 <= phases[0][0]
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    assert phases[-1][1] <= s1


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_rnn_serve_emits_plan_operands_launch(cell, tmp_path):
    from repro.core.cells import RNNCellConfig, init_weights, \
        quantize_weights
    from repro.kernels.fused_rnn import ops
    from repro.obs import SPANS

    cfg = RNNCellConfig(cell, 128, timesteps=2, batch=1, precision="int8")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d),
                          jax.numpy.bfloat16)
    ops.serve(cfg, w, x, interpret=True).block_until_ready()    # compile
    spans = _profiled(tmp_path, lambda: ops.serve(
        cfg, w, x, interpret=True).block_until_ready())
    assert {n for _, _, n in spans} <= set(SPANS)
    assert [n for _, _, n in spans] == ["rnn.plan", "rnn.operands",
                                        "rnn.launch"]
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


def test_jitted_programs_have_stable_names(setup):
    eng = _engine(setup)
    assert "decode_program" in eng.lower_decode().as_text()
    assert eng._decode_many.__name__ == "decode_program"
    assert eng._prefill.__name__ == "prefill_program"
