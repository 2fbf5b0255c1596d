"""The on-device decode hot path: fused sample-in-step equivalence,
multi-tick chunks, bucketed batched prefill, and the sync/compile-count
contracts of ISSUE 3 (engine side; the model-side masking equivalence is
in test_bucketed_prefill_* below)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine, VirtualClock, drive, make_workload
from repro.serving.sampler import SamplerConfig, sample, split_and_sample
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


# --------------------------------------------------- fused sample-in-step


@pytest.mark.parametrize("sampler", [
    SamplerConfig(),                                  # greedy
    SamplerConfig(temperature=0.8, top_k=5),          # stochastic
])
def test_fused_sample_matches_host_sampler(setup, sampler):
    """The engine's on-device sampling consumes the same key stream and
    produces the same tokens as the host-side prefill/decode/sample
    sequence replayed manually with model calls + split_and_sample."""
    cfg, model, params = setup
    prompt = [5, 9, 3, 7, 2]
    eng = _engine(setup, max_batch=1, seed=11,
                  temperature=sampler.temperature, top_k=sampler.top_k)
    r = eng.submit(list(prompt), max_new_tokens=5)
    eng.run()
    assert r.done and len(r.output) == 5

    # manual replay: identical batch layout (bucketed, batch = max_batch)
    key = jax.random.PRNGKey(11)
    S = eng.bucket(len(prompt))
    toks = np.zeros((1, S), np.int32)
    toks[0, :len(prompt)] = prompt
    batch = {"tokens": jnp.asarray(toks),
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    cache, logits = model.prefill(params, batch, NOSH, max_len=32)
    key, tok = split_and_sample(key, logits, sampler)
    out = [int(tok[0])]
    for _ in range(4):
        cache, logits = model.decode_step(params, cache, tok, NOSH)
        key, tok = split_and_sample(key, logits, sampler)
        out.append(int(tok[0]))
    assert r.output == out


def test_sample_helper_matches_sample(setup):
    """split_and_sample is literally split + sample with the same key."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 17))
    for cfg in (SamplerConfig(), SamplerConfig(temperature=1.1, top_k=4)):
        key = jax.random.PRNGKey(5)
        k2, sub = jax.random.split(key)
        new_key, toks = split_and_sample(key, logits, cfg)
        assert (np.asarray(toks) == np.asarray(sample(logits, sub, cfg))).all()
        assert (np.asarray(new_key) == np.asarray(k2)).all()


# --------------------------------------------------- decode_many == k x step


def _run_closed_loop(setup, sync_every, prompts, max_new, sampler):
    eng = _engine(setup, seed=123, sync_every=sync_every,
                  temperature=sampler.temperature, top_k=sampler.top_k)
    reqs = [eng.submit(list(p), max_new_tokens=m) for p, m in
            zip(prompts, max_new)]
    eng.run()
    return ([(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done)
             for r in reqs], eng.util_history, eng.ticks)


def test_decode_many_equals_k_steps_closed_loop(setup):
    """A sync_every=8 engine produces the same tokens, tick stamps, and
    per-tick util history as sync_every=1 on a closed-loop workload: tick
    attribution inside a chunk is exact, and the chunk breaks at a freed
    slot whenever the queue is non-empty."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14],
               [15, 16, 17]]
    max_new = [6, 3, 9, 4, 7]
    sampler = SamplerConfig(temperature=0.7, top_k=7)
    a = _run_closed_loop(setup, 1, prompts, max_new, sampler)
    b = _run_closed_loop(setup, 8, prompts, max_new, sampler)
    assert a == b


def test_decode_many_equals_k_steps_open_loop(setup):
    """Under drive() on a virtual clock, arrival-bounded chunks make the
    whole schedule independent of sync_every — the fused multi-tick loop
    is a pure wall-clock optimization."""
    cfg = setup[0]

    def one(sync_every):
        eng = _engine(setup, seed=9, sync_every=sync_every)
        items = make_workload("mmpp", rate=0.4, duration=16.0, seed=4,
                              vocab_size=cfg.vocab_size, prompt_len=(2, 6),
                              max_new_tokens=(2, 8))
        reqs = drive(eng, items, VirtualClock())
        return ([(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done)
                 for r in reqs], eng.util_history, eng.stats()["mean_util"])

    assert one(1) == one(4)


def test_sync_count_bound(setup):
    """The acceptance contract: steady-state decode performs <= 1 host
    sync per sync_every ticks (plus one per prefill launch)."""
    k = 8
    eng = _engine(setup, max_batch=4, sync_every=k)
    reqs = [eng.submit([1 + i, 2, 3], max_new_tokens=24) for i in range(4)]
    eng.run()
    assert all(r.done for r in reqs)
    s = eng.stats()
    assert s["host_syncs"] <= s["prefill_calls"] + math.ceil(s["ticks"] / k)
    # all four same-bucket admissions prefilled in ONE batched call
    assert s["prefill_calls"] == 1
    assert s["decode_chunks"] == math.ceil(s["ticks"] / k)


# --------------------------------------------------- bucketed prefill


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "qwen2.5-14b", "hymba-1.5b"])
def test_bucketed_prefill_matches_sequential(arch):
    """One right-padded batched prefill == per-prompt exact-length batch-1
    prefills: logits at the true last token, cache lengths, and the next
    decode step from the scattered rows (attention masking + identity-
    masked recurrent/ssd state + ring-window cache layout)."""
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [3, 5, 9]
    S, ML = 16, 24
    toks = np.zeros((len(lens), S), np.int32)
    prompts = []
    for i, L in enumerate(lens):
        p = rng.integers(0, cfg.vocab_size, L)
        prompts.append(p)
        toks[i, :L] = p
    batch = {"tokens": jnp.asarray(toks),
             "lengths": jnp.asarray(lens, jnp.int32)}
    cacheB, logitsB = model.prefill(params, batch, NOSH, max_len=ML)
    for i, p in enumerate(prompts):
        c1, l1 = model.prefill(params, {"tokens": jnp.asarray([p], jnp.int32)},
                               NOSH, max_len=ML)
        assert int(cacheB["lengths"][i]) == len(p)
        scale = float(jnp.max(jnp.abs(l1))) + 1e-9
        rel = float(jnp.max(jnp.abs(logitsB[i] - l1[0]))) / scale
        assert rel < 2e-2, f"{arch} len={len(p)}: prefill rel err {rel}"
        # continue decoding from the padded batch's cache row
        row = {"blocks": jax.tree.map(lambda a: a[:, i:i + 1],
                                      cacheB["blocks"]),
               "lengths": cacheB["lengths"][i:i + 1]}
        t = jnp.argmax(l1, axis=-1).astype(jnp.int32)
        _, dB = model.decode_step(params, row, t, NOSH)
        _, d1 = model.decode_step(params, c1, t, NOSH)
        rel = float(jnp.max(jnp.abs(dB - d1))) / scale
        assert rel < 2e-2, f"{arch} len={len(p)}: decode rel err {rel}"


def test_engine_bucketed_matches_batch1(setup):
    """End-to-end: the bucketed engine serves a mixed-length greedy
    workload with the same outputs and stamps as the legacy exact-length
    batch-1 admission path."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12],
               [13, 14, 15, 16, 17, 18]]

    def serve(bucketed):
        eng = _engine(setup, bucketed_prefill=bucketed)
        reqs = [eng.submit(list(p), max_new_tokens=4) for p in prompts]
        eng.run()
        return [(r.output, r.t_admit, r.t_done) for r in reqs]

    assert serve(True) == serve(False)


def test_prefill_recompile_bound(setup):
    """Mixed-length arrivals trigger at most n_buckets prefill compiles in
    bucketed mode; the legacy path compiles per distinct length."""
    cfg = setup[0]
    rng = np.random.default_rng(7)
    lengths = [int(rng.integers(2, 21)) for _ in range(12)]

    def serve(bucketed):
        eng = _engine(setup, max_len=32, bucketed_prefill=bucketed)
        for L in lengths:
            eng.submit([int(x) for x in rng.integers(0, cfg.vocab_size, L)],
                       max_new_tokens=2)
            eng.step()   # interleave admits so groups vary
        eng.run()
        return eng

    eng = serve(True)
    # max_len=32 -> buckets (8, 16, 31)
    assert eng.bucket_lengths == [8, 16, 31]
    assert eng.stats()["prefill_compiles"] <= len(eng.bucket_lengths)
    cache_size = getattr(eng._prefill, "_cache_size", None)
    if cache_size is not None:   # cross-check against the real jit cache
        assert cache_size() <= len(eng.bucket_lengths)
    legacy = serve(False)
    assert legacy.stats()["prefill_compiles"] == len(set(lengths))


def test_overlap_prefill_schedule_identical_fewer_syncs(setup):
    """Overlapped admission (prefill + first-token sample + slot scatter +
    decode chunk dispatched with no host sync in between) produces the
    bit-identical schedule of the synchronous path — same tokens, stamps,
    util — while performing strictly fewer blocking readbacks."""
    cfg = setup[0]

    def serve(overlap):
        eng = _engine(setup, max_batch=4, seed=3, overlap_prefill=overlap,
                      temperature=0.9, top_k=6)
        items = make_workload("poisson", rate=0.9, duration=24.0, seed=5,
                              vocab_size=cfg.vocab_size, prompt_len=(2, 14),
                              max_new_tokens=(2, 8))
        reqs = drive(eng, items, VirtualClock())
        sched = [(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done)
                 for r in reqs]
        return sched, eng.util_history, eng.stats()

    sched_o, util_o, stats_o = serve(True)
    sched_s, util_s, stats_s = serve(False)
    assert sched_o == sched_s
    assert util_o == util_s
    assert stats_o["prefill_calls"] == stats_s["prefill_calls"]
    assert stats_o["host_syncs"] < stats_s["host_syncs"]
    # sync path blocks once per prefill launch on top of the chunk syncs
    assert (stats_s["host_syncs"] - stats_o["host_syncs"]
            == stats_s["prefill_calls"])


def test_overlap_falls_back_for_instant_finish_rounds(setup):
    """Admission rounds that may retire at the prefill token (eos_id set,
    or max_new_tokens == 1) take the synchronous path so instant admits
    still free slots for same-tick retries; outputs are unaffected."""
    eng = _engine(setup, max_batch=1)
    reqs = [eng.submit([1, 2, 3 + i], max_new_tokens=1) for i in range(3)]
    eng.run()
    assert all(r.done and len(r.output) == 1 for r in reqs)
    assert eng.stats()["instant_admits"] == 3
    assert [r.t_done for r in reqs] == [0, 0, 0]   # same-tick slot reuse


def test_spf_policy_admits_shortest_first(setup):
    """policy='spf' admits the shortest queued prompt when a slot frees;
    FCFS admits in arrival order."""
    long1, long2, short = [1] * 10, [2] * 8, [3, 4]

    def order(policy):
        eng = _engine(setup, max_batch=1, policy=policy)
        a = eng.submit(list(long1), max_new_tokens=3)   # occupies the slot
        b = eng.submit(list(long2), max_new_tokens=3)   # queued first
        c = eng.submit(list(short), max_new_tokens=3)   # queued second
        eng.run()
        assert all(r.done for r in (a, b, c))
        return (b.t_admit, c.t_admit)

    b_f, c_f = order("fcfs")
    assert b_f < c_f                  # arrival order
    b_s, c_s = order("spf")
    assert c_s < b_s                  # shortest first

    with pytest.raises(ValueError, match="policy"):
        _engine(setup, policy="lifo")
