"""Property harness for the paged slot-state manager (PR 7).

The contract under test: :class:`repro.serving.paged.PagedSlotManager` is
a drop-in replacement for the dense :class:`SlotManager` — under ANY
interleaving of grant / release / preempt / resume / snapshot_many /
decode ops, the paged engine produces bit-identical schedules, logits
(via the tokens they argmax to), and live cache state, while its block
pools keep their accounting invariants (no leak, no double-allocation,
free-count conservation) after every operation.

Driven through the hypothesis stub (tests/conftest.py installs it when
the real package is absent): each property replays over deterministic
pseudo-random seeds, and a failing seed is reproducible from the
assertion traceback.  Three architectures pin the three cache families:
rwkv6 (pure recurrent — no pools at all), qwen2.5 (dense attention — KV
rings page), hymba (hybrid attention + SSM + conv — paged rings next to
per-slot state).
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.serving.paged import BlockPool, PagedSlotManager, \
    canonicalize_cache
from repro.serving.slotstate import SlotManager, gather_slots, \
    make_slot_manager
from repro.testing import reduced_config

ARCHS = ("rwkv6-1.6b", "qwen2.5-14b", "hymba-1.5b")
MAX_LEN = 32
BLOCK = 8


def _engine(model, params, sharder, *, seed, **knobs):
    plan = ServingPlan(arch=model.cfg.name, max_len=MAX_LEN, **knobs)
    return ServingEngine.from_plan(plan, params, model=model,
                                   sharder=sharder, seed=seed)


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduced_config(arch)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[arch] = (cfg, model, params, Sharder(None, {}))
        return cache[arch]

    return get


def _assert_trees_equal(a, b, what: str) -> None:
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree_util.tree_leaves_with_path(b)
    assert len(fa) == len(fb), f"{what}: leaf count differs"
    for (pa, la), (pb, lb) in zip(fa, fb):
        assert pa == pb, f"{what}: leaf order differs ({pa} vs {pb})"
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{what}: leaf {jax.tree_util.keystr(pa)} differs")


def _occupied_columns(engine):
    """The live (occupied-slot) cache columns, canonicalized: masked ring
    entries zeroed so dense and paged — which legitimately differ only in
    masked garbage — compare bit-equal iff their live state does."""
    occ = engine.sm.occupied()
    if not occ:
        return occ, None
    cols = jax.device_get(gather_slots(engine.sm.cache, engine.sm.axes, occ))
    return occ, canonicalize_cache(cols)


def _compare_engines(dense, paged, what: str) -> None:
    assert dense.sm.occupied() == paged.sm.occupied(), \
        f"{what}: occupancy diverged"
    occ_d, cols_d = _occupied_columns(dense)
    occ_p, cols_p = _occupied_columns(paged)
    if cols_d is not None:
        _assert_trees_equal(cols_d, cols_p, what)
    np.testing.assert_array_equal(dense.sm.next_token, paged.sm.next_token,
                                  err_msg=f"{what}: next_token mirrors")
    paged.sm.check_invariants()


def _lockstep(built, arch: str, seed: int, *, n_ops: int = 24,
              max_batch: int = 3) -> None:
    """Drive a dense and a paged engine through one identical random op
    script, comparing live state after every op and pool invariants after
    every op; then drain both and compare the complete schedules."""
    cfg, model, params, sharder = built(arch)
    rng = np.random.default_rng(seed)

    def make(layout):
        return _engine(model, params, sharder, seed=11,
                       max_batch=max_batch, cache_layout=layout)

    dense, paged = make("dense"), make(f"paged:{BLOCK}")
    reqs_d, reqs_p = [], []
    for op_i in range(n_ops):
        op = rng.choice(("submit", "step", "step", "preempt"))
        if op == "submit":
            n = int(rng.integers(1, 13))
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
            max_new = int(rng.integers(1, 7))
            reqs_d.append(dense.submit(list(prompt), max_new_tokens=max_new))
            reqs_p.append(paged.submit(list(prompt), max_new_tokens=max_new))
        elif op == "step":
            dense.step()
            paged.step()
        else:
            occ = dense.sm.occupied()
            k = int(rng.integers(0, len(occ) + 1))
            victims = [int(s) for s in rng.choice(occ, size=k,
                                                  replace=False)] if k else []
            dense.preempt_many(list(victims))
            paged.preempt_many(list(victims))
        _compare_engines(dense, paged, f"{arch} seed={seed} op[{op_i}]={op}")
    dense.run()
    paged.run()
    _compare_engines(dense, paged, f"{arch} seed={seed} drained")
    sched_d = [(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done,
                r.n_preempts) for r in reqs_d]
    sched_p = [(r.output, r.t_submit, r.t_admit, r.t_first, r.t_done,
                r.n_preempts) for r in reqs_p]
    assert sched_d == sched_p, f"{arch} seed={seed}: schedules diverged"
    assert dense.stats() == paged.stats(), \
        f"{arch} seed={seed}: stats diverged"


@pytest.mark.parametrize("arch", ARCHS)
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_random_interleavings_bit_exact(built, arch, seed):
    """THE property: any grant/release/preempt/resume/decode interleaving
    leaves dense and paged engines bit-identical in schedule, live cache
    columns, and stats, with clean pool invariants throughout."""
    _lockstep(built, arch, seed)


# ---------------------------------------------------------------------------
# Manager-level edges: snapshot_many / restore / grant / release.
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, prompt_len=4, max_new_tokens=4):
        self.prompt = [1] * prompt_len
        self.output = []
        self.max_new_tokens = max_new_tokens
        self.eos_id = None


LAYOUTS = ("dense", f"paged:{BLOCK}")


def _manager(built, arch, layout, max_batch=3):
    _, model, _, _ = built(arch)
    return make_slot_manager(model, max_batch, MAX_LEN, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS, ids=("dense", "paged"))
def test_layout_factory(built, layout):
    sm = _manager(built, "rwkv6-1.6b", layout)
    assert isinstance(sm, PagedSlotManager) == (layout != "dense")
    assert isinstance(sm, SlotManager)


@pytest.mark.parametrize("layout", LAYOUTS, ids=("dense", "paged"))
def test_snapshot_many_empty_is_noop(built, layout):
    sm = _manager(built, "qwen2.5-14b", layout)
    assert sm.snapshot_many([]) == []
    assert sm.metrics["slots.snapshots"].value == 0


@pytest.mark.parametrize("layout", LAYOUTS, ids=("dense", "paged"))
def test_snapshot_many_rejects_duplicates_and_unoccupied(built, layout):
    sm = _manager(built, "qwen2.5-14b", layout)
    sm.grant(0, _FakeReq(), next_token=5)
    with pytest.raises(ValueError, match="duplicate"):
        sm.snapshot_many([0, 0])
    with pytest.raises(ValueError, match="unoccupied"):
        sm.snapshot_many([0, 1])


@pytest.mark.parametrize("layout", LAYOUTS, ids=("dense", "paged"))
def test_grant_release_restore_occupancy_errors(built, layout):
    sm = _manager(built, "qwen2.5-14b", layout)
    req = _FakeReq()
    sm.grant(1, req, next_token=5)
    with pytest.raises(ValueError, match="occupied"):
        sm.grant(1, _FakeReq(), next_token=6)
    (snap,) = sm.snapshot_many([1])
    with pytest.raises(ValueError, match="occupied"):
        sm.restore(1, snap, req)
    sm.release(1)
    with pytest.raises(ValueError, match="already-free"):
        sm.release(1)
    sm.restore(1, snap, req)          # free again: restore is legal now
    assert sm.occupied() == [1]


@pytest.mark.parametrize("arch", ("qwen2.5-14b", "hymba-1.5b"))
def test_restore_into_different_slot_cross_layout(built, arch):
    """A dense snapshot restored into a *different-index* slot of a paged
    manager (and vice versa) carries bit-identical live state: snapshots
    are layout- and slot-portable."""
    cfg, model, params, sharder = built(arch)

    def run_and_snap(layout):
        eng = _engine(model, params, sharder, seed=3, max_batch=3,
                      cache_layout=layout)
        req = eng.submit([7, 3, 9, 2, 8], max_new_tokens=8)
        for _ in range(3):
            eng.step()
        (snap,) = eng.sm.snapshot_many([0])
        eng.sm.release(0)
        return eng, req, snap

    eng_d, req_d, snap_d = run_and_snap("dense")
    eng_p, req_p, snap_p = run_and_snap(f"paged:{BLOCK}")
    # cross-restore, each into a different free slot index
    eng_p.sm.restore(2, snap_d, req_p)
    eng_d.sm.restore(1, snap_p, req_d)
    eng_p.sm.check_invariants()
    col_d = canonicalize_cache(jax.device_get(
        gather_slots(eng_d.sm.cache, eng_d.sm.axes, [1])))
    col_p = canonicalize_cache(jax.device_get(
        gather_slots(eng_p.sm.cache, eng_p.sm.axes, [2])))
    _assert_trees_equal(col_d, col_p, f"{arch} cross-layout restore")


@pytest.mark.parametrize("layout", LAYOUTS, ids=("dense", "paged"))
def test_snapshot_restore_roundtrip_bit_exact(built, layout):
    """snapshot -> release -> restore into another slot leaves the live
    column bit-identical to the original (same manager, either layout)."""
    cfg, model, params, sharder = built("hymba-1.5b")
    eng = _engine(model, params, sharder, seed=5, max_batch=3,
                  cache_layout=layout)
    req = eng.submit([4, 8, 15, 16, 23, 42], max_new_tokens=8)
    for _ in range(4):
        eng.step()
    before = canonicalize_cache(jax.device_get(
        gather_slots(eng.sm.cache, eng.sm.axes, [0])))
    (snap,) = eng.sm.snapshot_many([0])
    eng.sm.release(0)
    eng.sm.restore(2, snap, req)
    after = canonicalize_cache(jax.device_get(
        gather_slots(eng.sm.cache, eng.sm.axes, [2])))
    _assert_trees_equal(before, after, f"{layout} roundtrip")


# ---------------------------------------------------------------------------
# BlockPool unit invariants.
# ---------------------------------------------------------------------------


def test_block_pool_cover_release_conservation():
    pool = BlockPool(ring_len=32, block_size=8, max_batch=3)
    assert pool.n_pages == 4 and pool.capacity == 13
    assert pool.cover(0, 9)           # 2 pages
    assert not pool.cover(0, 9)       # idempotent: no change
    assert not pool.cover(0, 3)       # never shrinks
    assert pool.cover(1, 32)          # full ring
    assert not pool.cover(1, 500)     # capped at the ring
    pool.check(occupied=[0, 1])
    assert len(pool.free_list) == 12 - 2 - 4
    freed = pool.release(0)
    assert len(freed) == 2 and pool.release(0) == []   # second release: noop
    pool.check(occupied=[1])
    assert len(pool.free_list) == 12 - 4
    pool.release(1)
    pool.check(occupied=[])
    assert pool.free_list == list(range(1, 13))        # full conservation


def test_block_pool_flat_index_routes_through_table():
    pool = BlockPool(ring_len=8, block_size=4, max_batch=2)
    pool.cover(1, 8)                   # slot 1 gets blocks, slot 0 none
    idx = pool.flat_index().reshape(2, 8)
    # slot 0 is unallocated: every position routes to the null block
    assert set(idx[0] // pool.block) == {0}
    # slot 1: positions map contiguously through its two allocated blocks
    b0, b1 = pool.table[1, 0], pool.table[1, 1]
    np.testing.assert_array_equal(
        idx[1], np.r_[b0 * 4 + np.arange(4), b1 * 4 + np.arange(4)])


@settings(max_examples=10, deadline=None)
@given(block=st.integers(min_value=1, max_value=40),
       ring=st.sampled_from((8, 24, 32)),
       seed=st.integers(min_value=0, max_value=10**6))
def test_block_pool_random_ops_keep_invariants(block, ring, seed):
    """Random cover/release sequences never leak, double-allocate, or
    break free-count conservation, at any block size (including blocks
    larger than the ring, which clamp)."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(ring_len=ring, block_size=block, max_batch=4)
    occupied = set()
    for _ in range(50):
        slot = int(rng.integers(0, 4))
        if rng.random() < 0.65:
            pool.cover(slot, int(rng.integers(0, 2 * ring)))
            occupied.add(slot)
        else:
            pool.release(slot)
            occupied.discard(slot)
        pool.check(occupied=sorted(occupied))


# ---------------------------------------------------------------------------
# Fragmentation gauges: the memory claim behind the layout.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ("qwen2.5-14b", "hymba-1.5b"))
def test_paged_bytes_resident_never_exceeds_dense(built, arch):
    cfg, model, params, sharder = built(arch)

    def engines():
        for lay in LAYOUTS:
            yield _engine(model, params, sharder, seed=2, max_batch=3,
                          cache_layout=lay)

    dense, paged = engines()
    assert paged.sm.bytes_resident() <= dense.sm.bytes_resident()
    for i in range(3):
        prompt = [1 + i, 2, 3]
        dense.submit(list(prompt), max_new_tokens=6)
        paged.submit(list(prompt), max_new_tokens=6)
    while dense.step() | paged.step():
        assert paged.sm.bytes_resident() <= dense.sm.bytes_resident()
        assert paged.sm.padding_waste() <= dense.sm.padding_waste()
    # drained: paged drops to its floor (null blocks + tables only)
    assert paged.sm.tokens_in_flight() == 0
    assert paged.sm.blocks_free() == sum(
        p.capacity - 1 for p in paged.sm._pools.values())


# ---------------------------------------------------------------------------
# Fault interleavings (PR 8): inject/quarantine/retry under dense vs paged.
# The recovery layer (numeric guard, scrub, rollback, watchdog) routes
# through gather/scatter/release — exactly the ops the paged pools remap —
# so any fault interleaving must leave the two layouts bit-identical in
# surviving columns and outputs, with clean pool invariants throughout.
# ---------------------------------------------------------------------------


def _fault_lockstep(built, arch: str, seed: int, *, n_ops: int = 20,
                    max_batch: int = 3) -> None:
    from repro.serving import FaultInjector, FaultPlan, FaultSpec

    cfg, model, params, sharder = built(arch)
    rng = np.random.default_rng(seed)
    kinds = ("poison_slot", "stall_slot", "drop_readback", "fail_prefill")
    fplan = FaultPlan(tuple(
        FaultSpec(kind=kinds[int(rng.integers(0, len(kinds)))],
                  tick=int(rng.integers(1, n_ops)),
                  slot=int(rng.integers(0, max_batch)),
                  mode=("nan", "garbage")[int(rng.integers(0, 2))],
                  seed=seed + j)
        for j in range(3)))

    def make(layout):
        eng = _engine(model, params, sharder, seed=11, max_batch=max_batch,
                      cache_layout=layout, retry_budget=2, watchdog_ticks=3)
        eng.attach_injector(FaultInjector(fplan))   # per-engine ledger
        return eng

    dense, paged = make("dense"), make(f"paged:{BLOCK}")
    reqs_d, reqs_p = [], []
    for op_i in range(n_ops):
        op = rng.choice(("submit", "step", "step"))
        if op == "submit":
            n = int(rng.integers(1, 13))
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
            max_new = int(rng.integers(1, 7))
            reqs_d.append(dense.submit(list(prompt), max_new_tokens=max_new))
            reqs_p.append(paged.submit(list(prompt), max_new_tokens=max_new))
        else:
            dense.step()
            paged.step()
        _compare_engines(dense, paged,
                         f"{arch} seed={seed} op[{op_i}]={op}")
    dense.run()
    paged.run()
    _compare_engines(dense, paged, f"{arch} seed={seed} drained")
    out_d = [(r.output, r.done, r.shed, r.retries) for r in reqs_d]
    out_p = [(r.output, r.done, r.shed, r.retries) for r in reqs_p]
    assert out_d == out_p, f"{arch} seed={seed}: fault outcomes diverged"
    assert dense.fault_stats() == paged.fault_stats(), \
        f"{arch} seed={seed}: fault stats diverged"
    assert dense.recovery.fault_events == paged.recovery.fault_events, \
        f"{arch} seed={seed}: fault events diverged"


@pytest.mark.parametrize("arch", ARCHS)
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_fault_interleavings_bit_exact(built, arch, seed):
    """Inject/quarantine/retry under any interleaving: dense and paged
    engines agree bit-for-bit on surviving cache columns, outputs,
    retries, shed set, fault events, and fault counters — and the paged
    pools keep their invariants through scrub/release recovery."""
    _fault_lockstep(built, arch, seed)
