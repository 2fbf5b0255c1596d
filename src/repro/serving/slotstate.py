"""Unified slot-state manager: the serving cache as an addressable store.

The engine's serving state is a cache pytree (stacked-period KV rings,
rwkv ``wkv``/shift states, ssd/conv states, per-slot ``lengths``) plus
host-side per-slot control vectors (next token, active mask, EOS id,
remaining budget).  Pre-refactor this knowledge was smeared through
``ServingEngine`` and only flowed one way (prefill rows scattered *into*
slots).  :class:`SlotManager` centralizes it behind a symmetric
gather/scatter API keyed on the batch-axis contract that
:meth:`repro.models.lm.LM.cache_batch_axes` declares for every cache
leaf — no layer-kind special cases, so any architecture the LM wrapper
serves is preemptable for free.

The symmetric half is what enables preemption: :meth:`snapshot` gathers
one slot's full device state into a host :class:`SlotSnapshot` (a single
``device_get``), and :meth:`restore` scatters it back into *any* free
slot later.  The round trip is bit-exact — device→host→device copies
preserve every dtype's bits, KV ring positions are absolute (slot-
independent), and recurrent states carry no slot identity — so under
greedy decoding an evicted request resumes the exact token trajectory it
would have produced uninterrupted, wherever and whenever it lands
(property-tested across rwkv/dense/hymba in ``tests/test_preemption.py``).
Stochastic sampling consumes one engine-global PRNG key per batch tick,
so there the guarantee is schedule-relative: the trajectory is unchanged
iff the request decodes in the same slot on the same ticks (e.g. an
evict + next-step resume into the same slot is a provable no-op; a
delayed or cross-slot resume re-rolls the randomness, which is sampling
noise, not state corruption).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import LM
from repro.obs.registry import MetricsRegistry


def _index(a, ax: int, idx):
    ix = [slice(None)] * a.ndim
    ix[ax] = idx
    return tuple(ix)


def gather_slots(cache, axes, slots: Sequence[int]):
    """Gather the given slot columns out of every cache leaf (device op).

    ``axes`` is the leaf→batch-axis pytree from ``LM.cache_batch_axes``;
    the result keeps a slot axis of size ``len(slots)`` in every leaf, so
    it scatters back with :func:`scatter_slots` unchanged."""
    idx = jnp.asarray(list(slots), jnp.int32)
    return jax.tree.map(lambda a, ax: jnp.take(a, idx, axis=ax),
                        cache, axes)


def _scatter_rows(cache, sub, slots, *, axes):
    """Row ``r`` of ``sub`` into slot ``slots[r]`` of ``cache``, every
    leaf at once; a slot index past the last slot drops its row."""
    return jax.tree.map(
        lambda a, s, ax: a.at[_index(a, ax, slots)].set(
            s.astype(a.dtype), mode="drop"),
        cache, sub, axes)


def scatter_slots(cache, axes, slots: Sequence[int], sub):
    """Scatter slot columns (one per entry of ``slots``) into the cache —
    the inverse of :func:`gather_slots`; one pytree op for the group."""
    idx = jnp.asarray(list(slots), jnp.int32)
    return jax.tree.map(
        lambda a, s, ax: a.at[_index(a, ax, idx)].set(
            jnp.asarray(s).astype(a.dtype)),
        cache, sub, axes)


@dataclasses.dataclass
class SlotSnapshot:
    """One slot's complete decode state, on host.

    ``cache_col`` is the host copy of every cache leaf's slot column
    (slot axis kept, size 1); ``next_token`` is the last sampled token —
    the decode input the slot would have consumed next.  Together with
    the request's own host state (``output``, ``max_new_tokens``,
    ``eos_id``) this is everything needed to resume bit-exactly."""

    cache_col: Any
    next_token: int

    def nbytes(self) -> int:
        return int(sum(np.asarray(leaf).nbytes
                       for leaf in jax.tree.leaves(self.cache_col)))


class SlotManager:
    """Owns the decode-slot state: cache pytree + host control mirrors.

    The engine asks it *where* things go (free/occupied slots), moves
    state through it (prefill insertion, snapshot/restore, post-chunk
    refresh), and never touches the cache layout directly.  Policy — who
    gets a slot — stays in :mod:`repro.serving.scheduler`."""

    def __init__(self, model: LM, max_batch: int, max_len: int,
                 registry: Optional[MetricsRegistry] = None,
                 sharder=None):
        self.max_batch = max_batch
        self.max_len = max_len
        # a mesh sharder lays the cache out as the decode program reads it
        # (KV rings split along their length); None keeps one device
        self._cache_shardings = (
            sharder.param_shardings(model.cache_specs(max_batch, max_len))
            if sharder is not None and sharder.mesh is not None else None)
        self._init_storage(model, max_batch, max_len)
        # prefill rows scatter in place: one program per prefill row count
        self._insert_rows = jax.jit(
            functools.partial(_scatter_rows, axes=self.axes),
            donate_argnums=0)
        self._init_byte_accounting(model)
        self._init_col_specs(model)
        self.slots: List[Optional[object]] = [None] * max_batch
        # host mirrors of the per-slot device control vectors
        self.next_token = np.zeros((max_batch,), np.int32)
        self.active = np.zeros((max_batch,), bool)
        self.eos = np.full((max_batch,), -1, np.int32)
        self.remaining = np.zeros((max_batch,), np.int32)
        # telemetry: shared with the engine's registry when passed in,
        # so engine.reset_telemetry() covers slot counters too
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._snapshots = self.metrics.counter(
            "slots.snapshots", "slot columns gathered to host (evictions)")
        self._restores = self.metrics.counter(
            "slots.restores", "snapshots scattered back into slots")
        self._snapshot_bytes = self.metrics.counter(
            "slots.snapshot_bytes", "host bytes held by eviction snapshots")
        self._prefill_inserts = self.metrics.counter(
            "slots.prefill_inserts", "prefill rows scattered into slots")
        self.metrics.gauge("slots.active", "occupied decode slots",
                           fn=lambda: float(self.n_active()))
        self.metrics.gauge("slots.free", "free decode slots",
                           fn=lambda: float(self.max_batch - self.n_active()))
        # fragmentation gauges — shared names across dense/paged layouts so
        # benchmarks sample one vocabulary; dense semantics: the whole
        # cache is committed up front, so bytes_resident is constant and
        # the waste is everything not covered by live tokens
        self.metrics.gauge(
            "slots.blocks_free", "free cache-pool blocks (0 under dense)",
            fn=lambda: float(self.blocks_free()))
        self.metrics.gauge(
            "slots.bytes_resident", "cache bytes committed to slot state",
            fn=lambda: float(self.bytes_resident()))
        self.metrics.gauge(
            "slots.padding_waste",
            "committed cache bytes not backing live tokens",
            fn=lambda: float(self.padding_waste()))

    # ----------------------------------------------------------- storage seam
    def _init_storage(self, model: LM, max_batch: int, max_len: int) -> None:
        """Allocate the backing store.  The dense layout owns the cache
        pytree directly; :class:`repro.serving.paged.PagedSlotManager`
        overrides this to build block pools instead and serves ``cache``
        as a materialized view property."""
        self.cache = model.init_cache(max_batch, max_len,
                                      self._cache_shardings)
        self.axes = model.cache_batch_axes(self.cache)
        self.page_axes = model.cache_page_axes(self.cache)

    def ensure_chunk(self, budget: int) -> None:
        """Hook called by the engine before each decode chunk of up to
        ``budget`` ticks.  Dense layout: no-op (every slot's full column
        is pre-committed).  Paged layout: extends each active slot's block
        table to cover the chunk's ring writes."""

    # -------------------------------------------------------- byte accounting
    def _init_byte_accounting(self, model: LM) -> None:
        """Precompute per-token / per-slot byte factors from the dense
        leaf shapes: pageable leaves (KV rings) group by ring length S
        (local-window rings saturate before full-length ones), everything
        else is per-slot state.  Both layouts share these factors, so the
        dense and paged fragmentation gauges are directly comparable."""
        paxes = {tuple(p): ax for p, ax in jax.tree_util.tree_leaves_with_path(
            self.page_axes, is_leaf=lambda x: x is None)}
        self._ring_token_bytes: Dict[int, int] = {}   # ring length S -> bytes
        self._per_slot_bytes = 0
        self._dense_cache_bytes = 0
        for path, spec in jax.tree_util.tree_leaves_with_path(
                model.cache_specs(self.max_batch, self.max_len)):
            nbytes = spec.nbytes
            self._dense_cache_bytes += nbytes
            lax_ = paxes[tuple(path)]
            if lax_ is None:
                self._per_slot_bytes += nbytes // self.max_batch
            else:
                s = int(spec.shape[lax_])
                per_tok = nbytes // (self.max_batch * s)
                self._ring_token_bytes[s] = (
                    self._ring_token_bytes.get(s, 0) + per_tok)

    # ------------------------------------------------- snapshot compatibility
    def _init_col_specs(self, model: LM) -> None:
        """Precompute the expected per-slot snapshot column spec — leaf
        path → (shape with the slot axis collapsed to 1, dtype) — from the
        model's cache specs.  This is the compatibility contract a
        :class:`SlotSnapshot` must meet to be restorable here; it is
        independent of ``max_batch`` (the slot axis is normalized away)
        but pins architecture, ``max_len`` (ring lengths) and cache
        dtypes.  Shared by both layouts: the paged manager snapshots and
        restores through the same dense-view columns."""
        ax_by_path = {tuple(p): ax for p, ax in
                      jax.tree_util.tree_leaves_with_path(self.axes)}
        self._col_specs: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        for path, spec in jax.tree_util.tree_leaves_with_path(
                model.cache_specs(self.max_batch, self.max_len)):
            ax = ax_by_path[tuple(path)]
            shape = list(spec.shape)
            shape[ax] = 1
            self._col_specs[jax.tree_util.keystr(path)] = (
                tuple(shape), str(np.dtype(spec.dtype)))

    def snapshot_compat_errors(self, snap: SlotSnapshot) -> List[str]:
        """Field-naming compatibility report for restoring ``snap`` into
        this manager.  Empty list ⇒ compatible.  Each entry names the
        offending cache leaf (pytree path) and how it diverges — missing
        leaf, extra leaf, shape or dtype mismatch — so a cross-engine
        transit between engines whose arch/max_len/cache spec differ
        fails with a readable diagnosis instead of a deep scatter error."""
        got: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(snap.cache_col):
            a = np.asarray(leaf)
            got[jax.tree_util.keystr(path)] = (tuple(a.shape), str(a.dtype))
        want = self._col_specs
        errs: List[str] = []
        for name in sorted(set(want) - set(got)):
            errs.append(f"{name}: required by this engine's cache spec but "
                        f"missing from the snapshot (different architecture?)")
        for name in sorted(set(got) - set(want)):
            errs.append(f"{name}: present in the snapshot but not in this "
                        f"engine's cache spec (different architecture?)")
        for name in sorted(set(want) & set(got)):
            w_shape, w_dtype = want[name]
            g_shape, g_dtype = got[name]
            if g_shape != w_shape:
                errs.append(
                    f"{name}: slot-column shape {g_shape} != expected "
                    f"{w_shape} (origin engine's arch/max_len differs)")
            elif g_dtype != w_dtype:
                errs.append(f"{name}: dtype {g_dtype} != expected {w_dtype}")
        return errs

    def check_snapshot_compat(self, snap: SlotSnapshot) -> None:
        """Raise ``ValueError`` naming every incompatible cache leaf if
        ``snap`` cannot be restored into this manager.  The router calls
        this before every cross-engine transit; :meth:`restore` calls it
        unconditionally so a bad hand-off can never reach the scatter."""
        errs = self.snapshot_compat_errors(snap)
        if errs:
            raise ValueError(
                "snapshot incompatible with this engine's cache spec "
                f"({len(errs)} field(s)):\n  - " + "\n  - ".join(errs))

    def _slot_tokens(self, slot: int) -> int:
        """Host-side estimate of a slot's current sequence length (prompt
        + generated so far) — gauge precision, not scheduling truth."""
        req = self.slots[slot]
        if req is None:
            return 0
        return min(self.max_len, len(req.prompt) + len(req.output))

    def useful_bytes(self) -> int:
        """Bytes actually backing live tokens/state of occupied slots."""
        total = 0
        for slot in self.occupied():
            toks = self._slot_tokens(slot)
            total += self._per_slot_bytes
            total += sum(min(s, toks) * tok_b
                         for s, tok_b in self._ring_token_bytes.items())
        return total

    def tokens_in_flight(self) -> int:
        """Total sequence tokens currently resident across occupied slots."""
        return sum(self._slot_tokens(s) for s in self.occupied())

    # fragmentation gauge backends (paged overrides all three)
    def blocks_free(self) -> int:
        return 0

    def bytes_resident(self) -> int:
        return self._dense_cache_bytes

    def padding_waste(self) -> int:
        return self.bytes_resident() - self.useful_bytes()

    # ------------------------------------------------------------ occupancy
    def free(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def occupied(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    def running(self) -> List[Tuple[int, object]]:
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    # ------------------------------------------------------------- grant/free
    def grant(self, slot: int, req, next_token: Optional[int]) -> None:
        """Mark a slot occupied by ``req``.  ``next_token`` may be None
        when the first token is still on device (overlapped admission);
        the post-chunk refresh fills the host mirror."""
        if self.slots[slot] is not None:
            raise ValueError(f"grant into occupied slot {slot}")
        self.slots[slot] = req
        self.active[slot] = True
        self.eos[slot] = -1 if req.eos_id is None else req.eos_id
        self.remaining[slot] = req.max_new_tokens - len(req.output) - (
            1 if next_token is None else 0)
        if next_token is not None:
            self.next_token[slot] = next_token

    def release(self, slot: int) -> None:
        if self.slots[slot] is None:
            raise ValueError(f"release of already-free slot {slot}")
        self.slots[slot] = None
        self.active[slot] = False

    # ------------------------------------------------------- prefill insert
    def insert_from_prefill(self, slots: Sequence[int], rows: Sequence[int],
                            cacheN) -> None:
        """Scatter prefill-cache rows into engine slots (one pytree op for
        the whole admitted group): the write half of the gather/scatter
        pair, with the prefill batch rows as the source columns."""
        self._prefill_inserts.inc(len(list(slots)))
        # every row of cacheN scatters; rows no slot was granted to aim
        # past the last slot and drop, so the program depends only on the
        # prefill's row count, not on how many requests it admitted
        target = np.full((int(cacheN["lengths"].shape[0]),), self.max_batch,
                         np.int32)
        target[list(rows)] = list(slots)
        self.cache = self._insert_rows(self.cache, cacheN,
                                       jnp.asarray(target))

    # ------------------------------------------------------ preempt / resume
    def snapshot(self, slot: int) -> SlotSnapshot:
        """Gather one slot's device state to host (one blocking
        ``device_get``) — the evict-to-host half of preemption."""
        return self.snapshot_many([slot])[0]

    def snapshot_many(self, slots: Sequence[int]) -> List[SlotSnapshot]:
        """Batched eviction gather: one ``gather_slots`` + one blocking
        ``device_get`` for all N victim columns, split into per-slot
        snapshots on host.  Bit-identical to N sequential
        :meth:`snapshot` calls (``jnp.take`` then a host ``np.take`` per
        slot preserves every leaf's bytes), at one device round-trip
        instead of N — a preemption burst costs one host sync.

        An empty victim list is a no-op (no device round-trip); duplicate
        or unoccupied victims are rejected — a duplicate would otherwise
        snapshot one slot twice and double-requeue its request."""
        slots = list(slots)
        if not slots:
            return []
        if len(set(slots)) != len(slots):
            raise ValueError(f"duplicate slots in snapshot_many: {slots}")
        for s in slots:
            if self.slots[s] is None:
                raise ValueError(f"snapshot of unoccupied slot {s}")
        cols = jax.device_get(gather_slots(self.cache, self.axes,
                                           list(slots)))
        out = []
        for k, slot in enumerate(slots):
            col = jax.tree.map(lambda a, ax, k=k: np.take(a, [k], axis=ax),
                               cols, self.axes)
            snap = SlotSnapshot(cache_col=col,
                                next_token=int(self.next_token[slot]))
            self._snapshots.inc()
            self._snapshot_bytes.inc(snap.nbytes())
            out.append(snap)
        return out

    def restore(self, slot: int, snap: SlotSnapshot, req) -> None:
        """Scatter a snapshot into a (not necessarily the same) free slot
        and re-arm the control mirrors — the resume half.  No model call,
        no sampler-key consumption: the request decodes its next tick as
        if it had never left."""
        if self.slots[slot] is not None:
            raise ValueError(f"restore into occupied slot {slot}")
        self.check_snapshot_compat(snap)
        self._restores.inc()
        self.cache = scatter_slots(self.cache, self.axes, [slot],
                                   snap.cache_col)
        self.slots[slot] = req
        self.active[slot] = True
        self.eos[slot] = -1 if req.eos_id is None else req.eos_id
        self.remaining[slot] = req.max_new_tokens - len(req.output)
        self.next_token[slot] = snap.next_token

    def scrub(self, slots: Sequence[int]) -> None:
        """Zero-wipe slot columns (fault quarantine): no poisoned value
        survives for the guard scan or the slot's next tenant.  Device-only
        (no host sync); works under both layouts — the paged manager's
        ``cache`` setter re-pages the wiped view and re-heals its null
        block, and the subsequent ``release`` wipes the freed blocks."""
        slots = list(slots)
        if not slots:
            return
        col = gather_slots(self.cache, self.axes, slots)
        self.cache = scatter_slots(self.cache, self.axes, slots,
                                   jax.tree.map(jnp.zeros_like, col))

    # ------------------------------------------------------ post-chunk sync
    def refresh_after_chunk(self, last_tokens: np.ndarray) -> None:
        """Re-derive the host mirrors from the authoritative slot table
        after a decode chunk's readback."""
        self.next_token = last_tokens.copy()
        self.active = np.array([r is not None for r in self.slots])
        self.remaining = np.array(
            [r.max_new_tokens - len(r.output) if r is not None else 0
             for r in self.slots], np.int32)

    def stats(self) -> Dict[str, int]:
        # historical keys preserved; extended counters live in .metrics
        return {"active": self.n_active(),
                "free": self.max_batch - self.n_active()}


def make_slot_manager(model: LM, max_batch: int, max_len: int, *,
                      layout: str = "dense",
                      registry: Optional[MetricsRegistry] = None,
                      sharder=None) -> SlotManager:
    """Construct the slot manager for a ``ServingPlan.cache_layout``:
    ``"dense"`` → :class:`SlotManager`, ``"paged:<block_size>"`` →
    :class:`repro.serving.paged.PagedSlotManager` (imported lazily; it
    depends on this module).  A mesh ``sharder`` lays the dense cache out
    on its mesh; the paged pool stays on one device."""
    from repro.plan.plan import parse_cache_layout

    block = parse_cache_layout(layout)
    if block is None:
        return SlotManager(model, max_batch, max_len, registry=registry,
                           sharder=sharder)
    from repro.serving.paged import PagedSlotManager

    return PagedSlotManager(model, max_batch, max_len, block_size=block,
                            registry=registry)
