"""Fault recovery for the serving engine: guard scan, quarantine,
rollback/retry/shed, the stuck-slot watchdog, and recovery points.

An engine holds one :class:`Recovery` (``engine.recovery``) only when its
plan enables the watchdog (``plan.watchdog_ticks > 0``) or a
:class:`repro.serving.faults.FaultInjector` is attached; otherwise
``engine.recovery`` is None and the hot path never enters this module.
The engine calls it at these points of a step, and nowhere else:

* :meth:`Recovery.on_step` — the start of a step (the injector fires
  what is due);
* :meth:`Recovery.on_admit` — a slot granted or resumed;
* :meth:`Recovery.on_finish` — a request finished;
* :meth:`Recovery.prefill_fails` — before a prefill launch;
* :meth:`Recovery.on_readback` and :meth:`Recovery.on_chunk` — on either
  side of a chunk's bookkeeping: which slots' tokens to discard, then
  quarantine, the watchdog and the recovery-point refresh.

The policy: a slot whose state cannot be vouched for (poisoned, its
readback lost, or wedged past the watchdog) is scrubbed and released,
and its request rolls back to its last good recovery point, charged one
retry; past ``plan.retry_budget`` the request is shed.  The engine never
emits a token it cannot vouch for.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.slotstate import SlotSnapshot

log = logging.getLogger("repro.serving")

#: ``fault_stats()`` key -> (registry counter, help), in reporting order
FAULT_COUNTERS = {
    "injected": ("faults.injected", "faults fired by the attached injector"),
    "quarantined": ("faults.quarantined",
                    "slots quarantined (poison / dropped readback / "
                    "watchdog)"),
    "retries": ("faults.retries",
                "request rollbacks (re-queued from the last good snapshot "
                "or re-prefilled)"),
    "shed": ("faults.shed", "requests shed after exhausting retry_budget"),
    "watchdog_evictions": ("faults.watchdog_evictions",
                           "stuck slots evicted by the watchdog"),
}


def idle_journal(max_batch: int) -> Dict[str, Any]:
    """The journal fields of an engine with no recovery: what
    :meth:`Recovery.journal` writes before any fault or progress."""
    return {"stalled": [], "last_progress": [0] * max_batch,
            "faults": dict.fromkeys(FAULT_COUNTERS, 0)}


class Recovery:
    """All of one engine's recovery state and policy.

    State: the armed ``drop_readback`` / ``fail_prefill`` flags and the
    ``stalled`` / ``poisoned`` slot sets (set by the injector),
    ``points`` (uid -> last good snapshot and output length),
    ``last_progress`` (per slot, the tick it last emitted a token),
    ``fault_events`` and the ``faults.*`` counters in the engine's
    registry."""

    def __init__(self, engine):
        self.engine = engine
        self.retry_budget = int(engine.plan.retry_budget)
        self.watchdog_ticks = int(engine.plan.watchdog_ticks)
        self.injector = None
        self.counters = {key: engine.metrics.counter(name, doc)
                         for key, (name, doc) in FAULT_COUNTERS.items()}
        self._host_syncs = engine.metrics["engine.host_syncs"]
        self.fault_events: List[Dict[str, Any]] = []
        self._awaiting: Dict[int, Dict[str, Any]] = {}  # uid -> open event
        self.points: Dict[int, Tuple[SlotSnapshot, int]] = {}
        self.stalled: Set[int] = set()      # slots frozen by stall_slot
        self.poisoned: Set[int] = set()     # scribbled, not yet caught
        self.last_progress = np.zeros((engine.max_batch,), np.int64)
        self.drop_readback = False          # armed: lose the next readback
        self.fail_prefill = False           # armed: fail the next prefill
        self._bad: List[int] = []           # this chunk's discarded slots
        self._dropped = False               # ... because it was lost

    def attach(self, injector) -> None:
        """Attach a :class:`repro.serving.faults.FaultInjector`; its due
        faults fire at the start of every step."""
        if injector.plan.needs_watchdog() and self.watchdog_ticks <= 0:
            raise ValueError(
                "fault plan contains stall_slot faults but the engine's "
                "watchdog is off; set plan.watchdog_ticks > 0 so stalled "
                "requests can be evicted and retried")
        self.injector = injector

    def stats(self) -> Dict[str, int]:
        return {key: ctr.value for key, ctr in self.counters.items()}

    # ------------------------------------------------------------- hooks
    def on_step(self) -> None:
        if self.injector is not None:
            self.injector.apply_due(self.engine)   # may raise EngineKilled

    def on_admit(self, req, slot: Optional[int]) -> None:
        """``req`` was granted ``slot`` (None: it finished at its prefill
        token).  A rolled-back request that made it back closes its open
        fault event and emits the quarantine span (fault tick -> now)."""
        tick = self.engine.ticks
        if slot is not None:
            self.last_progress[slot] = tick
        event = self._awaiting.pop(req.uid, None)
        if event is None:
            return
        event["recovered_at"] = tick
        if self.engine.tracer is not None:
            self.engine.tracer.request_quarantine(req, event["tick"], tick)

    def on_finish(self, req) -> None:
        self.points.pop(req.uid, None)

    def prefill_fails(self, reqs) -> bool:
        """True when an armed fault fails this prefill call before launch:
        the whole group rolls back (fresh requests re-prefill from scratch,
        charged one retry), and the engine admits no more this tick."""
        if not self.fail_prefill:
            return False
        self.fail_prefill = False
        tick = self.engine.ticks
        self.fault_events.append(
            {"kind": "fail_prefill", "tick": tick, "uid": None,
             "slot": None, "recovered_at": None})
        if self.engine.tracer is not None:
            self.engine.tracer.engine_fault(tick, "fail_prefill",
                                            rows=len(reqs))
        for req in reqs:
            self._rollback(req, tick, "fail_prefill")
        return True

    def on_readback(self, n: int, active_idx: List[int]) -> Set[int]:
        """The slots whose tokens in this chunk's readback the engine must
        discard: every slot that decoded when an armed fault lost the
        readback (the overlapped first tokens riding on it included),
        else the slots the guard scan finds poisoned."""
        sm = self.engine.sm
        self._dropped = self.drop_readback and n > 0
        self.drop_readback = False
        if self._dropped:
            self._bad = [i for i in active_idx
                         if sm.slots[i] is not None and sm.active[i]]
        elif self.injector is not None and n > 0:
            self._bad = self._scan_poisoned(active_idx)
        else:
            self._bad = []
        return set(self._bad)

    def on_chunk(self, n: int, acts, active_idx: List[int]) -> None:
        """After a chunk's bookkeeping: note which slots emitted a token,
        quarantine the discarded slots, re-assert stalls over the
        refreshed mirrors, run the watchdog, and refresh every survivor's
        recovery point."""
        sm, tick = self.engine.sm, self.engine.ticks
        for i in active_idx:
            if i not in self._bad and acts[:n, i].any():
                self.last_progress[i] = tick
        kind = "drop_readback" if self._dropped else "poison"
        for i in self._bad:
            if sm.slots[i] is not None:
                self._quarantine(i, tick, kind)
        # refresh_after_chunk derived `active` from occupancy: re-freeze
        # slots the injector stalled (their request is wedged, not done)
        for i in list(self.stalled):
            if sm.slots[i] is None:
                self.stalled.discard(i)
            else:
                sm.active[i] = False
        if self.watchdog_ticks > 0:
            for i in sm.occupied():
                if tick - self.last_progress[i] >= self.watchdog_ticks:
                    self._quarantine(i, tick, "watchdog")
        self._refresh_points()

    # ------------------------------------------------------------ journal
    def journal(self) -> Dict[str, Any]:
        """The fault fields of the engine's checkpoint journal."""
        return {"stalled": sorted(self.stalled),
                "last_progress": [int(x) for x in self.last_progress],
                "faults": {k: int(v) for k, v in self.stats().items()}}

    def journal_points(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """The recovery points for the journal: their cache columns (for
        the array tree) and ``uid -> [n_out, next_token]`` (JSON).  A
        stalled slot's point predates its live column, so the points
        cannot be rebuilt from the slot columns."""
        cols = {f"u{uid}": snap.cache_col
                for uid, (snap, _) in self.points.items()}
        meta = {str(uid): [n_out, int(snap.next_token)]
                for uid, (snap, n_out) in self.points.items()}
        return cols, meta

    def load(self, ex: Dict[str, Any], point_cols: Dict[str, Any]) -> None:
        """Read the fault fields and the recovery points (``point_cols``:
        their restored cache columns) back from a checkpoint's ``engine``
        journal."""
        self.points = {
            int(uid): (SlotSnapshot(point_cols[f"u{uid}"], int(nt)),
                       int(n_out))
            for uid, (n_out, nt) in ex.get("points", {}).items()}
        faults = ex.get("counters", {}).get("faults", {})
        for key, ctr in self.counters.items():
            ctr.inc(int(faults.get(key, 0)))
        self.stalled = set(int(s) for s in ex.get("stalled", []))
        self.last_progress[:] = np.asarray(ex["last_progress"],
                                           dtype=np.int64)

    # ------------------------------------------------------------ policy
    def _scan_poisoned(self, active_idx: List[int]) -> List[int]:
        """Per-slot non-finite guard over every float cache leaf, reduced
        on device to one (max_batch,) flag vector — runs only while a
        poison is outstanding, so fault-free chunks pay nothing."""
        sm = self.engine.sm
        self.poisoned = {s for s in self.poisoned if sm.slots[s] is not None}
        if not self.poisoned:
            return []
        flags = np.zeros((self.engine.max_batch,), bool)
        checks = []
        for leaf, ax in zip(jax.tree.leaves(sm.cache),
                            jax.tree.leaves(sm.axes)):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                continue
            red = tuple(d for d in range(leaf.ndim) if d != ax)
            checks.append(jnp.any(~jnp.isfinite(leaf), axis=red))
        for bad in jax.device_get(checks):
            flags |= np.asarray(bad)
        caught = [i for i in active_idx
                  if flags[i] and sm.slots[i] is not None]
        self.poisoned -= set(caught)
        return caught

    def _quarantine(self, slot: int, tick: int, kind: str) -> None:
        """Pull a bad slot out of service: scrub the column (no residue
        for the next tenant), release the slot, roll the request back."""
        sm = self.engine.sm
        req = sm.slots[slot]
        self.counters["quarantined"].inc()
        if kind == "watchdog":
            self.counters["watchdog_evictions"].inc()
        sm.scrub([slot])
        sm.release(slot)
        self.stalled.discard(slot)
        self.poisoned.discard(slot)
        self._rollback(req, tick, kind, slot)

    def _rollback(self, req, tick: int, kind: str,
                  slot: Optional[int] = None) -> None:
        """Re-queue ``req`` from its last good recovery point (or from
        scratch when none exists), charging one retry; past the budget the
        request is shed.  Emits the fault event + trace instants."""
        eng = self.engine
        event = {"kind": kind, "tick": tick, "uid": req.uid, "slot": slot,
                 "recovered_at": None}
        self.fault_events.append(event)
        self._awaiting[req.uid] = event
        if eng.tracer is not None:
            eng.tracer.request_fault(req, tick, kind, slot)
        req.retries += 1
        rp = self.points.get(req.uid)
        if req.retries > self.retry_budget:
            req.shed = True
            event["shed"] = True
            event["recovered_at"] = tick
            self._awaiting.pop(req.uid, None)
            self.points.pop(req.uid, None)
            self.counters["shed"].inc()
            if eng.tracer is not None:
                eng.tracer.request_quarantine(req, tick, tick)
                eng.tracer.request_shed(req, tick)
            if eng.live is not None:
                eng.live.observe_request(req, tick)
            log.debug("shed req %d at tick %d: retry budget %d exhausted "
                      "(%s)", req.uid, tick, self.retry_budget, kind)
            return
        self.counters["retries"].inc()   # re-queues, not the shedding try
        if rp is not None:
            snap, n_out = rp
            del req.output[n_out:]
            req.saved = snap
        else:
            del req.output[:]
            req.saved = None
        eng.scheduler.requeue_front(req)
        if eng.tracer is not None:
            eng.tracer.request_retry(req, tick, req.retries)
        log.debug("rolled back req %d at tick %d (%s, retry %d/%d, "
                  "%d tokens kept)", req.uid, tick, kind, req.retries,
                  self.retry_budget, len(req.output))

    def _refresh_points(self) -> None:
        """Snapshot every occupied slot as its request's last *good*
        recovery point (the guard scan / quarantine already removed every
        slot known bad, so what remains is vouched-for state).

        Stalled slots are skipped: the fused chunk advances *every*
        lane's device state (only the token/remaining writebacks are
        masked by ``active``), so a wedged slot's column silently drifts
        from its frozen outputs — its recovery point must stay the last
        pre-stall snapshot or the watchdog rollback resumes from state
        the request never emitted tokens for."""
        sm = self.engine.sm
        occ = [i for i in sm.occupied() if i not in self.stalled]
        if not occ:
            return
        snaps = sm.snapshot_many(occ)
        self._host_syncs.inc()
        for slot, snap in zip(occ, snaps):
            req = sm.slots[slot]
            self.points[req.uid] = (snap, len(req.output))


__all__ = ["FAULT_COUNTERS", "Recovery", "idle_journal"]
