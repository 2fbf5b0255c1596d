"""Slot-based continuous-batching serving engine with an on-device hot path.

The batched decode step runs every tick over all occupied slots; requests
join by prefilling into a free slot and leave on EOS/length without
disturbing the others — the standard continuous-batching scheme
(Orca/vLLM) on a fixed-slot KV cache.

The engine is mechanism only; the serving stack is three explicit layers:

* :mod:`repro.serving.scheduler` owns *policy* — which queued request to
  admit (FCFS / SPF / EDF) and, for preemptive EDF, which running request
  to evict when a tighter deadline arrives;
* :mod:`repro.serving.slotstate` owns *state* — the cache pytree and the
  per-slot control mirrors, with a symmetric gather/scatter API so a
  slot's whole decode state can be evicted to host and later restored
  bit-exactly into any free slot (preempt → resume);
* this module owns *execution* — ``step()`` asks the scheduler, moves
  state through the slot manager, runs the prefill / fused-decode
  programs, and reports telemetry;
* :mod:`repro.plan` owns the *design point* — every constructor knob
  (capacity, bucket set, chunking, policy, sampling) lives in one frozen
  :class:`~repro.plan.ServingPlan`; build engines with
  :meth:`ServingEngine.from_plan` (the kwargs constructor is a shim that
  assembles a plan internally and behaves identically).

The steady-state hot path is the paper's thesis applied at the host level:
breaking the serving loop into per-kernel launches (decode, then a host
round-trip to sample, then a host read of the lengths) wastes the machine
on host↔device traffic exactly the way per-kernel launches waste it on
inter-kernel data movement.  So the decode tick is ONE fused jit program —
decode + sample + EOS/length done-mask + per-slot token writeback, with
the PRNG key carried as state — and up to ``sync_every`` ticks run
on-device between host syncs (a ``lax.while_loop`` that early-exits when
every slot is done, or when a slot frees while requests are queued so the
host can admit).  The host only intervenes to admit and retire.

Admission is bucketed batched prefill: prompts are right-padded to
power-of-two length buckets (capped at ``max_len - 1``) and all
same-bucket admissions prefill in one fixed-batch call, so the number of
prefill XLA compiles is bounded by the bucket count instead of the number
of distinct prompt lengths, and bursty (MMPP) arrival spikes amortize
into one program launch.  Slot insertion is one pytree scatter for the
whole admitted group.

With ``overlap_prefill=True`` (default) admission no longer serializes
with decode: the prefill program, the on-device first-token sample, the
slot scatter, and the decode chunk are all dispatched back-to-back with
no host sync in between, and the first tokens ride home on the chunk's
single readback.  The schedule (tick stamps, outputs, utilization) is
bit-identical to the synchronous path; only the blocking-readback count
drops.  Admission rounds that can finish at the prefill token (a request
with an ``eos_id``, or ``max_new_tokens == 1``) fall back to the
synchronous path, because instant retirement frees the slot for further
same-tick admissions and that decision needs the sampled token on host.

Virtual-clock semantics are unchanged: with the default ``sync_every=1``
(and for any ``sync_every`` under ``workload.drive``'s arrival-bounded
chunks) the tick-stamp schedule is bit-identical to the per-tick host
loop, so the fused path is a pure wall-clock optimization.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import Sharder
from repro.models.lm import LM
from repro.obs.registry import LiveMetrics, MetricsRegistry
from repro.obs.spans import span
from repro.obs.trace import Tracer
from repro.plan.plan import MIN_BUCKET, ServingPlan
from repro.serving.sampler import SamplerConfig, split_and_sample
from repro.serving.scheduler import POLICIES, Scheduler, make_scheduler
from repro.serving.slotstate import SlotSnapshot, gather_slots, \
    make_slot_manager, scatter_slots

log = logging.getLogger("repro.serving")


class EngineKilled(RuntimeError):
    """Raised by ``step()`` when an attached fault injector schedules a
    ``kill_engine`` fault at the current tick — the process-crash stand-in
    for the crash-restart path.  ``faults.drive_resilient`` catches it,
    restores a fresh engine from the last checkpoint, and replays."""

    def __init__(self, tick: int):
        super().__init__(f"engine killed by fault injector at tick {tick}")
        self.tick = tick


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    deadline: Optional[float] = None   # absolute, clock units (EDF + SLO)
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    shed: bool = False            # rejected at submit: provably past its
    #                               deadline (plan.shed_late admission ctl)
    truncated: bool = False       # prompt tail dropped (truncate_prompts)
    capped: bool = False          # cache can't hold max_new_tokens: the
    #                               output will stop short (length cut)
    retries: int = 0              # fault recoveries consumed (rollback /
    #                               re-prefill); shed past plan.retry_budget
    # tick stamps (engine tick counter; see serving.metrics for semantics)
    t_submit: int = 0             # tick at submission
    t_admit: Optional[int] = None   # tick the prefill ran (slot granted)
    t_first: Optional[int] = None   # tick the first token was produced
    t_done: Optional[int] = None    # tick the request completed
    # preemption lifecycle (EDF --preempt): evict-to-host / resume stamps
    n_preempts: int = 0
    t_preempts: List[int] = dataclasses.field(default_factory=list)
    t_resumes: List[int] = dataclasses.field(default_factory=list)
    saved: Optional[SlotSnapshot] = dataclasses.field(
        default=None, repr=False)   # host state while evicted


#: Request fields journaled by ``ServingEngine.checkpoint()`` — everything
#: except ``saved``, whose cache column travels in the array tree (the
#: paired ``next_token`` scalar rides as ``saved_next_token``).
_REQ_FIELDS = ("uid", "prompt", "max_new_tokens", "eos_id", "deadline",
               "output", "done", "shed", "truncated", "capped", "retries",
               "t_submit", "t_admit", "t_first", "t_done",
               "n_preempts", "t_preempts", "t_resumes")


def _req_to_json(req: "Request") -> Dict[str, Any]:
    d = {f: getattr(req, f) for f in _REQ_FIELDS}
    if req.saved is not None:
        d["saved_next_token"] = int(req.saved.next_token)
    return d


def _req_from_json(d: Dict[str, Any]) -> "Request":
    d = dict(d)
    d.pop("saved_next_token", None)
    return Request(**d)


def _is_reduced(cfg) -> bool:
    """Best-effort identity check for the kwargs shim: a config that
    differs from the registry entry of its own name is a reduced (or
    otherwise customized) variant.  Unknown names count as reduced —
    the flag only matters when ``from_plan`` has to rebuild the model."""
    try:
        from repro.configs import ARCHS

        return ARCHS.get(cfg.name) != cfg
    except Exception:  # pragma: no cover - configs import should not fail
        return True


@dataclasses.dataclass
class _PendingAdmit:
    """An overlapped admission group: first tokens still on device, host
    bookkeeping deferred to the decode chunk's readback."""

    reqs: List[Request]
    rows: List[int]
    slots: List[int]
    first: jax.Array            # (rows,) sampled prefill tokens, on device


def _decode_many(model: LM, sharder: Sharder, sampler: SamplerConfig,
                 max_len: int, k: int,
                 params, cache, tokens, key, active, eos, remaining,
                 limit, stop_on_free):
    """Up to ``min(k, limit)`` fused decode ticks on device, no host sync.

    Per tick: decode_step + sample + done-mask (EOS / cache-full /
    max_new_tokens) + per-slot token writeback, threading the PRNG key.
    Early-exits when no slot is active, or — when ``stop_on_free`` — after
    the first tick that frees a slot, so the host can admit a queued
    request at exactly the tick the per-tick loop would have.

    Returns (n_ticks, cache, key, toks (k,B), acts (k,B), dones (k,B));
    rows >= n_ticks of the buffers are zero.
    """
    B = tokens.shape[0]
    st = dict(i=jnp.int32(0), cache=cache, tokens=tokens, key=key,
              active=active, remaining=remaining,
              toks=jnp.zeros((k, B), jnp.int32),
              acts=jnp.zeros((k, B), bool),
              dones=jnp.zeros((k, B), bool),
              freed=jnp.bool_(False))

    def cond(st):
        return ((st["i"] < limit) & st["active"].any()
                & jnp.logical_not(stop_on_free & st["freed"]))

    def body(st):
        cache, logits = model.decode_step(params, st["cache"], st["tokens"],
                                          sharder)
        key, sampled = split_and_sample(st["key"], logits, sampler)
        active = st["active"]
        tokens = jnp.where(active, sampled, st["tokens"])
        remaining = st["remaining"] - active.astype(jnp.int32)
        hit_eos = (eos >= 0) & (sampled == eos)
        full = cache["lengths"] >= max_len - 1
        done_now = active & (hit_eos | full | (remaining <= 0))
        i = st["i"]
        return dict(
            i=i + 1, cache=cache, tokens=tokens, key=key,
            active=active & ~done_now, remaining=remaining,
            toks=st["toks"].at[i].set(tokens),
            acts=st["acts"].at[i].set(active),
            dones=st["dones"].at[i].set(done_now),
            freed=st["freed"] | done_now.any())

    st = jax.lax.while_loop(cond, body, st)
    return (st["i"], st["cache"], st["key"],
            st["toks"], st["acts"], st["dones"])


class ServingEngine:
    """Plan-driven construction: every design parameter lives in one
    :class:`repro.plan.ServingPlan` (``engine.plan``) — build with
    :meth:`from_plan`.  The historical kwargs constructor is kept as a
    thin shim that assembles a plan internally, so ``ServingEngine(model,
    params, sharder, max_batch=..., ...)`` keeps working with a
    bit-identical schedule to the equivalent ``from_plan`` engine."""

    def __init__(self, model: LM, params, sharder: Sharder, *,
                 max_batch: int = 4, max_len: int = 128,
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 truncate_prompts: bool = False, sync_every: int = 1,
                 policy: str = "fcfs", preempt: bool = False,
                 bucketed_prefill: bool = True,
                 overlap_prefill: bool = True,
                 shed_late: bool = False,
                 cache_layout: str = "dense",
                 plan: Optional[ServingPlan] = None,
                 tracer: Optional[Tracer] = None):
        if plan is None:   # kwargs shim: capture the knobs as a plan
            plan = ServingPlan(
                arch=model.cfg.name, reduced=_is_reduced(model.cfg),
                max_batch=max_batch, max_len=max_len,
                cache_layout=cache_layout,
                sync_every=sync_every, policy=policy, preempt=preempt,
                bucketed_prefill=bucketed_prefill,
                overlap_prefill=overlap_prefill, shed_late=shed_late,
                temperature=sampler.temperature, top_k=sampler.top_k,
                truncate_prompts=truncate_prompts,
                provenance={"source": "engine-kwargs"})
        plan.validate()
        if plan.tile_plans and hasattr(model, "with_tile_plans"):
            # thread the DSE-chosen kernel geometry into every block call
            # (both jit seams below close over this rebound model)
            model = model.with_tile_plans(plan.tile_plans)
        self.plan = plan
        self.model = model
        # the programs read the serving copy: weights rounded to the compute
        # dtype once here, not converted in every decode and prefill call
        self.params, cast_bytes = model.serving_params(params)
        self.sharder = sharder
        self.max_batch = plan.max_batch
        self.max_len = plan.max_len
        self.sampler = SamplerConfig(temperature=plan.temperature,
                                     top_k=plan.top_k)
        self.truncate_prompts = plan.truncate_prompts
        self.sync_every = int(plan.sync_every)
        self.policy = plan.policy
        self.bucketed_prefill = plan.bucketed_prefill
        self.overlap_prefill = plan.overlap_prefill
        self.shed_late = plan.shed_late
        self._buckets = plan.resolved_buckets()
        # one registry for the whole stack: scheduler + slot-state counters
        # register into it, so reset_telemetry() covers them by construction
        self.metrics = MetricsRegistry()
        self.scheduler: Scheduler = make_scheduler(
            plan.policy, preempt=plan.preempt, registry=self.metrics)
        self.cache_layout = plan.cache_layout
        self._paged = plan.cache_layout != "dense"
        self.sm = make_slot_manager(model, self.max_batch, self.max_len,
                                    layout=plan.cache_layout,
                                    registry=self.metrics)
        c = self.metrics.counter
        self._c_completed = c("engine.completed",
                              "requests finished since construction")
        self._c_total_tokens = c("engine.total_tokens",
                                 "tokens generated (prefill + decode)")
        self._c_instant_admits = c("engine.instant_admits",
                                   "requests done at their prefill token")
        self._c_host_syncs = c("engine.host_syncs",
                               "blocking device->host readbacks")
        self._c_decode_chunks = c("engine.decode_chunks",
                                  "fused decode_many launches")
        self._c_prefill_calls = c("engine.prefill_calls",
                                  "prefill program launches")
        self._c_preemptions = c("engine.preemptions",
                                "slots evicted to host")
        self._c_resumes = c("engine.resumes",
                            "evicted requests restored to a slot")
        self._c_evicted_tokens = c("engine.evicted_tokens",
                                   "tokens already generated at eviction")
        self._c_shed = c("engine.shed",
                         "requests rejected at submit (admission control)")
        # fault-tolerance counters: registered always (so reset_telemetry
        # covers them), but surfaced via fault_stats() rather than stats()
        # — no-fault runs keep their historical stats()/BENCH bytes
        self._c_f_injected = c("faults.injected",
                               "faults fired by the attached injector")
        self._c_f_quarantined = c("faults.quarantined",
                                  "slots quarantined (poison / dropped "
                                  "readback / watchdog)")
        self._c_f_retries = c("faults.retries",
                              "request rollbacks (re-queued from the last "
                              "good snapshot or re-prefilled)")
        self._c_f_shed = c("faults.shed",
                           "requests shed after exhausting retry_budget")
        self._c_f_watchdog = c("faults.watchdog_evictions",
                               "stuck slots evicted by the watchdog")
        self.metrics.gauge("engine.ticks", "virtual-clock tick counter",
                           fn=lambda: float(self._tick))
        self.metrics.gauge("engine.params_cast_bytes",
                           "parameter bytes the serving copy holds at the "
                           "compute dtype", fn=lambda: float(cast_bytes))
        log.info("serving copy: %d parameter bytes at the compute dtype",
                 cast_bytes)
        self.finished: List[Request] = []   # completed Requests, in order
        self.util_history: List[float] = []  # per-tick (active+instant)/max
        self.prefill_shapes: Set[Tuple[int, int]] = set()  # (rows, S) seen
        self.tracer = tracer          # optional structured event tracer
        self.live: Optional[LiveMetrics] = None   # enable_live_metrics()
        self._decode_compile_traced = False  # decode program built once
        self._pending: List[_PendingAdmit] = []  # overlapped admissions
        self._tick = 0
        self._uid_next = 0   # plain int (not itertools.count): journaled
        #                      by checkpoint() so restored engines mint
        #                      identical uids for replayed submissions
        # ---- fault tolerance (inert unless an injector is attached or
        # ---- the plan enables the watchdog — see _fault_mode) ----------
        self.retry_budget = int(plan.retry_budget)
        self.watchdog_ticks = int(plan.watchdog_ticks)
        self._injector = None                   # faults.FaultInjector
        self.fault_events: List[Dict[str, Any]] = []
        self._awaiting: Dict[int, Dict[str, Any]] = {}  # uid -> open event
        self._recovery: Dict[int, Tuple[Optional[SlotSnapshot], int]] = {}
        self._stalled: Set[int] = set()         # slots frozen by stall_slot
        self._poison_outstanding: Set[int] = set()  # scribbled, not yet seen
        self._last_progress = np.zeros((self.max_batch,), np.int64)
        self._drop_readback = False             # armed: next chunk readback
        #                                         is discarded wholesale
        self._fail_prefill = False              # armed: next prefill call
        #                                         fails before launch
        self._prefill_blocked = False           # a prefill failed this tick
        self.restored_from: Optional[Dict[str, Any]] = None
        self._key = jax.random.PRNGKey(seed)
        sampler, max_len, k = self.sampler, self.max_len, self.sync_every

        # named functions, so the programs are ``jit_decode_program`` and
        # ``jit_prefill_program`` in traces and compile logs
        def decode_program(params, cache, tokens, key, active, eos,
                           remaining, limit, stop_on_free):
            return _decode_many(model, sharder, sampler, max_len, k,
                                params, cache, tokens, key, active, eos,
                                remaining, limit, stop_on_free)

        def prefill_program(params, batch):
            return model.prefill(params, batch, sharder, max_len=max_len)

        self._decode_many = jax.jit(decode_program, donate_argnums=1)
        self._prefill = jax.jit(prefill_program)

    @classmethod
    def from_plan(cls, plan: ServingPlan, params, *,
                  model: Optional[LM] = None,
                  sharder: Optional[Sharder] = None,
                  seed: int = 0,
                  tracer: Optional[Tracer] = None) -> "ServingEngine":
        """Build an engine from a :class:`repro.plan.ServingPlan` — the
        plan-centric constructor.  ``model``/``sharder`` default to what
        the plan's identity fields describe (``arch`` + ``reduced`` +
        ``shard_mode``); pass them explicitly to reuse an already-built
        model (the benchmark sweeps do)."""
        plan.validate()
        if model is None:
            from repro.configs import get_config
            from repro.models.lm import build_model
            from repro.testing import reduced_config

            cfg = (reduced_config(plan.arch) if plan.reduced
                   else get_config(plan.arch))
            model = build_model(cfg)
        if sharder is None:
            from repro.dist.sharding import make_sharder

            sharder = make_sharder(model.cfg, None, plan.shard_mode)
        return cls(model, params, sharder, seed=seed, plan=plan,
                   tracer=tracer)

    def lower_decode(self):
        """Lower the fused decode-chunk program that :meth:`step` launches,
        at this engine's shapes, for inspection: ``.compile().as_text()``
        shows which kernels it holds."""
        return self._decode_many.lower(
            self.params, self.sm.cache, self.sm.next_token, self._key,
            self.sm.active, self.sm.eos, self.sm.remaining,
            np.int32(self.sync_every), np.bool_(False))

    # ------------------------------------------------- back-compat accessors
    @property
    def cache(self):
        return self.sm.cache

    @property
    def slots(self) -> List[Optional[Request]]:
        return self.sm.slots

    @property
    def queue(self):
        return self.scheduler.queue

    # counters live in the registry; these read-only views keep the
    # historical attribute names (engine.completed, engine.shed, ...)
    @property
    def completed(self) -> int:
        return self._c_completed.value

    @property
    def total_tokens(self) -> int:
        return self._c_total_tokens.value

    @property
    def instant_admits(self) -> int:
        return self._c_instant_admits.value

    @property
    def host_syncs(self) -> int:
        return self._c_host_syncs.value

    @property
    def decode_chunks(self) -> int:
        return self._c_decode_chunks.value

    @property
    def prefill_calls(self) -> int:
        return self._c_prefill_calls.value

    @property
    def preemptions(self) -> int:
        return self._c_preemptions.value

    @property
    def resumes(self) -> int:
        return self._c_resumes.value

    @property
    def evicted_tokens(self) -> int:
        return self._c_evicted_tokens.value

    @property
    def shed(self) -> int:
        return self._c_shed.value

    def enable_live_metrics(self, window: int = 64) -> LiveMetrics:
        """Attach a rolling :class:`repro.obs.LiveMetrics` window (last
        ``window`` ticks); the engine feeds it every tick and every
        retired request.  Returns the window for polling (``snapshot()``
        / ``line()``)."""
        self.live = LiveMetrics(window)
        return self.live

    # ------------------------------------------------------------------ API
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               deadline: Optional[float] = None) -> Request:
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}: the prefill always emits "
                             f"one token")
        limit = self.max_len - 1  # >= 1 cache slot left for generation
        truncated = False
        if len(prompt) > limit:
            if not self.truncate_prompts:
                raise ValueError(
                    f"prompt length {len(prompt)} exceeds max_len-1 = "
                    f"{limit}; raise max_len or construct the engine with "
                    f"truncate_prompts=True to drop the tail")
            log.warning("truncating prompt from %d to %d tokens "
                        "(max_len=%d)", len(prompt), limit, self.max_len)
            prompt, truncated = prompt[:limit], True
        req = Request(self._uid_next, prompt, max_new_tokens, eos_id,
                      deadline=deadline, truncated=truncated,
                      t_submit=self._tick)
        self._uid_next += 1
        # the `full` stop in the decode loop cuts generation at max(2,
        # max_len - len(prompt)) tokens (prefill token + decodes until the
        # cache fills): flag requests whose max_new_tokens cannot fit
        # instead of cutting the output silently
        cap = max(2, self.max_len - len(prompt))
        if max_new_tokens > cap:
            req.capped = True
            log.warning("request %d: max_new_tokens=%d exceeds cache room "
                        "for a %d-token prompt (max_len=%d); output stops "
                        "at %d tokens", req.uid, max_new_tokens,
                        len(prompt), self.max_len, cap)
        if self.tracer is not None:
            # every submission is traced — shed traffic included, so
            # obs.observe.fit_profile sees the *offered* load, not just
            # what admission control let through
            self.tracer.request_submit(req, self._tick)
        if (self.shed_late and deadline is not None
                and self._provably_late(req)):
            # deadline-aware admission control: reject work that cannot
            # meet its SLO even if admitted this very tick, instead of
            # spending slot-ticks on a guaranteed violation
            req.shed = True
            self._c_shed.inc()
            if self.tracer is not None:
                self.tracer.request_shed(req, self._tick)
            if self.live is not None:
                self.live.observe_request(req, self._tick)
            log.debug("shed req %d at tick %d: deadline %.1f < earliest "
                      "completion", req.uid, self._tick, deadline)
            return req
        self.scheduler.submit(req)
        return req

    def _provably_late(self, req: Request) -> bool:
        """True when the request cannot meet its deadline even with a slot
        granted *now*: earliest completion is the prefill tick plus the
        remaining decode ticks.  The bound is strictly conservative — a
        request with an ``eos_id`` could retire at its prefill token, so
        only the prefill tick counts; without one the output length is
        exactly ``max_new_tokens`` (or the cache cap, whichever is
        smaller).  Completion-by-deadline uses the SLO convention
        ``t_done + 1 <= deadline``.

        The bound equates one engine tick with one deadline clock unit —
        exact on the virtual clock (the benchmark/SLO convention, where
        deadlines are tick-denominated by construction).  Under
        ``--clock wall`` ticks run at the hardware's pace, so the bound
        is a heuristic there, not a proof."""
        if req.eos_id is not None:
            min_decode = 0      # could instant-EOS at the prefill token
        else:
            cap = max(2, self.max_len - len(req.prompt))
            min_decode = min(req.max_new_tokens, cap) - 1
        earliest_end = self._tick + 1 + min_decode
        return req.deadline < earliest_end

    def has_work(self) -> bool:
        """True while any request is queued or occupying a slot."""
        return bool(len(self.scheduler)) or self.sm.n_active() > 0

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.step():
                break

    # ------------------------------------------------------------- buckets
    def bucket(self, n: int) -> int:
        """Padded prefill length for an n-token prompt: the smallest
        bucket that fits it.  The bucket set comes from the plan
        (``plan.buckets``, defaulting to the historical pow2 set)."""
        if not self.bucketed_prefill:
            return n
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    @property
    def bucket_lengths(self) -> List[int]:
        """All bucket lengths this engine can emit (= its prefill compile
        ceiling in bucketed mode)."""
        return list(self._buckets)

    # ----------------------------------------------------------------- ticks
    def step(self, max_ticks: Optional[int] = None) -> bool:
        """One host intervention: ask the scheduler (preempt + admit), run
        up to ``min(sync_every, max_ticks)`` fused decode ticks on device
        with a single host sync at the end, report telemetry.  Returns
        False when idle."""
        with span("engine.step"):
            return self._step(max_ticks)

    def _step(self, max_ticks: Optional[int]) -> bool:
        budget = self.sync_every if max_ticks is None \
            else max(1, min(int(max_ticks), self.sync_every))
        if self._injector is not None:
            self._apply_due_faults()   # may raise EngineKilled
        with span("engine.schedule"):
            n_instant = self._schedule()
        if self.tracer is not None:
            self.tracer.counter(self._tick, "queue_depth",
                                len(self.scheduler))
        active_idx = self.sm.occupied()
        if not active_idx:
            if n_instant:
                # prefill-only tick: every admit finished at its first
                # token.  Real work happened, so time still advances.
                self._observe_tick(self._tick, n_instant / self.max_batch)
                self._tick += 1
                return True
            return bool(len(self.scheduler))
        # if requests wait in the queue, break the chunk as soon as a slot
        # frees so admission happens at the same tick the per-tick loop
        # would have admitted at
        stop_on_free = bool(len(self.scheduler))
        if self.tracer is not None and not self._decode_compile_traced:
            # the fused decode program has fixed shapes: XLA builds it
            # exactly once, on the first chunk launch
            self.tracer.compile(self._tick, "decode", self.max_batch,
                                self.sync_every)
            self._decode_compile_traced = True
        with span("engine.launch"):
            # paged layout: extend every occupied slot's block coverage
            # for the chunk's ring writes before the program launches
            # (dense: no-op)
            self.sm.ensure_chunk(budget)
            tokens_in = self._merge_pending_tokens()
            n, self.sm.cache, self._key, toks, acts, dones = \
                self._decode_many(
                    self.params, self.sm.cache, tokens_in, self._key,
                    self.sm.active, self.sm.eos, self.sm.remaining,
                    np.int32(budget), np.bool_(stop_on_free))
        self._c_decode_chunks.inc()
        # ---- the chunk's single blocking host<->device sync -------------
        # (overlapped admissions' first tokens ride home on the same pull)
        with span("engine.readback"):
            n, toks, acts, dones, firsts = jax.device_get(
                (n, toks, acts, dones, [p.first for p in self._pending]))
        with span("engine.bookkeep"):
            self._bookkeep(int(n), toks, acts, dones, firsts, active_idx,
                           n_instant)
        return True

    def _bookkeep(self, n: int, toks, acts, dones, firsts,
                  active_idx: List[int], n_instant: int) -> None:
        """Host side of a chunk's readback: append its tokens, retire
        finished requests, report its ticks, refresh the slot mirrors."""
        self._c_host_syncs.inc()
        # fault path: a dropped readback discards the whole chunk's tokens
        # (and the overlapped first tokens riding on it) — every slot that
        # decoded rolls back to its last recovery point
        dropped = self._drop_readback and n > 0
        self._drop_readback = False
        if not dropped:
            for p, fv in zip(self._pending, firsts):
                for req, row in zip(p.reqs, p.rows):
                    req.output.append(int(fv[row]))
                    self._c_total_tokens.inc()
        self._pending = []
        if dropped:
            bad = [i for i in active_idx if self.sm.slots[i] is not None
                   and self.sm.active[i]]
        elif self._injector is not None and n > 0:
            bad = self._scan_poisoned(active_idx)
        else:
            bad = []
        bad_set = set(bad)
        progressed: Set[int] = set()
        base = self._tick
        if self.tracer is not None:
            self.tracer.decode_chunk(base, n, len(active_idx))
        for j in range(n):
            n_active = 0
            for i in active_idx:
                req = self.sm.slots[i]
                if req is None or not acts[j, i] or i in bad_set:
                    continue
                n_active += 1
                progressed.add(i)
                req.output.append(int(toks[j, i]))
                self._c_total_tokens.inc()
                if dones[j, i]:
                    self._finish(req, base + j)
                    self.sm.release(i)
            self._observe_tick(
                base + j,
                (n_active + (n_instant if j == 0 else 0)) / self.max_batch)
        self._tick += n
        if self.tracer is not None:
            self.tracer.host_sync(self._tick)
        if n > 0:
            # refresh the host mirrors from the authoritative slot table
            self.sm.refresh_after_chunk(toks[n - 1])
        else:
            # fault mode only: every occupied slot is stalled, so the
            # fused loop ran zero ticks.  Time still advances one tick so
            # the watchdog can reach its threshold and evict.
            self._observe_tick(self._tick, n_instant / self.max_batch)
            self._tick += 1
        if self._fault_mode:
            self._fault_epilogue(bad, dropped, progressed)

    # ------------------------------------------------------------- internals
    def _finish(self, req: Request, tick: int) -> None:
        req.done = True
        req.t_done = tick
        if self._fault_mode:
            self._recovery.pop(req.uid, None)
        self._c_completed.inc()
        self.finished.append(req)
        if self.tracer is not None:
            self.tracer.request_done(req, tick)
        if self.live is not None:
            self.live.observe_request(req, tick)

    def _observe_tick(self, tick: int, util: float) -> None:
        """One virtual-clock tick's utilization, fanned out to every
        observer: the aggregate history, the rolling live window, and the
        trace's counter track."""
        self.util_history.append(util)
        if self.live is not None:
            self.live.observe_tick(tick, util)
        if self.tracer is not None:
            self.tracer.counter(tick, "util", util)
            if self._paged:
                # fragmentation tracks, paged runs only — dense traces
                # stay byte-identical to the pre-paged engine
                self.tracer.counter(tick, "blocks_free",
                                    self.sm.blocks_free())
                self.tracer.counter(tick, "bytes_resident",
                                    self.sm.bytes_resident())
                self.tracer.counter(tick, "padding_waste",
                                    self.sm.padding_waste())

    # -------------------------------------------------------- fault tolerance
    @property
    def _fault_mode(self) -> bool:
        """True when any recovery machinery must run: an injector is
        attached or the plan's watchdog is enabled.  Everything in this
        section is gated on it, so plain engines keep a byte-identical
        schedule, telemetry, and trace."""
        return self._injector is not None or self.watchdog_ticks > 0

    def attach_injector(self, injector) -> None:
        """Attach a :class:`repro.serving.faults.FaultInjector`; its due
        faults are applied at the top of every :meth:`step`."""
        if injector.plan.needs_watchdog() and self.watchdog_ticks <= 0:
            raise ValueError(
                "fault plan contains stall_slot faults but the engine's "
                "watchdog is off; set plan.watchdog_ticks > 0 so stalled "
                "requests can be evicted and retried")
        self._injector = injector

    def fault_stats(self) -> Dict[str, float]:
        """Fault/recovery counter view — separate from :meth:`stats` so
        no-fault runs keep their historical stats() keys byte-for-byte."""
        return self.metrics.view({
            "injected": "faults.injected",
            "quarantined": "faults.quarantined",
            "retries": "faults.retries",
            "shed": "faults.shed",
            "watchdog_evictions": "faults.watchdog_evictions",
        })

    def _apply_due_faults(self) -> None:
        """Fire every fault the injector scheduled at or before the current
        tick.  Slot faults (poison/stall) stay armed while no slot is
        occupied — they need a victim — and fall back to the lowest
        occupied slot when their nominal target is empty, so a fault plan
        written against one workload stays meaningful on another."""
        for idx, spec in self._injector.due(self._tick):
            if spec.kind == "kill_engine":
                self._injector.fire(idx, self._tick)
                self._c_f_injected.inc()
                self.fault_events.append(
                    {"kind": "kill_engine", "tick": self._tick,
                     "uid": None, "slot": None, "recovered_at": None})
                if self.tracer is not None:
                    self.tracer.engine_fault(self._tick, "kill_engine")
                raise EngineKilled(self._tick)
            if spec.kind == "drop_readback":
                self._injector.fire(idx, self._tick)
                self._c_f_injected.inc()
                self._drop_readback = True
                if self.tracer is not None:
                    self.tracer.engine_fault(self._tick, "drop_readback")
            elif spec.kind == "fail_prefill":
                self._injector.fire(idx, self._tick)
                self._c_f_injected.inc()
                self._fail_prefill = True
            else:   # poison_slot / stall_slot need an occupied victim
                occ = self.sm.occupied()
                if not occ:
                    continue   # not fired: stays due for a later tick
                slot = (spec.slot if spec.slot in occ else occ[0])
                self._injector.fire(idx, self._tick)
                self._c_f_injected.inc()
                if self.tracer is not None:
                    self.tracer.engine_fault(self._tick, spec.kind,
                                             slot=slot)
                if spec.kind == "poison_slot":
                    self._poison(slot, spec)
                else:
                    self._stalled.add(slot)
                    self.sm.active[slot] = False

    def _poison(self, slot: int, spec) -> None:
        """Corrupt ``slot``'s cache column in place: overwrite every float
        leaf with NaN (``mode="nan"``) or seeded large-magnitude garbage
        salted with ±Inf (``mode="garbage"``) — both detectable by the
        non-finite guard scan after the next chunk."""
        col = jax.device_get(gather_slots(self.sm.cache, self.sm.axes,
                                          [slot]))
        rng = np.random.default_rng(spec.seed)

        def scribble(a):
            a = np.asarray(a)
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            if spec.mode == "nan":
                return np.full_like(a, np.nan)
            g = (rng.standard_normal(a.shape) * 1e30).astype(np.float32)
            g[rng.uniform(size=a.shape) < 0.25] = np.inf
            g.reshape(-1)[0] = -np.inf   # at least one non-finite value
            return g.astype(a.dtype)

        bad = jax.tree.map(scribble, col)
        self.sm.cache = scatter_slots(self.sm.cache, self.sm.axes, [slot],
                                      bad)
        self._poison_outstanding.add(slot)

    def _scan_poisoned(self, active_idx: List[int]) -> List[int]:
        """Per-slot non-finite guard over every float cache leaf, reduced
        on device to one (max_batch,) flag vector — runs only while a
        poison is outstanding, so fault-free chunks pay nothing."""
        self._poison_outstanding = {
            s for s in self._poison_outstanding
            if self.sm.slots[s] is not None}
        if not self._poison_outstanding:
            return []
        flags = np.zeros((self.max_batch,), bool)
        cache = self.sm.cache
        checks = []
        for leaf, ax in zip(jax.tree.leaves(cache),
                            jax.tree.leaves(self.sm.axes)):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                continue
            red = tuple(d for d in range(leaf.ndim) if d != ax)
            checks.append(jnp.any(~jnp.isfinite(leaf), axis=red))
        for bad in jax.device_get(checks):
            flags |= np.asarray(bad)
        caught = [i for i in active_idx
                  if flags[i] and self.sm.slots[i] is not None]
        self._poison_outstanding -= set(caught)
        return caught

    def _quarantine(self, slot: int, tick: int, kind: str) -> None:
        """Pull a bad slot out of service: scrub the column (no residue
        for the next tenant), release the slot, roll the request back."""
        req = self.sm.slots[slot]
        self._c_f_quarantined.inc()
        if kind == "watchdog":
            self._c_f_watchdog.inc()
        self.sm.scrub([slot])
        self.sm.release(slot)
        self._stalled.discard(slot)
        self._poison_outstanding.discard(slot)
        self._rollback(req, tick, kind, slot)

    def _rollback(self, req: Request, tick: int, kind: str,
                  slot: Optional[int] = None) -> None:
        """Re-queue ``req`` from its last good recovery point (or from
        scratch when none exists), charging one retry; past the budget the
        request is shed — the engine never emits tokens it cannot vouch
        for.  Emits the fault event + trace instants."""
        event = {"kind": kind, "tick": tick, "uid": req.uid, "slot": slot,
                 "recovered_at": None}
        self.fault_events.append(event)
        self._awaiting[req.uid] = event
        if self.tracer is not None:
            self.tracer.request_fault(req, tick, kind, slot)
        req.retries += 1
        rp = self._recovery.get(req.uid)
        if req.retries > self.retry_budget:
            req.shed = True
            event["shed"] = True
            event["recovered_at"] = tick
            self._awaiting.pop(req.uid, None)
            self._recovery.pop(req.uid, None)
            self._c_f_shed.inc()
            if self.tracer is not None:
                self.tracer.request_quarantine(req, tick, tick)
                self.tracer.request_shed(req, tick)
            if self.live is not None:
                self.live.observe_request(req, tick)
            log.debug("shed req %d at tick %d: retry budget %d exhausted "
                      "(%s)", req.uid, tick, self.retry_budget, kind)
            return
        self._c_f_retries.inc()   # counts re-queues, not the shedding try
        if rp is not None:
            snap, n_out = rp
            del req.output[n_out:]
            req.saved = snap
        else:
            del req.output[:]
            req.saved = None
        self.scheduler.requeue_front(req)
        if self.tracer is not None:
            self.tracer.request_retry(req, tick, req.retries)
        log.debug("rolled back req %d at tick %d (%s, retry %d/%d, "
                  "%d tokens kept)", req.uid, tick, kind, req.retries,
                  self.retry_budget, len(req.output))

    def _mark_recovered(self, req: Request) -> None:
        """A rolled-back request made it back into a slot: close its open
        fault event and emit the quarantine span (fault tick -> now)."""
        event = self._awaiting.pop(req.uid, None)
        if event is None:
            return
        event["recovered_at"] = self._tick
        if self.tracer is not None:
            self.tracer.request_quarantine(req, event["tick"], self._tick)

    def _fault_epilogue(self, bad: List[int], dropped: bool,
                        progressed: Set[int]) -> None:
        """End-of-chunk fault bookkeeping: quarantine flagged slots, run
        the watchdog, re-assert stalls over the refreshed mirrors, and
        refresh every survivor's recovery point."""
        for i in progressed:
            self._last_progress[i] = self._tick
        for i in bad:
            if self.sm.slots[i] is not None:
                self._quarantine(i, self._tick,
                                 "drop_readback" if dropped else "poison")
        # refresh_after_chunk derived `active` from occupancy: re-freeze
        # slots the injector stalled (their request is wedged, not done)
        for i in list(self._stalled):
            if self.sm.slots[i] is None:
                self._stalled.discard(i)
            else:
                self.sm.active[i] = False
        if self.watchdog_ticks > 0:
            for i in self.sm.occupied():
                if self._tick - self._last_progress[i] >= self.watchdog_ticks:
                    self._quarantine(i, self._tick, "watchdog")
        self._refresh_recovery()

    def _refresh_recovery(self) -> None:
        """Snapshot every occupied slot as its request's last *good*
        recovery point (the guard scan / quarantine above already removed
        every slot known bad, so what remains is vouched-for state).

        Stalled slots are skipped: the fused chunk advances *every*
        lane's device state (only the token/remaining writebacks are
        masked by ``active``), so a wedged slot's column silently drifts
        from its frozen outputs — its recovery point must stay the last
        pre-stall snapshot or the watchdog rollback resumes from state
        the request never emitted tokens for."""
        occ = [i for i in self.sm.occupied() if i not in self._stalled]
        if not occ:
            return
        snaps = self.sm.snapshot_many(occ)
        self._c_host_syncs.inc()
        for slot, snap in zip(occ, snaps):
            req = self.sm.slots[slot]
            self._recovery[req.uid] = (snap, len(req.output))

    def _merge_pending_tokens(self):
        """Decode-chunk input tokens: the host mirror, with overlapped
        admissions' first tokens merged in on device (they were sampled by
        the prefill program and never came to host)."""
        if not self._pending:
            return self.sm.next_token
        tokens = jnp.asarray(self.sm.next_token)
        for p in self._pending:
            tokens = tokens.at[jnp.asarray(p.slots, jnp.int32)].set(
                p.first[jnp.asarray(p.rows, jnp.int32)])
        return tokens

    # ----------------------------------------------------------- scheduling
    def preempt(self, slot: int) -> Request:
        """Evict the request in ``slot`` to host memory and requeue it
        (see :meth:`preempt_many` — this is the one-victim case).  Public
        for manual load shedding and the round-trip tests."""
        return self.preempt_many([slot])[0]

    def preempt_many(self, slots: List[int]) -> List[Request]:
        """Evict N running requests to host memory and requeue them, in
        ``slots`` order, with ONE batched device->host transfer.

        ``SlotManager.snapshot_many`` gathers every victim's cache column
        in a single ``gather_slots`` + ``device_get`` instead of N
        sequential snapshots, so a preemption burst (EDF under an arrival
        spike) costs one host sync, not one per victim.  Bookkeeping is
        per-victim and order-preserving — ``requeue_front`` runs in
        ``slots`` order exactly as N sequential :meth:`preempt` calls
        would, so the schedule is bit-identical to the sequential path.
        Once the scheduler grants a victim a slot again it resumes
        bit-exactly under greedy decoding (with stochastic sampling the
        engine-global key stream makes resumed tokens slot/tick-dependent
        — see slotstate's module docstring)."""
        if not slots:
            return []   # no victims: no gather, no host sync
        reqs: List[Request] = []
        for slot in slots:
            if self.sm.slots[slot] is None:
                raise ValueError(f"slot {slot} is empty")
            reqs.append(self.sm.slots[slot])
        snaps = self.sm.snapshot_many(slots)
        self._c_host_syncs.inc()
        if self.tracer is not None:
            self.tracer.host_sync(self._tick)
        for slot, req, snap in zip(slots, reqs, snaps):
            req.saved = snap
            req.n_preempts += 1
            req.t_preempts.append(self._tick)
            self._c_preemptions.inc()
            self._c_evicted_tokens.inc(len(req.output))
            self.sm.release(slot)
            self.scheduler.requeue_front(req)
            if self.tracer is not None:
                self.tracer.request_preempt(req, self._tick, slot,
                                            len(req.output))
            log.debug("preempted req %d from slot %d at tick %d "
                      "(%d tokens evicted to host)", req.uid, slot,
                      self._tick, len(req.output))
        return reqs

    def _schedule(self) -> int:
        """One scheduler consultation: preempt (if the policy does), then
        admit queued requests into free slots.  Returns how many admits
        finished at their prefill token."""
        if self.scheduler.preemptive and len(self.scheduler):
            victims = self.scheduler.victims(self.sm.running(),
                                             len(self.sm.free()))
            if victims:
                self.preempt_many(victims)
        return self._admit()

    def _admit(self) -> int:
        """Admit queued requests into free slots — evicted requests are
        restored from their host snapshots (no model call), fresh ones go
        through bucketed batched prefill.  Returns how many finished at
        their prefill token (max_new_tokens=1 / instant EOS) — those never
        occupy a slot, so further queued requests are retried in the same
        tick."""
        n_instant = 0
        while len(self.scheduler):
            free = self.sm.free()
            if not free:
                break
            picked = self.scheduler.pick(len(free))
            resumes = [r for r in picked if r.saved is not None]
            fresh = [r for r in picked if r.saved is None]
            for req in resumes:
                slot = free.pop(0)
                self.sm.restore(slot, req.saved, req)
                req.saved = None
                req.t_resumes.append(self._tick)
                self._c_resumes.inc()
                if self._fault_mode:
                    self._last_progress[slot] = self._tick
                    self._mark_recovered(req)
                if self.tracer is not None:
                    self.tracer.request_resume(req, self._tick, slot)
                log.debug("resumed req %d into slot %d at tick %d",
                          req.uid, slot, self._tick)
            if not fresh:
                continue
            if self.bucketed_prefill:
                groups: Dict[int, List[Request]] = {}
                for req in fresh:
                    groups.setdefault(self.bucket(len(req.prompt)),
                                      []).append(req)
                grouped = sorted(groups.items())
            else:
                # legacy comparison path: one exact-length batch-1 prefill
                # per request (compile count grows with distinct lengths)
                grouped = [(len(r.prompt), [r]) for r in fresh]
            # instant retirement (EOS at the prefill token / one-token
            # budget) frees the slot for further same-tick admissions, and
            # that decision needs the sampled token on host: such rounds
            # take the synchronous path
            overlap = (self.overlap_prefill
                       and not any(r.eos_id is not None
                                   or r.max_new_tokens == 1 for r in fresh))
            for S, reqs in grouped:
                with span("engine.prefill"):
                    n_instant += self._prefill_group(S, reqs, free, overlap)
            if self._prefill_blocked:
                # a fault just failed the prefill call and requeued its
                # group; stop admitting this tick or we'd pick the same
                # requests again in an endless same-tick loop
                self._prefill_blocked = False
                break
        return n_instant

    def _prefill_group(self, S: int, reqs: List[Request],
                       free: List[int], overlap: bool) -> int:
        """One padded batched prefill for same-bucket admissions: sample
        every first token in one call, scatter all granted slots in one
        pytree op.  Mutates ``free`` as slots are granted.

        ``overlap=True`` keeps the sampled first tokens on device and
        defers the host bookkeeping to the decode chunk's readback, so
        the prefill never blocks the chunk launch."""
        if self._fail_prefill:
            # injected fault: the prefill call fails before launch.  The
            # whole group rolls back (fresh requests: re-prefill from
            # scratch, charged one retry) and admission stops this tick.
            self._fail_prefill = False
            self._prefill_blocked = True
            self.fault_events.append(
                {"kind": "fail_prefill", "tick": self._tick, "uid": None,
                 "slot": None, "recovered_at": None})
            if self.tracer is not None:
                self.tracer.engine_fault(self._tick, "fail_prefill",
                                         rows=len(reqs))
            for req in reqs:
                self._rollback(req, self._tick, "fail_prefill")
            return 0
        rows = self.max_batch if self.bucketed_prefill else len(reqs)
        tokens = np.zeros((rows, S), np.int32)
        lengths = np.ones((rows,), np.int32)   # dummy rows: 1 valid token
        for r_i, req in enumerate(reqs):
            tokens[r_i, :len(req.prompt)] = req.prompt
            lengths[r_i] = len(req.prompt)
        batch = {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray(lengths)}
        if self.model.cfg.m_rope_sections:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (rows, 3, S))
        if self.tracer is not None:
            if (rows, S) not in self.prefill_shapes:
                self.tracer.compile(self._tick, "prefill", rows, S)
            self.tracer.prefill(self._tick, S, rows, len(reqs), overlap)
        cacheN, logitsN = self._prefill(self.params, batch)
        self._c_prefill_calls.inc()
        self.prefill_shapes.add((rows, S))
        self._key, first = split_and_sample(self._key, logitsN, self.sampler)
        if overlap:
            grant_rows, grant_slots = [], []
            for r_i, req in enumerate(reqs):
                slot = free.pop(0)
                self.sm.grant(slot, req, None)
                req.t_admit = req.t_first = self._tick
                if self._fault_mode:
                    self._last_progress[slot] = self._tick
                    self._mark_recovered(req)
                grant_rows.append(r_i)
                grant_slots.append(slot)
            self.sm.insert_from_prefill(grant_slots, grant_rows, cacheN)
            self._pending.append(_PendingAdmit(list(reqs), grant_rows,
                                               grant_slots, first))
            return 0
        first = np.asarray(first)
        self._c_host_syncs.inc()
        if self.tracer is not None:
            self.tracer.host_sync(self._tick)
        n_instant = 0
        grant_rows, grant_slots = [], []
        for r_i, req in enumerate(reqs):
            tok = int(first[r_i])
            req.output.append(tok)
            self._c_total_tokens.inc()
            req.t_admit = req.t_first = self._tick
            if self._fault_mode:
                self._mark_recovered(req)
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.output) >= req.max_new_tokens):
                # done at the prefill token: never occupies a slot
                self._finish(req, self._tick)
                n_instant += 1
                self._c_instant_admits.inc()
                continue
            slot = free.pop(0)
            self.sm.grant(slot, req, tok)
            if self._fault_mode:
                self._last_progress[slot] = self._tick
            grant_rows.append(r_i)
            grant_slots.append(slot)
        if grant_rows:
            self.sm.insert_from_prefill(grant_slots, grant_rows, cacheN)
        return n_instant

    # ------------------------------------------------------- crash restart
    def all_requests(self) -> List[Request]:
        """Every request the engine is currently tracking: finished, slot
        resident, and queued (in that order).  Shed requests the caller
        already holds are final — they appear in no engine structure."""
        out: List[Request] = list(self.finished)
        out.extend(r for r in self.sm.slots if r is not None)
        out.extend(self.scheduler.queue)
        return out

    def checkpoint(self, manager, *, clock_now: Optional[float] = None,
                   blocking: bool = True) -> int:
        """Journal the complete engine state through a
        :class:`repro.checkpoint.CheckpointManager` step (named by the
        current tick): PRNG key + slot mirrors + every occupied slot's
        cache column + every evicted snapshot column as the array tree,
        and requests / queue order / tick / uid counter / fault state as
        JSON extra.  :meth:`restore` rebuilds an engine that replays the
        remaining schedule bit-identically.

        Must run between steps (no overlapped admissions in flight) — the
        driver checkpoints at chunk boundaries, where that always holds."""
        if self._pending:
            raise RuntimeError("checkpoint() with overlapped admissions "
                               "in flight; call between steps")
        from repro.plan import io as plan_io

        occ = self.sm.occupied()
        slot_cols: Dict[str, Any] = {}
        slots_json: Dict[str, Any] = {}
        if occ:
            snaps = self.sm.snapshot_many(occ)
            self._c_host_syncs.inc()
            for slot, snap in zip(occ, snaps):
                slot_cols[f"s{slot}"] = snap.cache_col
                slots_json[str(slot)] = _req_to_json(self.sm.slots[slot])
        saved_cols: Dict[str, Any] = {}
        queue_json: List[Dict[str, Any]] = []
        for req in self.scheduler.queue:
            queue_json.append(_req_to_json(req))
            if req.saved is not None:
                saved_cols[f"u{req.uid}"] = req.saved.cache_col
        state = {
            "key": self._key,
            "next_token": np.asarray(self.sm.next_token),
            "active": np.asarray(self.sm.active),
            "eos": np.asarray(self.sm.eos),
            "remaining": np.asarray(self.sm.remaining),
            "slot_cols": slot_cols,
            "saved_cols": saved_cols,
        }
        extra = {"engine": {
            "plan": plan_io.to_dict(self.plan.resolve()),
            "tick": self._tick,
            "uid_next": self._uid_next,
            "clock_now": clock_now,
            "slots": slots_json,
            "queue": queue_json,
            "finished": [_req_to_json(r) for r in self.finished],
            "stalled": sorted(self._stalled),
            "last_progress": [int(x) for x in self._last_progress],
            "util_history": list(self.util_history),
            "counters": {
                "total_tokens": self.total_tokens,
                "instant_admits": self.instant_admits,
                "shed": self.shed,
                "faults": {k: int(v) for k, v in self.fault_stats().items()},
            },
        }}
        manager.save(self._tick, state, extra=extra, blocking=blocking)
        return self._tick

    @classmethod
    def restore(cls, manager, params, *, model: Optional[LM] = None,
                sharder: Optional[Sharder] = None,
                step: Optional[int] = None,
                tracer: Optional[Tracer] = None) -> "ServingEngine":
        """Rebuild an engine from a :meth:`checkpoint` step (latest when
        ``step`` is None).  The restored engine's remaining schedule —
        tick stamps, outputs, uids minted for replayed submissions — is
        bit-identical to the uninterrupted engine's from the checkpoint
        tick, because every input to the deterministic loop (PRNG key,
        cache columns, mirrors, queue order, counters) is journaled."""
        if step is None:
            step = manager.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint steps under {manager.directory}")
        extra = manager.manifest(step).get("extra") or {}
        if "engine" not in extra:
            raise ValueError(
                f"checkpoint step {step} was not written by "
                f"ServingEngine.checkpoint(): no 'engine' extra")
        ex = extra["engine"]
        from repro.plan import io as plan_io

        plan = plan_io.from_dict(ex["plan"])
        eng = cls.from_plan(plan, params, model=model, sharder=sharder,
                            tracer=tracer)
        occ = sorted(int(k) for k in ex["slots"])
        saved_uids = [d["uid"] for d in ex["queue"]
                      if "saved_next_token" in d]
        template = {
            "key": eng._key,
            "next_token": np.asarray(eng.sm.next_token),
            "active": np.asarray(eng.sm.active),
            "eos": np.asarray(eng.sm.eos),
            "remaining": np.asarray(eng.sm.remaining),
            "slot_cols": {f"s{i}": gather_slots(eng.sm.cache, eng.sm.axes,
                                                [i]) for i in occ},
            "saved_cols": {f"u{u}": gather_slots(eng.sm.cache, eng.sm.axes,
                                                 [0]) for u in saved_uids},
        }
        st = manager.restore(template, step=step)
        # slot-resident requests first: the public restore path scatters
        # each journaled column back (covers dense and paged layouts)
        for i in occ:
            req = _req_from_json(ex["slots"][str(i)])
            snap = SlotSnapshot(st["slot_cols"][f"s{i}"],
                                int(st["next_token"][i]))
            eng.sm.restore(i, snap, req)
            eng._recovery[req.uid] = (snap, len(req.output))
        # then overwrite the mirrors wholesale: restore() above recomputed
        # remaining/active heuristically; the journaled arrays are exact
        # (stalled slots inactive, mid-flight remaining counts, ...)
        eng.sm.next_token[:] = st["next_token"]
        eng.sm.active[:] = st["active"]
        eng.sm.eos[:] = st["eos"]
        eng.sm.remaining[:] = st["remaining"]
        eng._key = jnp.asarray(st["key"])
        for d in ex["queue"]:
            nt = d.get("saved_next_token")
            req = _req_from_json(d)
            if nt is not None:
                req.saved = SlotSnapshot(st["saved_cols"][f"u{req.uid}"],
                                         int(nt))
            eng.scheduler.submit(req)
        for d in ex["finished"]:
            eng.finished.append(_req_from_json(d))
            eng._c_completed.inc()
        c = ex.get("counters", {})
        eng._c_total_tokens.inc(int(c.get("total_tokens", 0)))
        eng._c_instant_admits.inc(int(c.get("instant_admits", 0)))
        eng._c_shed.inc(int(c.get("shed", 0)))
        fc = c.get("faults", {})
        for ctr, key in ((eng._c_f_injected, "injected"),
                         (eng._c_f_quarantined, "quarantined"),
                         (eng._c_f_retries, "retries"),
                         (eng._c_f_shed, "shed"),
                         (eng._c_f_watchdog, "watchdog_evictions")):
            ctr.inc(int(fc.get(key, 0)))
        eng._tick = int(ex["tick"])
        eng._uid_next = int(ex["uid_next"])
        eng.util_history = list(ex.get("util_history", []))
        eng._stalled = set(int(s) for s in ex.get("stalled", []))
        eng._last_progress[:] = np.asarray(ex["last_progress"],
                                           dtype=np.int64)
        eng.restored_from = {"step": step, "clock_now": ex["clock_now"]}
        return eng

    # ------------------------------------------------------------- telemetry
    @property
    def ticks(self) -> int:
        return self._tick

    def align_clock(self, tick: int) -> None:
        """Advance the idle tick counter to a shared external clock
        (never rewinds).  Under a solo ``drive()`` the engine's tick
        domain may lag the clock while idle — harmless, since every stamp
        lives in the one engine's domain.  A disaggregated fleet exchanges
        stamps *across* engines (TTFT on the prefill replica, completion
        on the decode replica), so the router aligns every replica to the
        fleet clock before each round; see ``repro.serving.router``."""
        self._tick = max(self._tick, int(tick))

    def reset_telemetry(self) -> None:
        """Zero the counters/histories (e.g. after a jit warmup run, so
        wall-clock tick timings exclude compile).  The engine must be
        drained; queued or in-flight requests would get skewed stamps.

        ``metrics.reset()`` covers every registered counter — engine,
        scheduler, and slot-state alike — by construction, so a counter
        added anywhere in the stack can never leak warmup counts.  Two
        things deliberately survive: ``prefill_shapes`` mirrors the jit
        cache, which a telemetry reset does not clear (so the reported
        ``prefill_compiles`` stays truthful about programs built), and an
        attached tracer restarts empty at tick 0 (warmup events would
        otherwise overlap the measured run's restarted clock)."""
        if self.has_work():
            raise RuntimeError("reset_telemetry() on a busy engine")
        self.metrics.reset()
        self.finished = []
        self.util_history = []
        self._tick = 0
        if self.live is not None:
            self.live.reset()
        if self.tracer is not None:
            self.tracer.reset()

    def stats(self) -> Dict[str, float]:
        util = self.util_history
        out: Dict[str, float] = {
            "active": self.sm.n_active(),
            "queued": len(self.scheduler),
        }
        out.update(self.metrics.view({
            "completed": "engine.completed",
            "total_tokens": "engine.total_tokens",
        }))
        out["ticks"] = self._tick
        out["mean_util"] = sum(util) / len(util) if util else 0.0
        out.update(self.metrics.view({
            "instant_admits": "engine.instant_admits",
            "host_syncs": "engine.host_syncs",
            "decode_chunks": "engine.decode_chunks",
            "prefill_calls": "engine.prefill_calls",
        }))
        out["prefill_compiles"] = len(self.prefill_shapes)
        out.update(self.metrics.view({
            "preemptions": "engine.preemptions",
            "resumes": "engine.resumes",
            "evicted_tokens": "engine.evicted_tokens",
            "shed": "engine.shed",
        }))
        return out


# re-exported for back-compat: the policy registry lives in scheduler.py
__all__ = ["Request", "ServingEngine", "EngineKilled", "POLICIES",
           "MIN_BUCKET"]
