"""Jit'd wrappers dispatching RNNCellConfig workloads onto the fused
Pallas kernels (TPU) or their interpret-mode execution (CPU validation).

``serve`` is the entry point used by ``repro.core.cells.serve(...,
impl="kernel")`` and the DeepBench benchmark harness.  Block size bh comes
from the DSE (repro.core.dse) unless overridden — scored at the batch
actually served, not the DeepBench cell's batch-1 default — or from a
``tile_plans`` entry passed as ``plan``.

A request is one dispatch.  The tile is chosen once per (config, batch,
requested tile) and kept; each (config, tile, persistent, interpret)
gets one jitted request program, which builds the f32 zero states (and a
zero ``b_h`` for GRU weights that lack one) inside its trace, so no
eager device op runs per request.  :data:`METRICS` counts the request
programs traced (``rnn.programs_built``: one per new input structure,
so a build after warm-up is a compile in the served path) and the
requests served (``rnn.requests_served``).
"""

from __future__ import annotations

import functools
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret_mode, tile_arg
from repro.kernels.fused_rnn.fused_rnn import fused_gru, fused_lstm
from repro.obs.registry import MetricsRegistry
from repro.obs.spans import span

F32 = jnp.float32

METRICS = MetricsRegistry()
_BUILT = METRICS.counter("rnn.programs_built",
                         "request programs traced (one per input structure)")
_SERVED = METRICS.counter("rnn.requests_served", "calls of serve()")


def _weights_for_kernel(cfg, w: Dict) -> Tuple:
    """Split quantized/unquantized weight dicts into kernel operands."""
    s_x = w.get("w_x_scale")
    s_h = w.get("w_h_scale")
    wx, wh = w["w_x"], w["w_h"]
    if s_x is None:
        wx = wx.astype(jnp.bfloat16)
        s_x = jnp.ones(w["b"].shape, F32)
    if s_h is None:
        wh = wh.astype(jnp.bfloat16)
        s_h = jnp.ones(w["b"].shape, F32)
    return wx, wh, s_x, s_h


def default_bh(cfg, batch: int) -> int:
    """DSE-chosen H tile for serving ``batch`` lanes of this cell.

    The batch must reach ``best_plan`` — the VMEM working set scales
    with it, so scoring at the config's batch-1 default silently picks
    the single-lane tile (e.g. lstm H=4096 wants bh=128 at b=1 but the
    smaller batched tile once the state/io buffers claim their share)."""
    from repro.core.dse import best_plan
    return best_plan(cfg, max_batch=batch).bh


@functools.lru_cache(maxsize=None)
def _tile(cfg, batch: int, bh: int, persistent: bool) -> int:
    """The H tile served: the whole of H for the persistent variant, else
    ``bh`` (or the DSE's tile when 0) snapped to a divisor of H."""
    from repro.core.dse import snap_tile

    if persistent:
        return cfg.hidden
    return snap_tile(cfg.hidden, bh or default_bh(cfg, batch))


@functools.lru_cache(maxsize=None)
def _program(cfg, bh: int, persistent: bool, interpret: bool):
    """The jitted request program of one cell at one tile:
    ``(w, x_seq, state) -> y``, with ``state=None`` for zero states."""

    def rnn_request(w, x_seq, state):
        _BUILT.inc()                             # runs once per trace
        B, H = x_seq.shape[1], cfg.hidden
        wx, wh, s_x, s_h = _weights_for_kernel(cfg, w)
        h0 = jnp.zeros((B, H), F32) if state is None else state[0]
        if cfg.cell == "lstm":
            c0 = (state[1] if state is not None and len(state) > 1
                  else jnp.zeros((B, H), F32))
            y, _, _ = fused_lstm(x_seq, wx, wh, s_x, s_h, w["b"], h0, c0,
                                 bh=bh, interpret=interpret,
                                 persistent=persistent)
        else:
            b_h = w["b_h"] if "b_h" in w else jnp.zeros_like(w["b"])
            y, _ = fused_gru(x_seq, wx, wh, s_x, s_h, w["b"], b_h, h0,
                             bh=bh, interpret=interpret,
                             persistent=persistent)
        return y

    return jax.jit(rnn_request)


def serve(cfg, w: Dict, x_seq: jax.Array, *, bh: int = 0,
          state: Optional[Tuple[jax.Array, ...]] = None,
          interpret: Optional[bool] = None,
          plan: Optional[Mapping[str, object]] = None) -> jax.Array:
    """Run T serving steps through the fused kernel.  x_seq (T, B, D).

    ``plan`` is a ``tile_plans`` entry: ``bh`` overrides the tile (snapped
    to a divisor of H), ``persistent: true`` selects the weights-resident
    variant (whole-H tile, validated against the VMEM budget by
    ``ServingPlan.validate``)."""
    with span("rnn.plan"):
        if interpret is None:
            interpret = interpret_mode()
        persistent = bool((plan or {}).get("persistent", False))
        bh = _tile(cfg, x_seq.shape[1], tile_arg(plan, "bh", bh or 0),
                   persistent)
    with span("rnn.operands"):
        program = _program(cfg, bh, persistent, interpret)
    with span("rnn.launch"):
        y = program(w, x_seq, state)
    _SERVED.inc()
    return y
