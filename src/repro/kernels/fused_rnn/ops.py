"""Jit'd wrappers dispatching RNNCellConfig workloads onto the fused
Pallas kernels (TPU) or their interpret-mode execution (CPU validation).

``serve`` is the entry point used by ``repro.core.cells.serve(...,
impl="kernel")`` and the DeepBench benchmark harness.  Block size bh comes
from the DSE (repro.core.dse) unless overridden — scored at the batch
actually served, not the DeepBench cell's batch-1 default — or from a
``tile_plans`` entry passed as ``plan``.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret_mode, tile_arg
from repro.kernels.fused_rnn.fused_rnn import fused_gru, fused_lstm
from repro.obs.spans import span

F32 = jnp.float32


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


def serve(cfg, w: Dict, x_seq: jax.Array, *, bh: int = 0,
          state: Optional[Tuple[jax.Array, ...]] = None,
          interpret: Optional[bool] = None,
          plan: Optional[Mapping[str, object]] = None) -> jax.Array:
    """Run T serving steps through the fused kernel.  x_seq (T, B, D).

    ``plan`` is a ``tile_plans`` entry: ``bh`` overrides the tile (snapped
    to a divisor of H), ``persistent: true`` selects the weights-resident
    variant (whole-H tile, validated against the VMEM budget by
    ``ServingPlan.validate``)."""
    from repro.core.dse import snap_tile

    if interpret is None:
        interpret = interpret_mode()
    T, B, D = x_seq.shape
    H = cfg.hidden
    with span("rnn.plan"):
        persistent = bool((plan or {}).get("persistent", False))
        bh = tile_arg(plan, "bh", bh or 0)
        if not bh:
            bh = H if persistent else default_bh(cfg, B)
        bh = H if persistent else snap_tile(H, bh)
    with span("rnn.operands"):
        wx, wh, s_x, s_h = _weights_for_kernel(cfg, w)
        if state is None:
            h0 = jnp.zeros((B, H), F32)
            c0 = jnp.zeros((B, H), F32)
        else:
            h0 = state[0]
            c0 = state[1] if len(state) > 1 else jnp.zeros((B, H), F32)
        b_h = (None if cfg.cell == "lstm"
               else w.get("b_h", jnp.zeros_like(w["b"])))
    with span("rnn.launch"):
        if cfg.cell == "lstm":
            y, _, _ = fused_lstm(x_seq, wx, wh, s_x, s_h, w["b"], h0, c0,
                                 bh=bh, interpret=interpret,
                                 persistent=persistent)
        else:
            y, _ = fused_gru(x_seq, wx, wh, s_x, s_h, w["b"], b_h, h0,
                             bh=bh, interpret=interpret,
                             persistent=persistent)
    return y
