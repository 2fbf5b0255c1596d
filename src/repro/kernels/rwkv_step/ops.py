"""Plan adapter for the fused RWKV6 step kernel.

``repro.models.recurrence.linear_attention_step_planned`` drives the
kernel in the serving decode step.  A ``tile_plans["rwkv"]`` entry sets
the head tile: its ``bh`` is in hidden units (``core.dse.best_rwkv_plan``
counts head_tile x head_dim), converted to whole heads here and snapped to
a divisor of the head count.
"""

from __future__ import annotations

from typing import Mapping, Optional

from repro.kernels.dispatch import tile_arg


def head_tile(n_heads: int, head_dim: int,
              plan: Optional[Mapping[str, object]]) -> int:
    """Heads per grid step for a plan whose ``bh`` counts hidden units."""
    from repro.core.dse import snap_tile

    bh_units = tile_arg(plan, "bh", 0)
    if not bh_units:
        return n_heads
    return snap_tile(n_heads, max(1, bh_units // head_dim))
