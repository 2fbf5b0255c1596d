"""Plain float32 reference of the DeepBench LSTM and GRU cells, with
nothing imported from the program under test.

Weights are (rows, G, H) per input (``w_x`` for x, ``w_h`` for h) with
gate order (i, j, f, o) for the LSTM and (r, z, n) for the GRU:

  LSTM  c' = s(f) c + s(i) tanh(j),  h' = s(o) tanh(c'),
        each gate z = x W_x + h W_h + b
  GRU   r = s(x W_xr + b_r + h W_hr + b'_r),  z likewise,
        n = tanh(x W_xn + b_n + r (h W_hn + b'_n)),  h' = (1 - z) n + z h

Every product is f32 at ``Precision.HIGHEST``.  Weights stored as int8
with a per-(gate, unit) scale are widened as q * scale.  The control
(:func:`quantize`, 4 bits) is this reference with weights rounded to
int4 per (gate, unit).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def quantize(w: jax.Array, bits: int):
    """Symmetric per-(gate, unit) rounding over the contraction axis:
    (q, scale) with w ~= q * scale."""
    top = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / top
    q = jnp.clip(jnp.round(w / scale), -top, top)
    return q, scale[0]


def widen(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(F32) * scale.astype(F32)[None]


def _gates(a, w):
    """a (B, rows) @ w (rows, G, H) -> (B, G, H)."""
    return jnp.einsum("br,rgh->bgh", a, w, precision=HIGHEST)


@jax.jit
def lstm(w: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x (T, B, D) -> h at every step (T, B, H)."""
    H = w["w_h"].shape[0]
    B = x.shape[1]

    def step(carry, xt):
        h, c = carry
        z = _gates(xt.astype(F32), w["w_x"]) + _gates(h, w["w_h"]) + w["b"]
        i, j, f, o = (z[:, g] for g in range(4))
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(j)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((B, H), F32)
    return jax.lax.scan(step, (zero, zero), x)[1]


@jax.jit
def gru(w: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    H = w["w_h"].shape[0]
    B = x.shape[1]

    def step(h, xt):
        zx = _gates(xt.astype(F32), w["w_x"]) + w["b"]
        zh = _gates(h, w["w_h"]) + w["b_h"]
        r = jax.nn.sigmoid(zx[:, 0] + zh[:, 0])
        z = jax.nn.sigmoid(zx[:, 1] + zh[:, 1])
        n = jnp.tanh(zx[:, 2] + r * zh[:, 2])
        h = (1.0 - z) * n + z * h
        return h, h

    return jax.lax.scan(step, jnp.zeros((B, H), F32), x)[1]


def run(cell: str, w: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return (lstm if cell == "lstm" else gru)(w, x)
