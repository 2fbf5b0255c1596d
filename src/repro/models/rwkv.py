"""RWKV6 (Finch) block: data-dependent-decay time mix + channel mix.

The closest assigned architecture to the paper's own subject — a recurrent
cell served one token at a time.  Train/prefill use the chunked closed form
(:mod:`repro.models.recurrence`); decode uses the fused single-step
recurrence, which is exactly the paper's loop-based LSTM-1 pattern: per
output element, a fused dot-product -> decay/bonus update -> readout with
no materialized intermediates.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dot, groupnorm_heads, rmsnorm
from repro.models.params import ParamSpec
from repro.models.recurrence import (chunked_linear_attention,
                                     linear_attention_step_planned)

F32 = jnp.float32
LORA_RANK = 32
DECAY_RANK = 64
N_MIX = 5  # (w, k, v, r, g)


def rwkv_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    ff = cfg.d_ff
    z = lambda *s: ParamSpec(tuple(s), jnp.float32, (None,) * len(s), init="zeros")
    w = lambda shape, axes: ParamSpec(shape, jnp.float32, axes,
                                      compute_only=True)
    specs = {
        "ln1": z(d),
        "ln2": z(d),
        # time-mix ddlerp
        "mu_base": z(d),
        "mu": z(N_MIX, d),
        "lora_a": w((d, N_MIX * LORA_RANK), ("embed", None)),
        "lora_b": ParamSpec((N_MIX, LORA_RANK, d), jnp.float32, (None, None, "embed"),
                            scale=1e-2),
        # projections
        "wr": w((d, d), ("embed", "q_flat")),
        "wk": w((d, d), ("embed", "q_flat")),
        "wv": w((d, d), ("embed", "q_flat")),
        "wg": w((d, d), ("embed", "q_flat")),
        "wo": w((d, d), ("q_flat", "embed")),
        # data-dependent decay
        "decay_base": ParamSpec((d,), jnp.float32, (None,), init="custom",
                                custom_init=_decay_init),
        "decay_a": w((d, DECAY_RANK), ("embed", None)),
        "decay_b": ParamSpec((DECAY_RANK, d), jnp.float32, (None, "embed"),
                             scale=1e-2),
        "bonus": z(d),
        "wkv_norm": z(d),
        # channel mix
        "mu_ck": z(d),
        "mu_cr": z(d),
        "wk_c": w((d, ff), ("embed", "mlp")),
        "wv_c": w((ff, d), ("mlp", "embed")),
        "wr_c": w((d, d), ("embed", "q_flat")),
    }
    return specs


def _decay_init(key: jax.Array, spec: ParamSpec) -> jax.Array:
    # spread decay half-lives per channel (rwkv-style ratio init)
    d = spec.shape[0]
    ratio = jnp.arange(d, dtype=F32) / max(1, d - 1)
    return (-6.0 + 5.0 * ratio).astype(spec.dtype)  # log(-log w) range


def _shift_seq(x: jax.Array, prev: Optional[jax.Array]) -> jax.Array:
    """Token shift: x_{t-1} (zeros / cached tail at t=0).  x: (B, T, d)."""
    pad = jnp.zeros_like(x[:, :1]) if prev is None else prev[:, None, :]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _ddlerp(params, x: jax.Array, xs: jax.Array):
    """Data-dependent token-shift interpolation -> the 5 mixed streams."""
    dx = xs - x
    xb = x + dx * params["mu_base"].astype(x.dtype)
    lora = jnp.tanh(dot(xb, params["lora_a"]))
    B, T = x.shape[:2]
    lora = lora.reshape(B, T, N_MIX, LORA_RANK)
    mix = params["mu"].astype(F32) + jnp.einsum(
        "btnr,nrd->btnd", lora.astype(F32), params["lora_b"].astype(F32))
    streams = x[:, :, None, :].astype(F32) + dx[:, :, None, :].astype(F32) * mix
    return [s.astype(x.dtype) for s in
            jnp.split(streams, N_MIX, axis=2)]  # each (B,T,1,d)


def _time_mix_inputs(params, x, xs, cfg: ModelConfig):
    xw, xk, xv, xr, xg = [s[:, :, 0, :] for s in _ddlerp(params, x, xs)]
    r = dot(xr, params["wr"])
    k = dot(xk, params["wk"])
    v = dot(xv, params["wv"])
    g = jax.nn.silu(dot(xg, params["wg"]))
    dd = jnp.tanh(dot(xw, params["decay_a"]))
    dd = jax.lax.dot_general(dd.astype(F32), params["decay_b"].astype(F32),
                             (((dd.ndim - 1,), (0,)), ((), ())))
    log_decay = -jnp.exp(
        jnp.clip(params["decay_base"].astype(F32) + dd, -8.0, 3.0))
    return r, k, v, g, log_decay


def _heads(x: jax.Array, hd: int) -> jax.Array:
    B, T, d = x.shape
    return x.reshape(B, T, d // hd, hd).transpose(0, 2, 1, 3)  # (B,H,T,hd)


def _last_valid(x: jax.Array, lengths: Optional[jax.Array]) -> jax.Array:
    """x (B, T, d) -> the last *valid* token per example (B, d): x[:, -1]
    when lengths is None, else x[b, lengths[b]-1] (right-padded batch)."""
    if lengths is None:
        return x[:, -1, :]
    idx = jnp.maximum(lengths - 1, 0)[:, None, None]
    return jnp.take_along_axis(x, idx, axis=1)[:, 0, :]


def time_mix(params, x: jax.Array, cfg: ModelConfig, sharder, *,
             prev: Optional[jax.Array] = None,
             state: Optional[jax.Array] = None,
             lengths: Optional[jax.Array] = None):
    """Full-sequence wkv.  x: (B, T, d).  Returns (out, new_shift, new_state).

    ``lengths`` (B,) marks true per-example lengths in a right-padded
    batch: padded steps are forced to (decay 1, k 0) so they leave the
    recurrent state untouched — the same identity trick
    chunked_linear_attention uses for its own chunk padding."""
    hd = cfg.rwkv.head_dim
    H = cfg.d_model // hd
    xs = _shift_seq(x, prev)
    r, k, v, g, log_decay = _time_mix_inputs(params, x, xs, cfg)
    if lengths is not None:
        valid = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
                 < lengths[:, None])[..., None]                  # (B, T, 1)
        k = jnp.where(valid, k, 0.0)
        log_decay = jnp.where(valid, log_decay, 0.0)
    rh, kh, vh = _heads(r, hd), _heads(k, hd), _heads(v, hd)
    wh = _heads(log_decay, hd)
    u = params["bonus"].astype(F32).reshape(H, hd)
    rh = sharder.constrain(rh, "batch", "rwkv_heads", "seq", None)
    y, new_state = chunked_linear_attention(
        rh, kh, vh, wh, chunk=min(cfg.rwkv.chunk, x.shape[1]),
        convention="exclusive", u=u, initial_state=state)
    y = y.transpose(0, 2, 1, 3).reshape(x.shape)
    y = groupnorm_heads(y.astype(x.dtype), params["wkv_norm"], H, cfg.norm_eps)
    out = dot(y * g, params["wo"])
    return out, _last_valid(x, lengths), new_state


def time_mix_step(params, x: jax.Array, cfg: ModelConfig, sharder, *,
                  prev: jax.Array, state: jax.Array, tile_plan=None):
    """Single-token wkv (decode).  x: (B, 1, d)."""
    hd = cfg.rwkv.head_dim
    H = cfg.d_model // hd
    xs = prev[:, None, :]
    r, k, v, g, log_decay = _time_mix_inputs(params, x, xs, cfg)
    sq = lambda t: t[:, 0, :].reshape(t.shape[0], H, hd)
    u = params["bonus"].astype(F32).reshape(H, hd)
    y, new_state = linear_attention_step_planned(
        state, sq(r), sq(k), sq(v), sq(log_decay),
        u=u, tile_plan=tile_plan)
    y = y.reshape(x.shape[0], 1, cfg.d_model)
    y = groupnorm_heads(y.astype(x.dtype), params["wkv_norm"], H, cfg.norm_eps)
    out = dot(y * g, params["wo"])
    return out, x[:, 0, :], new_state


def channel_mix(params, x: jax.Array, cfg: ModelConfig, sharder, *,
                prev: Optional[jax.Array] = None,
                lengths: Optional[jax.Array] = None):
    """Squared-relu channel mix.  Returns (out, new_shift)."""
    xs = _shift_seq(x, prev)
    dx = xs - x
    xk = x + dx * params["mu_ck"].astype(x.dtype)
    xr = x + dx * params["mu_cr"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(dot(xk, params["wk_c"])))
    kk = sharder.constrain(kk, "batch", "seq", "mlp")
    r = jax.nn.sigmoid(dot(xr, params["wr_c"]))
    out = r * dot(kk, params["wv_c"])
    return out, _last_valid(x, lengths)


def rwkv_block(params, x: jax.Array, cfg: ModelConfig, sharder, *,
               mode: str, cache: Optional[Dict] = None,
               lengths: Optional[jax.Array] = None, tile_plan=None):
    """Full rwkv block.  Returns (x, new_cache).  ``lengths`` masks padded
    steps of a right-padded prefill batch (see time_mix).  ``tile_plan``
    (a ``tile_plans["rwkv"]`` entry) routes the decode step to the fused
    Pallas kernel with the DSE-chosen head tile."""
    if mode == "decode":
        h, tm_shift, state = time_mix_step(
            params, rmsnorm(x, params["ln1"], cfg.norm_eps), cfg, sharder,
            prev=cache["tm_shift"], state=cache["wkv_state"],
            tile_plan=tile_plan)
        x = x + h
        h, cm_shift = channel_mix(
            params, rmsnorm(x, params["ln2"], cfg.norm_eps), cfg, sharder,
            prev=cache["cm_shift"])
        x = x + h
        return x, {"wkv_state": state.astype(F32), "tm_shift": tm_shift,
                   "cm_shift": cm_shift}
    prev_tm = cache["tm_shift"] if cache else None
    prev_cm = cache["cm_shift"] if cache else None
    state = cache["wkv_state"] if cache else None
    h, tm_shift, state = time_mix(
        params, rmsnorm(x, params["ln1"], cfg.norm_eps), cfg, sharder,
        prev=prev_tm, state=state, lengths=lengths)
    x = x + h
    h, cm_shift = channel_mix(
        params, rmsnorm(x, params["ln2"], cfg.norm_eps), cfg, sharder,
        prev=prev_cm, lengths=lengths)
    x = x + h
    new_cache = {"wkv_state": state.astype(F32), "tm_shift": tm_shift,
                 "cm_shift": cm_shift}
    return x, new_cache
