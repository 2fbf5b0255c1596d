"""SSD-form selective state-space block (hymba's mamba heads).

Hardware adaptation (DESIGN.md §Hardware-adaptation): Mamba1's per-(channel,
state) decay matrix A[d, n] admits no TPU-friendly parallel form without
materializing a (T, d_inner, d_state) tensor.  We use the Mamba2/SSD
restriction — scalar decay per head, state (head_dim x d_state) — which
reduces exactly to scalar-decay chunked linear attention with
q = C_t, k = B_t, v = dt_t * x_t.  hymba's ssm_state=16 is preserved.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dot, groupnorm_heads
from repro.models.params import ParamSpec
from repro.models.recurrence import chunked_linear_attention, linear_attention_step

F32 = jnp.float32


def _d_inner(cfg: ModelConfig) -> int:
    return cfg.d_model * cfg.ssm.expand


def _n_ssm_heads(cfg: ModelConfig) -> int:
    return _d_inner(cfg) // cfg.ssm.head_dim


def ssm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, s = cfg.d_model, cfg.ssm
    di, nh = _d_inner(cfg), _n_ssm_heads(cfg)
    return {
        "w_in": ParamSpec((d, 2 * di), jnp.float32, ("embed", "ssm_inner"),
                          compute_only=True),
        "conv_kernel": ParamSpec((s.conv_width, di), jnp.float32,
                                 (None, "ssm_inner"), scale=0.5),
        "conv_bias": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="zeros"),
        "w_bc": ParamSpec((di, 2 * s.d_state), jnp.float32, ("ssm_inner", None),
                          compute_only=True),
        "w_dt": ParamSpec((d, nh), jnp.float32, ("embed", None)),
        "dt_bias": ParamSpec((nh,), jnp.float32, (None,), init="custom",
                             custom_init=_dt_bias_init),
        "a_log": ParamSpec((nh,), jnp.float32, (None,), init="custom",
                           custom_init=_a_log_init),
        "d_skip": ParamSpec((nh,), jnp.float32, (None,), init="ones"),
        "ssm_norm": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="zeros"),
        "w_out": ParamSpec((di, d), jnp.float32, ("ssm_inner", "embed"),
                           compute_only=True),
    }


def _dt_bias_init(key, spec):
    # softplus^-1 of dt in [1e-3, 1e-1], log-spaced (mamba init)
    n = spec.shape[0]
    dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), n))
    return jnp.log(jnp.expm1(dt)).astype(spec.dtype)


def _a_log_init(key, spec):
    n = spec.shape[0]
    return jnp.log(jnp.linspace(1.0, 16.0, n)).astype(spec.dtype)


def _causal_depthwise_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array,
                           tail: Optional[jax.Array]) -> jax.Array:
    """Depthwise causal conv along time via shifted adds (no conv primitive).

    x: (B, T, di); kernel: (W, di); tail: (B, W-1, di) previous inputs."""
    W = kernel.shape[0]
    pad = (jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
           if tail is None else tail.astype(x.dtype))
    xp = jnp.concatenate([pad, x], axis=1)                # (B, T+W-1, di)
    out = jnp.zeros_like(x)
    T = x.shape[1]
    for w in range(W):
        out = out + xp[:, w:w + T, :] * kernel[w].astype(x.dtype)
    return out + bias.astype(x.dtype)


def _ssm_inputs(params, x: jax.Array, cfg: ModelConfig, conv_tail,
                lengths: Optional[jax.Array] = None):
    """Shared train/decode input computation.

    Returns (q, k, v, log_decay, x_heads, z, new_conv_tail).

    ``lengths`` (B,) marks true per-example lengths in a right-padded
    prefill batch; the conv tail is then gathered at the last valid
    positions (zeros before t=0, matching the causal-conv zero padding).
    Only supported for fresh prefills (conv_tail None)."""
    s = cfg.ssm
    di, nh = _d_inner(cfg), _n_ssm_heads(cfg)
    B, T, _ = x.shape
    xz = dot(x, params["w_in"])
    xi, z = jnp.split(xz, 2, axis=-1)
    xc = _causal_depthwise_conv(xi, params["conv_kernel"], params["conv_bias"],
                                conv_tail)
    if lengths is not None:
        w1 = s.conv_width - 1
        src = (lengths[:, None] - w1
               + jnp.arange(w1, dtype=jnp.int32)[None, :])       # (B, W-1)
        tail = jnp.take_along_axis(xi, jnp.maximum(src, 0)[:, :, None],
                                   axis=1)
        new_tail = jnp.where((src >= 0)[:, :, None], tail, 0.0)
    else:
        new_tail = (jnp.concatenate([conv_tail.astype(x.dtype), xi], axis=1)
                    [:, -(s.conv_width - 1):, :]
                    if conv_tail is not None
                    else xi[:, -(s.conv_width - 1):, :])
    xc = jax.nn.silu(xc)
    bc = dot(xc, params["w_bc"]).astype(F32)
    b_t, c_t = jnp.split(bc, 2, axis=-1)                  # (B,T,N) each
    dt = jax.nn.softplus(
        jax.lax.dot_general(x.astype(F32), params["w_dt"].astype(F32),
                            (((2,), (0,)), ((), ()))) +
        params["dt_bias"].astype(F32))                     # (B,T,nh)
    log_decay = -jnp.exp(params["a_log"].astype(F32)) * dt  # (B,T,nh) <= 0
    xh = xc.reshape(B, T, nh, s.head_dim)
    v = xh.astype(F32) * dt[..., None]                     # (B,T,nh,hd)
    # broadcast shared B/C across heads: (B, nh, T, N)
    q = jnp.repeat(c_t[:, None], nh, axis=1)              # (B,nh,T,N)
    k = jnp.repeat(b_t[:, None], nh, axis=1)
    vv = v.transpose(0, 2, 1, 3)                           # (B,nh,T,hd)
    ld = log_decay.transpose(0, 2, 1)[..., None]           # (B,nh,T,1)
    return q, k, vv, ld, xh, z, new_tail


def _finish(params, y: jax.Array, xh: jax.Array, z: jax.Array,
            cfg: ModelConfig) -> jax.Array:
    """y: (B,nh,T,hd) -> gated, normed, projected out (B,T,d)."""
    B, nh, T, hd = y.shape
    y = y + params["d_skip"].astype(F32)[None, :, None, None] * \
        xh.transpose(0, 2, 1, 3).astype(F32)
    y = y.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
    y = groupnorm_heads(y.astype(z.dtype), params["ssm_norm"], nh, cfg.norm_eps)
    y = y * jax.nn.silu(z)
    return dot(y, params["w_out"])


def ssm_mixer(params, x: jax.Array, cfg: ModelConfig, sharder, *,
              mode: str, cache: Optional[Dict] = None,
              lengths: Optional[jax.Array] = None):
    """SSD mixer.  x: (B, T, d).  Returns (out (B,T,d), new_cache).

    ``lengths`` masks padded steps of a right-padded prefill batch: padded
    steps get (decay 1, k 0) so the ssd_state carries through unchanged."""
    s = cfg.ssm
    if mode == "decode":
        conv_tail, state = cache["conv_state"], cache["ssd_state"]
        q, k, v, ld, xh, z, new_tail = _ssm_inputs(params, x, cfg, conv_tail)
        y, new_state = linear_attention_step(
            state, q[:, :, 0], k[:, :, 0], v[:, :, 0], ld[:, :, 0],
            convention="inclusive")
        y = y[:, :, None, :]                               # (B,nh,1,hd)
        out = _finish(params, y, xh, z, cfg)
        return out, {"conv_state": new_tail, "ssd_state": new_state.astype(F32)}

    conv_tail = cache["conv_state"] if cache else None
    state = cache["ssd_state"] if cache else None
    q, k, v, ld, xh, z, new_tail = _ssm_inputs(params, x, cfg, conv_tail,
                                               lengths=lengths)
    if lengths is not None:
        valid = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, None, :, None]
                 < lengths[:, None, None, None])                 # (B,1,T,1)
        k = jnp.where(valid, k, 0.0)
        ld = jnp.where(valid, ld, 0.0)
    y, new_state = chunked_linear_attention(
        q, k, v, ld, chunk=min(s.chunk, x.shape[1]),
        convention="inclusive", initial_state=state)
    out = _finish(params, y, xh, z, cfg)
    new_cache = {"conv_state": new_tail, "ssd_state": new_state.astype(F32)}
    return out, new_cache
