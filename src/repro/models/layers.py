"""Shared neural-net layers: norms, rotary embeddings (incl. M-RoPE),
gated/non-gated MLPs, embedding / logit head.

Everything is a pure function over an explicit params dict; parameter
shapes/logical axes come from the matching ``*_specs`` function.  Matmuls
accumulate in f32 (``preferred_element_type``) regardless of the bf16
compute dtype, mirroring the paper's narrow-multiply / wide-accumulate
mixed-precision scheme at the XLA level.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec

F32 = jnp.float32
# the activations' dtype (``embed``'s output), at which every matmul runs
COMPUTE_DTYPE = jnp.bfloat16


def wcast(w, dtype) -> jax.Array:
    """Weight view: plain array -> cast; int8-quantized dict -> dequantize.

    The paper's mixed-precision scheme at the XLA level: weights may be
    *stored* int8 (HBM reads halve) and are widened at the consuming
    matmul.  A convert inside a program is not free: on TPU v5e, XLA
    hoists the convert of an f32 weight out of the layer scan and writes
    the whole bf16 copy once per program call, at HBM speed, so that a
    decode tick spent half its device time rounding weights.  The served
    path therefore reads bf16 weights: ``ServingEngine`` rounds them once,
    at construction (``LM.serving_params``), and the ``astype`` here is a
    no-op on such a leaf."""
    if isinstance(w, dict):
        return (w["q"].astype(F32) * w["scale"].astype(F32)).astype(dtype)
    return w.astype(dtype)


def dot(x: jax.Array, w) -> jax.Array:
    """x @ w with f32 accumulation, result cast back to x.dtype."""
    w = wcast(w, x.dtype)
    y = jax.lax.dot_general(
        x, w,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=F32,
    )
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_specs(d: int, name_axes: Tuple = (None,)) -> ParamSpec:
    return ParamSpec((d,), jnp.float32, name_axes, init="zeros")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with (1 + scale) parametrization (gemma/llama style)."""
    dtype = x.dtype
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(F32))).astype(dtype)


def groupnorm_heads(x: jax.Array, scale: jax.Array, n_heads: int,
                    eps: float = 1e-6) -> jax.Array:
    """Per-head RMS normalization of a (..., n_heads * head_dim) tensor
    (RWKV's wkv output GroupNorm / gemma3 qk-norm building block)."""
    dtype = x.dtype
    *lead, d = x.shape
    x = x.reshape(*lead, n_heads, d // n_heads).astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    y = y.reshape(*lead, d)
    return (y * (1.0 + scale.astype(F32))).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Standard RoPE.  x: (B, S, H, D), positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)                  # (D/2,)
    angles = positions.astype(F32)[..., None] * freqs             # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(x: jax.Array, positions: jax.Array, theta: float,
                 sections: Tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions: (B, 3, S) — (temporal, height, width) streams.
    ``sections`` splits the D/2 rotary pairs across the three streams.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_frequencies(x.shape[-1], theta)                  # (D/2,)
    # angle per stream: (B, 3, S, D/2)
    angles = positions.astype(F32)[..., None] * freqs
    # select the stream each rotary-pair section listens to
    stream_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=half)  # (D/2,)
    select = jax.nn.one_hot(stream_id, 3, dtype=F32).T                 # (3, D/2)
    angles = jnp.einsum("bksd,kd->bsd", angles, select)                # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu_sq": lambda x: jnp.square(jax.nn.relu(x)),
}


def mlp_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    specs = {
        "w_up": ParamSpec((d, f), jnp.float32, ("embed", "mlp"),
                          compute_only=True),
        "w_down": ParamSpec((f, d), jnp.float32, ("mlp", "embed"),
                            compute_only=True),
    }
    if cfg.mlp_gated:
        specs["w_gate"] = ParamSpec((d, f), jnp.float32, ("embed", "mlp"),
                                    compute_only=True)
    return specs


def mlp(params: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
        sharder) -> jax.Array:
    act = _ACTS[cfg.mlp_act]
    up = dot(x, params["w_up"])
    if cfg.mlp_gated:
        gate = dot(x, params["w_gate"])
        h = act(gate) * up
    else:
        h = act(up)
    h = sharder.constrain(h, "batch", "seq", "mlp")
    return dot(h, params["w_down"])


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    v, d = cfg.padded_vocab, cfg.d_model
    specs = {"embedding": ParamSpec((v, d), jnp.float32, ("vocab", "embed"),
                                    scale=1.0, compute_only=True)}
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, v), jnp.float32, ("embed", "vocab"),
                                     compute_only=True)
    return specs


def embed(params, tokens: jax.Array, cfg: ModelConfig, sharder) -> jax.Array:
    x = params["embedding"].astype(COMPUTE_DTYPE)[tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return sharder.constrain(x, "batch", "seq", None)


def unembed(params, x: jax.Array, cfg: ModelConfig, sharder) -> jax.Array:
    """Final logits (f32)."""
    if cfg.tie_embeddings:
        w = wcast(params["embedding"], x.dtype).T
    else:
        w = wcast(params["lm_head"], x.dtype)
    # logits stay vocab-sharded even under sequence parallelism: gathering
    # the (small) hidden beats all-reducing the (huge) logits in bwd
    x = sharder.constrain(x, "batch", "logit_seq", None)
    logits = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=F32)
    if cfg.final_softcap > 0.0:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return sharder.constrain(logits, "batch", "logit_seq", "vocab")
