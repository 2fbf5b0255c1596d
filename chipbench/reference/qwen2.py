"""Plain float32 reference of a Qwen2 language model (Qwen2.5 Technical
Report, arXiv:2412.15115; the ``Qwen2ForCausalLM`` equations of the
model card's code), in the parameter layout the harness makes.

Written from the architecture's equations, with nothing imported from the
program under test: every matrix product at ``Precision.HIGHEST`` and the
residual stream in f32.  Per layer, on x (B, T, d):

    h = RMSNorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = RoPE(q), RoPE(k)      rotate-half, theta from the config
    a = softmax(q k^T / sqrt(hd) + causal) v    GQA: query head j reads
                                                 kv head j // (H / K)
    x = x + a Wo
    x = x + (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wu)) Wd

then logits = RMSNorm(x) W_head with an untied head.  RMSNorm scales by
(1 + gain), the layout's form of the published weight.  Layers run one
at a time, each upcasting only its own weights to f32, so the reference
fits beside the bf16 weights of a 14B model; attention runs over blocks
of queries, so no (T, T) score matrix per head exists whole.

The control (``lowp="float8_e4m3fn"``) is this reference with both
operands of every matrix product (the projections and the two attention
products) rounded to float8 e4m3, each scaled by its largest magnitude
along the contraction axis: the precision step below the configuration's
bfloat16.  The rounding and the gap summary follow ``reference/rwkv6.py``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
POS_BLOCK = 256            # positions per logits block
Q_BLOCK = 256              # queries per attention block
LEN_STEP = 512             # sequences are padded to a multiple of this


def lowp_round(a: jax.Array, axis: int, lowp: Optional[str]) -> jax.Array:
    """``a`` rounded to ``lowp`` with a scale per slice along ``axis``."""
    if not lowp:
        return a
    dtype = jnp.dtype(lowp)
    top = float(jnp.finfo(dtype).max)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(dtype).astype(F32) * s


def mm(a, w, lowp=None):
    """a (..., k) @ w (k, n) in f32."""
    return jnp.einsum("...k,kn->...n", lowp_round(a, -1, lowp),
                      lowp_round(w, 0, lowp), precision=HIGHEST)


def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def rope(x, theta: float):
    """Rotate-half RoPE on x (B, T, heads, hd) at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, lowp=None):
    """Causal GQA.  q (B, T, H, hd); k, v (B, T, K, hd) -> (B, T, H, hd);
    T a multiple of ``Q_BLOCK``."""
    B, T, H, hd = q.shape
    K = k.shape[2]
    qg = q.reshape(B, T // Q_BLOCK, Q_BLOCK, K, H // K, hd)
    kr, vr = lowp_round(k, -1, lowp), lowp_round(v, 1, lowp)

    def block(i):
        qb = lowp_round(qg[:, i], -1, lowp)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, kr,
                        precision=HIGHEST) / np.sqrt(hd)
        causal = (jnp.arange(T)[None, :]
                  <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None])
        p = jax.nn.softmax(jnp.where(causal, sc, NEG_INF), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", lowp_round(p, -1, lowp), vr,
                          precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)


def layer(p, x, m, lowp=None):
    """One Qwen2 decoder layer on x (B, T, d)."""
    B, T, _ = x.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    a = p["attn"]
    h = rmsnorm(x, p["norm1"], eps)
    q = (mm(h, a["wq"], lowp) + a["bq"]).reshape(B, T, H, hd)
    k = (mm(h, a["wk"], lowp) + a["bk"]).reshape(B, T, K, hd)
    v = (mm(h, a["wv"], lowp) + a["bv"]).reshape(B, T, K, hd)
    theta = m["rope_theta"]
    o = attention(rope(q, theta), rope(k, theta), v, lowp)
    x = x + mm(o.reshape(B, T, H * hd), a["wo"], lowp)
    f = p["mlp"]
    h = rmsnorm(x, p["norm2"], eps)
    g = jax.nn.silu(mm(h, f["w_gate"], lowp)) * mm(h, f["w_up"], lowp)
    return x + mm(g, f["w_down"], lowp)


def _layer_fn(m, lowp):
    def f(blocks, i, x):
        p = jax.tree.map(lambda a: a[i].astype(F32), blocks["p0"])
        return layer(p, x, m, lowp)
    return jax.jit(f)


def _head_fn(m, lowp_control):
    """Per position: the reference's best logit minus the target's logit
    and, with a control hidden state, minus the logit of the token the
    control puts first."""

    def logits(params, x, lowp):
        w = params["lm_head"].astype(F32)
        return mm(rmsnorm(x, params["final_norm"], m["norm_eps"]), w, lowp)

    def f(params, x, xc, target):
        ref = logits(params, x, None)
        best = jnp.max(ref, -1)
        safe = jnp.maximum(target, 0)
        got = jnp.take_along_axis(ref, safe[..., None], -1)[..., 0]
        served = jnp.where(target >= 0, best - got, -jnp.inf)
        if xc is None:
            return served, served
        pick = jnp.argmax(logits(params, xc, lowp_control), -1)
        picked = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        return served, jnp.where(target >= 0, best - picked, -jnp.inf)

    return jax.jit(f)


def served_gaps(params, m: Dict, seqs: Sequence[Tuple[List[int], List[int]]],
                control: Optional[str] = None, block: int = 4) -> Dict:
    """Over every served token of ``seqs`` (prompt, served tokens): the
    gap between the reference's best logit and the served token's, as its
    mean (``served``), its widest (``served_widest``) and the share of
    positions where it is above 0 (``served_off_pct``); with ``control``,
    the same of the token that the control precision puts first.
    Sequences run ``block`` at a time, each block padded to a multiple of
    ``LEN_STEP`` positions (padding follows every real position, so the
    causal mask keeps it out)."""
    blocks = params["blocks"]
    n_layers = jax.tree.leaves(blocks)[0].shape[0]
    f_ref = _layer_fn(m, None)
    f_ctl = _layer_fn(m, control) if control else None
    head = _head_fn(m, control)
    order = sorted(range(len(seqs)), key=lambda i: -(len(seqs[i][0]) +
                                                     len(seqs[i][1])))
    gaps, ctl = [], []
    for s in range(0, len(order), block):
        group = [seqs[i] for i in order[s:s + block]]
        T = max(len(p) + len(o) - 1 for p, o in group)
        T = -(-T // LEN_STEP) * LEN_STEP
        tokens = np.zeros((len(group), T), np.int32)
        target = np.full((len(group), T), -1, np.int32)
        for b, (p, o) in enumerate(group):
            full = list(p) + list(o)
            tokens[b, :len(full) - 1] = full[:-1]
            target[b, len(p) - 1:len(full) - 1] = o
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        xc = x
        for i in range(n_layers):
            x = f_ref(blocks, i, x)
            if f_ctl is not None:
                xc = f_ctl(blocks, i, xc)
        for c in range(0, T, POS_BLOCK):
            sl = slice(c, c + POS_BLOCK)
            g, gc = head(params, x[:, sl], xc[:, sl] if control else None,
                         jnp.asarray(target[:, sl]))
            keep = target[:, sl] >= 0
            gaps.append(np.asarray(g)[keep])
            ctl.append(np.asarray(gc)[keep])
    out = _summary("served", np.concatenate(gaps))
    if control:
        out.update(_summary("control", np.concatenate(ctl)))
    return out


def _summary(name: str, gaps: np.ndarray) -> Dict:
    return {name: float(gaps.mean()), f"{name}_widest": float(gaps.max()),
            f"{name}_off_pct": float(100.0 * (gaps > 0).mean()),
            "positions": int(gaps.size)}
