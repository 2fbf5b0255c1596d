"""Plain float32 reference of an RWKV-6 language model ("Finch",
arXiv:2404.05892), in the parameter layout the harness makes.

Written from the architecture's equations, with nothing imported from the
program under test: every matrix product at ``Precision.HIGHEST``, the
residual stream in f32, and the wkv recurrence as a step-by-step scan

    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T,   w_t = -exp(clip(w0 + dd_t))

Token shift, the data-dependent LoRA mix of the five streams (w, k, v, r,
g), the per-head normalisation of the wkv output and the squared-ReLU
channel mix follow the same paper; norms scale by (1 + gain).

The control (``lowp="float8_e4m3fn"``) is this reference with both
operands of every matrix product rounded to float8 e4m3, each scaled by
its largest magnitude along the contraction axis: the precision step
below the configuration's bfloat16.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
N_MIX, LORA = 5, 32
POS_BLOCK = 256            # positions per logits block
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


def shift(x):
    """x_{t-1} along axis 1, zeros at t = 0."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, logw, u):
    """r, k, logw (B, T, H, K); v (B, T, H, V); u (H, K) -> (B, T, H, V)."""
    B, T, H, K = r.shape

    def step(S, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.sum(rt[..., :, None] * (S + u[None, :, :, None] * kv),
                    axis=-2)
        return jnp.exp(wt)[..., None] * S + kv, y

    t_major = lambda a: jnp.moveaxis(a, 1, 0)
    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, ys = jax.lax.scan(step, S0, (t_major(r), t_major(k), t_major(v),
                                    t_major(logw)))
    return jnp.moveaxis(ys, 0, 1)


def layer(p, x, m, lowp=None):
    """One RWKV-6 block on x (B, T, d)."""
    B, T, d = x.shape
    hd, eps = m["head_dim"], m["norm_eps"]
    H = d // hd
    # time mix
    h = rmsnorm(x, p["ln1"], eps)
    dx = shift(h) - h
    lora = jnp.tanh(mm(h + dx * p["mu_base"], p["lora_a"], lowp))
    lora = lora.reshape(B, T, N_MIX, LORA)
    mix = p["mu"] + jnp.einsum("btnr,nrd->btnd",
                               lowp_round(lora, -1, lowp),
                               lowp_round(p["lora_b"], 1, lowp),
                               precision=HIGHEST)
    xw, xk, xv, xr, xg = [h + dx * mix[:, :, i] for i in range(N_MIX)]
    r, k, v = mm(xr, p["wr"], lowp), mm(xk, p["wk"], lowp), mm(xv, p["wv"],
                                                               lowp)
    g = jax.nn.silu(mm(xg, p["wg"], lowp))
    dd = mm(jnp.tanh(mm(xw, p["decay_a"], lowp)), p["decay_b"], lowp)
    logw = -jnp.exp(jnp.clip(p["decay_base"] + dd, -8.0, 3.0))
    heads = lambda a: a.reshape(B, T, H, hd)
    y = wkv(heads(r), heads(k), heads(v), heads(logw),
            p["bonus"].reshape(H, hd))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(B, T, d) * (1.0 + p["wkv_norm"])
    x = x + mm(y * g, p["wo"], lowp)
    # channel mix
    h = rmsnorm(x, p["ln2"], eps)
    dx = shift(h) - h
    kk = jnp.square(jax.nn.relu(mm(h + dx * p["mu_ck"], p["wk_c"], lowp)))
    rr = jax.nn.sigmoid(mm(h + dx * p["mu_cr"], p["wr_c"], lowp))
    return x + rr * mm(kk, p["wv_c"], lowp)


def _layer_fn(m, lowp):
    def f(blocks, i, x):
        p = jax.tree.map(lambda a: a[i], blocks["p0"])
        return layer(p, x, m, lowp)
    return jax.jit(f)


def _head_fn(m, lowp_control):
    """Per position: the reference's best logit minus the target's logit
    and, with a control hidden state, minus the logit of the token the
    control puts first."""

    def logits(params, x, lowp):
        w = params["lm_head"] if "lm_head" in params else \
            params["embedding"].T
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
    ``LEN_STEP`` positions."""
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
        tokens = np.zeros((block, T), np.int32)
        target = np.full((block, T), -1, np.int32)
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
