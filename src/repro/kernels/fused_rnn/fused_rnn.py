"""Pallas TPU kernel: the paper's loop-based fused RNN cell.

The Plasticine mapping (paper §3.3/§4) translated to the TPU memory
hierarchy (DESIGN.md §Hardware-adaptation):

  Plasticine                         TPU (this kernel)
  ----------------------------------- -----------------------------------
  weights resident in PMU scratchpads  weight blocks resident in VMEM; with
                                       one H tile the index map is constant
                                       in t, so Pallas fetches each block
                                       from HBM once for all T steps (with
                                       several tiles, every step streams
                                       them)
  per-element LSTM-1 dataflow          per-tile fused dataflow: gate dots,
                                       scale/bias, nonlinearities, c/h
                                       update in one kernel body (VREGs)
  hu x ru spatial unrolling            grid dimension over H-tiles (bh) and
                                       the MXU's 128-lane parallelism (rv)
  8-bit multiply, 16/32-bit reduce     int8 weight storage, bf16 multiply,
                                       f32 MXU accumulation
  recurrent state in registers         h/c carried across grid steps in a
                                       VMEM scratch accumulator; h is
                                       double-buffered by t parity so later
                                       H-tiles of step t still read h_{t-1}

Grid: (T, H/bh), executed sequentially ("arbitrary" semantics) — t-major,
tile-minor, which is exactly the paper's loop nest in Fig. 5.

Weight layout: callers pass w_x (D, G, H), w_h (H, G, H) with gate order
(i, j, f, o) for LSTM, (r, z, n) for GRU, and per (gate, unit) scales
(G, H) as produced by ``repro.core.cells.quantize_weights``.  The kernel
reads them as (D, G*H) / (H, G*H) — gates folded into the lane axis — and
takes one (rows, bh) block per gate, so every weight block is lane-dense
and int8 blocks never pad a size-G sublane axis up to the 32-row int8
tile.  On a TPU, ``bh`` must be a multiple of 128 (``core.dse`` only
proposes such tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hw

F32 = jnp.float32


def _gates(x, h_prev, wx_refs, wh_refs, sx_ref, sh_ref):
    """Per-gate pre-activations: ((B,D)x(D,bh) * s_x, (B,H)x(H,bh) * s_h)
    for each gate, int8/bf16 weights widened to bf16, f32 accumulation."""
    xb = x.astype(jnp.bfloat16)
    hb = h_prev.astype(jnp.bfloat16)

    def mm(a, w_ref):
        return jax.lax.dot_general(
            a, w_ref[...].astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)

    zx = [mm(xb, r) * sx_ref[g:g + 1, :] for g, r in enumerate(wx_refs)]
    zh = [mm(hb, r) * sh_ref[g:g + 1, :] for g, r in enumerate(wh_refs)]
    return zx, zh


def _lstm_math(zx, zh, b_ref, c_old):
    z = [zx[g] + zh[g] + b_ref[g:g + 1, :] for g in range(4)]
    i = jax.nn.sigmoid(z[0])
    j = jnp.tanh(z[1])
    f = jax.nn.sigmoid(z[2])
    o = jax.nn.sigmoid(z[3])
    c_new = f * c_old + i * j
    return o * jnp.tanh(c_new), c_new


def _gru_math(zx, zh, bx_ref, bh_ref, h_old):
    zx = [zx[g] + bx_ref[g:g + 1, :] for g in range(3)]
    zh = [zh[g] + bh_ref[g:g + 1, :] for g in range(3)]
    r = jax.nn.sigmoid(zx[0] + zh[0])
    z = jax.nn.sigmoid(zx[1] + zh[1])
    n = jnp.tanh(zx[2] + r * zh[2])
    return (1 - z) * n + z * h_old


def _lstm_kernel(x_ref, *refs, bh: int):
    wx_refs, wh_refs = refs[0:4], refs[4:8]
    (sx_ref, sh_ref, b_ref, h0_ref, c0_ref, y_ref, hT_ref, cT_ref,
     h_scr, c_scr) = refs[8:]
    t = pl.program_id(0)
    hb = pl.program_id(1)
    T = pl.num_programs(0)

    @pl.when((t == 0) & (hb == 0))
    def _init():
        h_scr[0] = h0_ref[...].astype(F32)
        h_scr[1] = h0_ref[...].astype(F32)
        c_scr[...] = c0_ref[...].astype(F32)

    cur = jax.lax.rem(t, 2)
    zx, zh = _gates(x_ref[0], h_scr[cur], wx_refs, wh_refs, sx_ref, sh_ref)
    sl = pl.ds(pl.multiple_of(hb * bh, bh), bh)
    h_new, c_new = _lstm_math(zx, zh, b_ref, c_scr[:, sl])
    c_scr[:, sl] = c_new
    h_scr[1 - cur, :, sl] = h_new                          # next step's h
    y_ref[0] = h_new.astype(y_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)
        cT_ref[...] = c_new.astype(cT_ref.dtype)


def _gru_kernel(x_ref, *refs, bh: int):
    wx_refs, wh_refs = refs[0:3], refs[3:6]
    (sx_ref, sh_ref, bx_ref, bh_ref, h0_ref, y_ref, hT_ref,
     h_scr) = refs[6:]
    t = pl.program_id(0)
    hb = pl.program_id(1)
    T = pl.num_programs(0)

    @pl.when((t == 0) & (hb == 0))
    def _init():
        h_scr[0] = h0_ref[...].astype(F32)
        h_scr[1] = h0_ref[...].astype(F32)

    cur = jax.lax.rem(t, 2)
    zx, zh = _gates(x_ref[0], h_scr[cur], wx_refs, wh_refs, sx_ref, sh_ref)
    sl = pl.ds(pl.multiple_of(hb * bh, bh), bh)
    h_new = _gru_math(zx, zh, bx_ref, bh_ref, h_scr[cur, :, sl])
    h_scr[1 - cur, :, sl] = h_new
    y_ref[0] = h_new.astype(y_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)


def _lstm_kernel_persistent(x_ref, *refs):
    """Persistent-decode variant (Sparse Persistent RNNs): the whole
    weight matrices live in VMEM for the full device loop — grid is (T,)
    only, there is no H-tile streaming and no double-buffered h parity.
    Requires the DSE to certify the weights fit (tile_vmem_bytes at
    bh == H); math is bit-identical to the streaming kernel at bh == H."""
    wx_refs, wh_refs = refs[0:4], refs[4:8]
    (sx_ref, sh_ref, b_ref, h0_ref, c0_ref, y_ref, hT_ref, cT_ref,
     h_scr, c_scr) = refs[8:]
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(F32)
        c_scr[...] = c0_ref[...].astype(F32)

    zx, zh = _gates(x_ref[0], h_scr[...], wx_refs, wh_refs, sx_ref, sh_ref)
    h_new, c_new = _lstm_math(zx, zh, b_ref, c_scr[...])
    c_scr[...] = c_new
    h_scr[...] = h_new
    y_ref[0] = h_new.astype(y_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)
        cT_ref[...] = c_new.astype(cT_ref.dtype)


def _gru_kernel_persistent(x_ref, *refs):
    wx_refs, wh_refs = refs[0:3], refs[3:6]
    (sx_ref, sh_ref, bx_ref, bh_ref, h0_ref, y_ref, hT_ref,
     h_scr) = refs[6:]
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(F32)

    h_prev = h_scr[...]
    zx, zh = _gates(x_ref[0], h_prev, wx_refs, wh_refs, sx_ref, sh_ref)
    h_new = _gru_math(zx, zh, bx_ref, bh_ref, h_prev)
    h_scr[...] = h_new
    y_ref[0] = h_new.astype(y_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)


def _call(kernel, args, *, G: int, bh: int, persistent: bool,
          n_state: int, interpret: bool, name: str):
    """The pallas_call shared by both cells and both variants.

    ``args`` = (x_seq, w_x, w_h, s_x, s_h, *biases, *states) with the
    weights in the callers' (rows, G, H) layout.  Weight index maps are
    constant in t, so with a single H tile (always, in the persistent
    grid) each weight block is fetched once, stays VMEM-resident across
    every time step (the paper's on-chip-weights requirement) and is
    single-buffered."""
    x_seq, w_x, w_h = args[:3]
    T, B, D = x_seq.shape
    H = w_h.shape[0]
    nt = H // bh
    if persistent:
        grid, sem = (T,), ("arbitrary",)
        ix = lambda f: (lambda t: f(t, 0))
    else:
        grid, sem = (T, nt), ("arbitrary", "arbitrary")
        ix = lambda f: f
    once = dict(pipeline_mode=pl.Buffered(1)) if nt == 1 else {}

    def wspec(rows, g):
        return pl.BlockSpec((rows, bh), ix(lambda t, h: (0, g * nt + h)),
                            **once)

    vec = pl.BlockSpec((G, bh), ix(lambda t, h: (0, h)))
    state = pl.BlockSpec((B, H), ix(lambda t, h: (0, 0)))
    n_vec = len(args) - 3 - n_state
    in_specs = ([pl.BlockSpec((1, B, D), ix(lambda t, h: (t, 0, 0)))]
                + [wspec(D, g) for g in range(G)]
                + [wspec(H, g) for g in range(G)]
                + [vec] * n_vec + [state] * n_state)
    out_specs = ([pl.BlockSpec((1, B, bh), ix(lambda t, h: (t, 0, h)))]
                 + [pl.BlockSpec((B, bh), ix(lambda t, h: (0, h)))]
                 * n_state)
    out_shape = ([jax.ShapeDtypeStruct((T, B, H), jnp.bfloat16)]
                 + [jax.ShapeDtypeStruct((B, H), F32)] * n_state)
    scratch = ([pltpu.VMEM((B, H) if persistent else (2, B, H), F32)]
               + [pltpu.VMEM((B, H), F32)] * (n_state - 1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=sem, vmem_limit_bytes=hw.vmem_budget()),
        interpret=interpret,
        name=name,
    )(x_seq, *([w_x.reshape(D, G * H)] * G),
      *([w_h.reshape(H, G * H)] * G), *args[3:])


def _tile(H: int, bh: int, persistent: bool) -> int:
    if persistent:
        return H
    bh = min(bh, H)
    assert H % bh == 0, (H, bh)
    return bh


@functools.partial(jax.jit, static_argnames=("bh", "interpret",
                                             "persistent"))
def fused_lstm(x_seq, w_x, w_h, s_x, s_h, b, h0, c0, *,
               bh: int = 256, interpret: bool = False,
               persistent: bool = False):
    """x_seq (T, B, D); w_x (D, 4, H) int8/bf16; s_* (4, H) f32; b (4, H);
    h0/c0 (B, H).  Returns (y (T, B, H) bf16, h_T (B, H) f32, c_T).

    ``bh`` is the H-tile (default 256 — the pre-DSE hardcoded geometry);
    ``persistent=True`` switches to the weights-resident variant (grid
    (T,) only, whole matrices pinned in VMEM — caller must have checked
    ``dse.tile_vmem_bytes(cfg, H)`` against the budget)."""
    H = w_h.shape[0]
    bh = _tile(H, bh, persistent)
    kernel = (_lstm_kernel_persistent if persistent
              else functools.partial(_lstm_kernel, bh=bh))
    return _call(kernel, (x_seq, w_x, w_h, s_x, s_h, b, h0, c0), G=4,
                 bh=bh, persistent=persistent, n_state=2,
                 interpret=interpret,
                 name="fused_lstm_persistent" if persistent
                 else "fused_lstm")


@functools.partial(jax.jit, static_argnames=("bh", "interpret",
                                             "persistent"))
def fused_gru(x_seq, w_x, w_h, s_x, s_h, b_x, b_h, h0, *,
              bh: int = 256, interpret: bool = False,
              persistent: bool = False):
    """x_seq (T, B, D); w_x (D, 3, H); s_* (3, H); b_* (3, H); h0 (B, H).
    Returns (y (T, B, H) bf16, h_T (B, H) f32).  See ``fused_lstm`` for
    the ``bh``/``persistent`` contract."""
    H = w_h.shape[0]
    bh = _tile(H, bh, persistent)
    kernel = (_gru_kernel_persistent if persistent
              else functools.partial(_gru_kernel, bh=bh))
    return _call(kernel, (x_seq, w_x, w_h, s_x, s_h, b_x, b_h, h0), G=3,
                 bh=bh, persistent=persistent, n_state=1,
                 interpret=interpret,
                 name="fused_gru_persistent" if persistent
                 else "fused_gru")
