"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles,
interpret=True on CPU (assignment deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cells import RNNCellConfig, init_weights, quantize_weights
from repro.core.quant import quantize_int8
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_rnn import ops as rnn_ops
from repro.kernels.fused_rnn import ref as rnn_ref
from repro.kernels.matmul_int8.matmul_int8 import matmul_w8a16
from repro.kernels.matmul_int8.ref import matmul_w8a16_ref


# ---------------------------------------------------------------------------
# fused RNN
# ---------------------------------------------------------------------------

RNN_SWEEP = [
    ("lstm", 128, 1, 4, "int8", 64),
    ("lstm", 256, 2, 3, "int8", 128),
    ("lstm", 256, 1, 3, "bf16", 256),
    ("lstm", 512, 4, 2, "int8", 128),
    ("gru", 128, 1, 4, "int8", 128),
    ("gru", 256, 2, 3, "bf16", 64),
    ("gru", 512, 1, 2, "int8", 512),
]


@pytest.mark.parametrize("cell,H,B,T,prec,bh", RNN_SWEEP)
def test_fused_rnn_vs_ref(cell, H, B, T, prec, bh):
    cfg = RNNCellConfig(cell, H, timesteps=T, batch=B, precision=prec)
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (T, B, cfg.d), jnp.bfloat16)
    y = rnn_ops.serve(cfg, w, x, bh=bh, interpret=True)
    wx, wh, sx, sh = rnn_ops._weights_for_kernel(cfg, w)
    h0 = jnp.zeros((B, H))
    if cell == "lstm":
        y_ref, _, _ = rnn_ref.fused_lstm_ref(x, wx, wh, sx, sh, w["b"], h0, h0)
    else:
        y_ref, _ = rnn_ref.fused_gru_ref(
            x, wx, wh, sx, sh, w["b"], w.get("b_h", jnp.zeros_like(w["b"])), h0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_fused_lstm_state_carry():
    """Final (h, c) outputs equal the oracle's final state."""
    cfg = RNNCellConfig("lstm", 128, timesteps=6, batch=2, precision="bf16")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(2)))
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 2, 128), jnp.bfloat16)
    wx, wh, sx, sh = rnn_ops._weights_for_kernel(cfg, w)
    from repro.kernels.fused_rnn.fused_rnn import fused_lstm
    h0 = jnp.zeros((2, 128))
    y, hT, cT = fused_lstm(x, wx, wh, sx, sh, w["b"], h0, h0, bh=64,
                           interpret=True)
    y_ref, hT_ref, cT_ref = rnn_ref.fused_lstm_ref(x, wx, wh, sx, sh,
                                                   w["b"], h0, h0)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(cT_ref),
                               atol=1e-2, rtol=1e-2)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_SWEEP = [
    (1, 2, 256, 64, True, 0, 0.0, jnp.bfloat16),
    (2, 1, 256, 64, True, 64, 0.0, jnp.bfloat16),
    (1, 2, 512, 128, True, 0, 50.0, jnp.bfloat16),
    (1, 1, 256, 64, False, 0, 0.0, jnp.bfloat16),
    (1, 2, 256, 64, True, 0, 0.0, jnp.float32),
]


@pytest.mark.parametrize("B,H,S,d,causal,win,cap,dtype", FLASH_SWEEP)
def test_flash_attention_vs_ref(B, H, S, d, causal, win, cap, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win, softcap=cap,
                          bq=128, bk=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# W8A16 matmul
# ---------------------------------------------------------------------------

MM_SWEEP = [
    (128, 256, 512, "none", None),
    (256, 512, 256, "silu", True),
    (128, 128, 128, "gelu", True),
    (512, 256, 128, "relu", None),
]


@pytest.mark.parametrize("M,K,N,act,with_bias", MM_SWEEP)
def test_matmul_w8a16_vs_ref(M, K, N, act, with_bias):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) / np.sqrt(K)
    wq, sc = quantize_int8(w, axis=0)
    b = (jax.random.normal(jax.random.fold_in(key, 2), (N,)) * 0.1
         if with_bias else None)
    out = matmul_w8a16(x, wq, sc[0], b, act=act, bm=128, bn=128, bk=128,
                       interpret=True)
    ref = matmul_w8a16_ref(x, wq, sc[0], b, act=act)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# PR 9: plan-driven tiles, persistent fused decode, split-KV flash-decoding
# ---------------------------------------------------------------------------


def test_default_bh_is_batch_aware():
    """Regression pin: the serving tile must be scored at the *served*
    batch.  lstm H=4096 (bf16) wants bh=512 single-lane but the smaller
    bh=256 tile once 256 slots of state/io claim their VMEM share — the
    old code passed no max_batch and silently served the b=1 tile."""
    from repro.core.dse import best_plan
    cfg = RNNCellConfig("lstm", 4096, precision="bf16")
    assert rnn_ops.default_bh(cfg, 1) == best_plan(cfg, max_batch=1).bh == 512
    assert rnn_ops.default_bh(cfg, 256) == 256
    assert rnn_ops.default_bh(cfg, 256) != best_plan(cfg).bh


def test_fused_rnn_plan_tile_sweep():
    """serve() under every candidate tile (plus non-divisor plan tiles,
    which must snap) matches the bh=H run bitwise — tiling the H axis
    never changes a single output bit."""
    from repro.core.dse import candidate_tiles
    cfg = RNNCellConfig("gru", 64, timesteps=3, batch=2, precision="bf16")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(4)))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 2, cfg.d), jnp.bfloat16)
    base = np.asarray(rnn_ops.serve(cfg, w, x, bh=64, interpret=True))
    for bh in candidate_tiles(64) + [48, 100]:   # 48, 100 snap to 32, 64
        y = rnn_ops.serve(cfg, w, x, interpret=True, plan={"bh": bh})
        assert (np.asarray(y) == base).all(), bh


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_fused_rnn_persistent_parity(cell, prec):
    """The persistent (weights-VMEM-resident) decode variant is the same
    math as the streaming kernel at bh=H — bitwise, plus tolerance vs the
    jnp oracle — and lowers to a different program (whole-weight constant
    BlockSpecs vs the streamed H tiles)."""
    cfg = RNNCellConfig(cell, 128, timesteps=5, batch=2, precision=prec)
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(6)))
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 2, cfg.d), jnp.bfloat16)
    y_stream = rnn_ops.serve(cfg, w, x, bh=128, interpret=True)
    y_pers = rnn_ops.serve(cfg, w, x, interpret=True,
                           plan={"persistent": True})
    assert (np.asarray(y_pers) == np.asarray(y_stream)).all()
    wx, wh, sx, sh = rnn_ops._weights_for_kernel(cfg, w)
    h0 = jnp.zeros((2, 128))
    if cell == "lstm":
        y_ref, _, _ = rnn_ref.fused_lstm_ref(x, wx, wh, sx, sh, w["b"],
                                             h0, h0)
    else:
        y_ref, _ = rnn_ref.fused_gru_ref(
            x, wx, wh, sx, sh, w["b"], w.get("b_h", jnp.zeros_like(w["b"])),
            h0)
    np.testing.assert_allclose(np.asarray(y_pers, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_fused_rnn_persistent_changes_lowering():
    cfg = RNNCellConfig("lstm", 128, timesteps=3, batch=1, precision="bf16")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(8)))
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 1, cfg.d), jnp.bfloat16)

    def text(plan):
        fn = jax.jit(lambda xx: rnn_ops.serve(cfg, w, xx, interpret=True,
                                              plan=plan))
        return fn.lower(x).as_text()

    assert text({"persistent": True}) != text({"bh": 128})


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("H", [128, 256])
@pytest.mark.parametrize("given_state", [False, True])
def test_serve_request_program_matches_direct_kernel(cell, H, given_state):
    """serve()'s request program builds the zero states inside its trace;
    its y is bit-identical to a direct kernel call with explicit f32
    states at the DSE's tile for the served batch."""
    from repro.core.dse import best_plan, snap_tile
    from repro.kernels.fused_rnn.fused_rnn import fused_gru, fused_lstm
    B, T = 2, 3
    cfg = RNNCellConfig(cell, H, timesteps=T, batch=B, precision="int8")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(10)))
    x = jax.random.normal(jax.random.PRNGKey(11), (T, B, cfg.d),
                          jnp.bfloat16)
    h0 = c0 = jnp.zeros((B, H), jnp.float32)
    state = None
    if given_state:
        k = jax.random.split(jax.random.PRNGKey(12))
        h0, c0 = (0.5 * jax.random.normal(k[i], (B, H)) for i in range(2))
        state = (h0, c0) if cell == "lstm" else (h0,)
    bh = snap_tile(H, best_plan(cfg, max_batch=B).bh)
    wx, wh, sx, sh = rnn_ops._weights_for_kernel(cfg, w)
    y = rnn_ops.serve(cfg, w, x, state=state, interpret=True)
    if cell == "lstm":
        y_direct, _, _ = fused_lstm(x, wx, wh, sx, sh, w["b"], h0, c0,
                                    bh=bh, interpret=True)
    else:
        y_direct, _ = fused_gru(x, wx, wh, sx, sh, w["b"], w["b_h"], h0,
                                bh=bh, interpret=True)
    assert y.dtype == y_direct.dtype and y.shape == y_direct.shape
    assert (np.asarray(y, np.float32) == np.asarray(y_direct,
                                                    np.float32)).all()


def test_serve_caches_plan_and_program(monkeypatch):
    """Repeated requests of one configuration run the DSE once and build
    one request program; a given state or a second configuration builds
    another; every call counts as a request served."""
    import repro.core.dse as dse
    calls = []
    orig = dse.best_plan

    def counted(*args, **kwargs):
        calls.append(args[0])
        return orig(*args, **kwargs)

    monkeypatch.setattr(dse, "best_plan", counted)
    built = rnn_ops.METRICS["rnn.programs_built"]
    served = rnn_ops.METRICS["rnn.requests_served"]
    # timesteps=7 keeps these configurations out of every other test's
    # cached plans and programs in this process
    cfg = RNNCellConfig("gru", 128, timesteps=7, batch=1, precision="int8")
    w = quantize_weights(cfg, init_weights(cfg, jax.random.PRNGKey(13)))
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 1, cfg.d),
                          jnp.bfloat16)
    b0, s0 = built.value, served.value
    ys = [rnn_ops.serve(cfg, w, x, interpret=True) for _ in range(3)]
    assert calls == [cfg]
    assert built.value - b0 == 1 and served.value - s0 == 3
    assert all((np.asarray(y) == np.asarray(ys[0])).all() for y in ys)

    rnn_ops.serve(cfg, w, x, interpret=True,
                  state=(jnp.zeros((1, 128), jnp.float32),))
    assert built.value - b0 == 2 and calls == [cfg]
    rnn_ops.serve(cfg, w, x, interpret=True,
                  state=(jnp.ones((1, 128), jnp.float32),))
    assert built.value - b0 == 2

    cfg2 = RNNCellConfig("lstm", 128, timesteps=7, batch=1,
                         precision="int8")
    w2 = quantize_weights(cfg2, init_weights(cfg2, jax.random.PRNGKey(15)))
    for _ in range(2):
        rnn_ops.serve(cfg2, w2, x, interpret=True)
    assert calls == [cfg, cfg2]
    assert built.value - b0 == 3 and served.value - s0 == 7


def test_flash_attention_pos_matches_iota_path():
    """With explicit iota positions the position-array kernel must equal
    the iota-masking kernel bitwise — same masks, same math."""
    B, H, S, d = 1, 2, 256, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for causal, win in ((True, 0), (True, 64), (False, 0)):
        base = flash_attention(q, k, v, causal=causal, window=win,
                               bq=128, bk=128, interpret=True)
        out = flash_attention(q, k, v, pos, pos, causal=causal, window=win,
                              bq=128, bk=128, interpret=True)
        assert (np.asarray(out) == np.asarray(base)).all(), (causal, win)


def test_flash_attention_pos_masks_padding():
    """-1 positions (right-padded bucketed prefill) mask those keys out:
    the valid prefix of the output must match the unpadded run."""
    B, H, S, d, n_valid = 1, 1, 256, 64, 200
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, H, S, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d),
                          jnp.bfloat16)
    pos = jnp.where(jnp.arange(S) < n_valid, jnp.arange(S), -1)
    pos = jnp.broadcast_to(pos.astype(jnp.int32), (B, S))
    out = flash_attention(q, k, v, pos, pos, causal=True,
                          bq=128, bk=128, interpret=True)
    ref = attention_ref(q[:, :, :n_valid], k[:, :, :n_valid],
                        v[:, :, :n_valid], causal=True)
    np.testing.assert_allclose(
        np.asarray(out[:, :, :n_valid], np.float32),
        np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2)


def _decode_ref(q, kc, vc, kv_pos, q_pos, *, causal, window):
    """jnp oracle mirroring models.attention.decode_attention (without
    sharder/cfg): q (B,H,hd), caches (B,S,H,hd)."""
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    mask = kv_pos >= 0
    if causal:
        mask &= kv_pos <= q_pos[:, None]
    if window > 0:
        mask &= (q_pos[:, None] - kv_pos) < window
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, vc.astype(jnp.float32))


DECODE_SWEEP = [
    # (B, H, K, S, bk, causal, window, holes)
    (2, 2, 2, 256, 128, True, 0, False),
    (1, 4, 2, 256, 64, True, 64, False),     # GQA + sliding window
    (2, 2, 2, 256, 128, True, 0, True),      # ring-buffer holes (-1 slots)
    (1, 2, 2, 512, 512, False, 0, False),    # single chunk, non-causal
]


@pytest.mark.parametrize("B,H,K,S,bk,causal,window,holes", DECODE_SWEEP)
def test_flash_decode_vs_ref(B, H, K, S, bk, causal, window, holes):
    from repro.kernels.flash_attention import ops as flash_ops
    key = jax.random.PRNGKey(10)
    q = jax.random.normal(key, (B, H, 64), jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, K, 64),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, 64),
                           jnp.bfloat16)
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if holes:   # empty ring slots scattered through the cache
        kv_pos = jnp.where(jnp.arange(S) % 5 == 3, -1, kv_pos)
    q_pos = jnp.full((B,), S // 2, jnp.int32)
    out = flash_ops.decode(q, kc, vc, kv_pos, q_pos, causal=causal,
                           window=window, plan={"bk": bk}, interpret=True)
    ke, ve = flash_ops._expand_kv(kc, vc, H)
    ref = _decode_ref(q, ke, ve, kv_pos, q_pos, causal=causal,
                      window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_flash_decode_chunk_count_is_bit_exact():
    """Splitting the KV axis into more chunks only reorders the LSE
    combine across chunks of *identical* per-chunk partials — outputs
    must stay equal within bf16 rounding of the same math."""
    from repro.kernels.flash_attention import ops as flash_ops
    B, H, S = 1, 2, 512
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, H, 64), jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, 64),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, 64),
                           jnp.bfloat16)
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q_pos = jnp.full((B,), S - 1, jnp.int32)
    outs = [np.asarray(flash_ops.decode(q, kc, vc, kv_pos, q_pos,
                                        plan={"bk": bk}, interpret=True),
                       np.float32)
            for bk in (512, 256, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-2, rtol=2e-2)


def test_attention_ops_snap_non_divisible_tiles():
    """A plan tuned for another shape degrades gracefully: bq/bk that do
    not divide the actual sequence snap to divisors instead of failing."""
    from repro.kernels.flash_attention import ops as flash_ops
    B, S, H, d = 1, 192, 2, 64           # 192 = 64*3: 128 does not divide
    key = jax.random.PRNGKey(12)
    q = jax.random.normal(key, (B, S, H, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, d),
                          jnp.bfloat16)
    out = flash_ops.attention(q, k, v, causal=True, interpret=True,
                              plan={"bq": 128, "bk": 512})
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3), np.float32),
        np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2)


def test_qdot_plan_tiles():
    """qdot under a tile plan (including non-divisible bm/bn/bk, snapped)
    matches the plain ref."""
    from repro.kernels.matmul_int8 import ops as mm_ops
    key = jax.random.PRNGKey(13)
    M, K, N = 96, 256, 384
    x = jax.random.normal(key, (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) / np.sqrt(K)
    wq, sc = quantize_int8(w, axis=0)
    leaf = {"q": wq, "scale": sc}
    ref = matmul_w8a16_ref(x, wq, sc[0], None)
    for plan in (None, {"bm": 256, "bn": 256, "bk": 512},
                 {"bm": 100, "bn": 130, "bk": 70}):
        out = mm_ops.qdot(x, leaf, interpret=True, plan=plan)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)
