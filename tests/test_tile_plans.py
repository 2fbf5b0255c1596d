"""PR 9: tile_plans close the kernel loop — validation, planner emission,
plan IO, CLI rescoring, and the end-to-end model/engine threading that
turns a plan entry into Pallas BlockSpec geometry."""

import dataclasses

import jax
import numpy as np
import pytest

from repro import hw
from repro.plan import ServingPlan
from repro.plan import io as plan_io
from repro.plan import planner
from repro.plan.plan import TILE_PLAN_KINDS, tiles_summary


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


GOOD_TILE_PLANS = [
    {},
    {"rwkv": {"bh": 128, "resident": True}},
    {"rwkv": {"bh": 64, "persistent": True, "resident": True,
              "impl": "auto"}},
    {"attn": {"bq": 128, "bk": 512},
     "matmul_int8": {"bm": 256, "bn": 256, "bk": 512}},
    {"fused_rnn": {"bh": 256, "n_tiles": 8, "vmem_bytes": 1024,
                   "resident": True, "step_latency_s": 1e-6, "util": 0.9,
                   "bound": "vmem"}},
]

BAD_TILE_PLANS = [
    {"bogus_kernel": {"bh": 8}},          # unknown kernel kind
    {"rwkv": [128]},                      # entry must be a mapping
    {"rwkv": {"bh": 0}},                  # tiles must be positive
    {"rwkv": {"bh": -8}},
    {"rwkv": {"bh": True}},               # bool is not a tile size
    {"rwkv": {"impl": "cuda"}},           # unknown dispatch impl
    {"rwkv": {"frobnicate": 3}},          # unknown field
    {"rwkv": {"persistent": "yes", "resident": True}},
    {"rwkv": {"persistent": True}},       # persistent needs resident proof
    {"rwkv": {"persistent": True, "resident": True,
              "vmem_bytes": 2 ** 40}},    # ... that actually fits VMEM
]


@pytest.mark.parametrize("tp", GOOD_TILE_PLANS)
def test_validate_accepts(tp):
    ServingPlan(arch="rwkv6-1.6b", tile_plans=tp).validate()


@pytest.mark.parametrize("tp", BAD_TILE_PLANS)
def test_validate_rejects(tp):
    with pytest.raises(ValueError):
        ServingPlan(arch="rwkv6-1.6b", tile_plans=tp).validate()


def test_tiles_summary():
    s = tiles_summary({
        "attn": {"bq": 256, "bk": 1024},
        "rwkv": {"bh": 512, "persistent": True, "resident": True},
    })
    assert "attn[bq256,bk1024]" in s
    assert "rwkv[bh512,persist]" in s


# ---------------------------------------------------------------------------
# planner emission
# ---------------------------------------------------------------------------


def test_tile_plans_for_rwkv():
    tp = planner.tile_plans_for("rwkv6-1.6b", 8, hw.DEFAULT, max_len=1024)
    assert set(tp) == {"rwkv"}
    entry = tp["rwkv"]
    # all 32 heads (2048 hidden units) of (8, 64, 64) f32 state fit VMEM
    assert entry["bh"] == 2048 and entry["resident"] is True
    assert entry["vmem_bytes"] <= hw.vmem_budget()
    # rwkv6_step has no weights to pin: never marked persistent
    assert entry["n_tiles"] == 1 and "persistent" not in entry
    ServingPlan(arch="rwkv6-1.6b", tile_plans=tp).validate()


def test_tile_plans_for_attn_families():
    tp = planner.tile_plans_for("gemma2-9b", 8, hw.DEFAULT, max_len=1024)
    assert set(tp) == {"attn", "local"}
    for entry in tp.values():
        assert entry["bq"] > 0 and entry["bk"] > 0
    ServingPlan(arch="gemma2-9b", tile_plans=tp).validate()


def test_tile_plans_for_hybrid_marks_persistent():
    """hymba's SSD half fits VMEM whole (n_tiles == 1, resident) — the
    planner must emit the persistent marker, with the DSE evidence that
    ``ServingPlan.validate`` demands alongside it."""
    tp = planner.tile_plans_for("hymba-1.5b", 8, hw.DEFAULT, max_len=1024)
    assert set(tp) == {"attn", "swa_ssm"}
    ssm = tp["swa_ssm"]
    assert ssm["persistent"] is True
    assert ssm["n_tiles"] == 1 and ssm["resident"] is True
    assert ssm["vmem_bytes"] <= hw.vmem_budget()
    ServingPlan(arch="hymba-1.5b", tile_plans=tp).validate()


def test_tile_plans_are_batch_aware():
    """Scored at the plan's max_batch: more decode lanes shrink the VMEM
    share left for weights, so the chosen design must change."""
    tp1 = planner.tile_plans_for("rwkv6-1.6b", 1, hw.DEFAULT)
    tp256 = planner.tile_plans_for("rwkv6-1.6b", 256, hw.DEFAULT)
    assert tp1["rwkv"] != tp256["rwkv"]


# ---------------------------------------------------------------------------
# plan IO
# ---------------------------------------------------------------------------


def test_plan_io_round_trips_tile_plans(tmp_path):
    tp = planner.tile_plans_for("rwkv6-1.6b", 8, hw.DEFAULT, max_len=1024)
    plan = ServingPlan(arch="rwkv6-1.6b", max_batch=8, tile_plans=tp)
    path = str(tmp_path / "plan.json")
    plan_io.save_plan(plan, path)
    loaded = plan_io.load_plan(path)
    assert dict(loaded.tile_plans) == dict(plan.tile_plans)
    loaded.validate()


def test_check_schema_covers_tile_plans():
    plan_io.check_schema()   # raises if tile_plans drift from the schema


# ---------------------------------------------------------------------------
# CLI: --hw-spec rescoring and staleness recompute
# ---------------------------------------------------------------------------


def _resolve(argv):
    from repro.launch.serve import build_parser, resolve_plan
    parser = build_parser()
    return resolve_plan(parser.parse_args(argv), parser)


def test_cli_hw_spec_scores_tile_plans():
    plan = _resolve(["--arch", "rwkv6-1.6b", "--hw-spec", "tpu-v5e"])
    assert plan.tile_plans
    expect = planner.tile_plans_for("rwkv6-1.6b", plan.max_batch,
                                    hw.TPU_V5E, max_len=plan.max_len)
    assert dict(plan.tile_plans) == expect
    assert "tile_plans" in plan.provenance["cli_overrides"]


def test_cli_hw_spec_other_silicon_differs():
    v5e = _resolve(["--arch", "rwkv6-1.6b", "--hw-spec", "tpu-v5e"])
    pls = _resolve(["--arch", "rwkv6-1.6b", "--hw-spec",
                    "plasticine-rnn-variant"])
    assert dict(v5e.tile_plans) != dict(pls.tile_plans)


def test_cli_unknown_hw_spec_errors():
    with pytest.raises(SystemExit):
        _resolve(["--arch", "rwkv6-1.6b", "--hw-spec", "tpu-v9"])


def test_cli_override_recomputes_stale_tile_plans(tmp_path):
    """A --plan file carries tile plans scored at its own max_batch; a
    --max-batch override makes that kernel half stale, so resolve_plan
    must rescore rather than serve the old geometry."""
    tp = planner.tile_plans_for("rwkv6-1.6b", 4, hw.DEFAULT, max_len=128)
    base = ServingPlan(arch="rwkv6-1.6b", max_batch=4, max_len=128,
                       tile_plans=tp)
    path = str(tmp_path / "plan.json")
    plan_io.save_plan(base, path)
    plan = _resolve(["--plan", path, "--max-batch", "256"])
    expect = planner.tile_plans_for("rwkv6-1.6b", 256, hw.DEFAULT,
                                    max_len=128)
    assert dict(plan.tile_plans) == expect
    assert dict(plan.tile_plans) != tp


# ---------------------------------------------------------------------------
# end-to-end: plan entry -> model -> kernel grid
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rwkv_setup():
    from repro.dist.sharding import Sharder
    from repro.models.lm import build_model
    from repro.testing import reduced_config

    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, Sharder(None, {})


def test_engine_threads_tile_plans(rwkv_setup):
    from repro.serving import ServingEngine

    cfg, model, params, sharder = rwkv_setup
    plan = ServingPlan(arch="rwkv6-1.6b", max_batch=2, max_len=32,
                       tile_plans={"rwkv": {"impl": "pallas", "bh": 64}})
    eng = ServingEngine.from_plan(plan, params, model=model,
                                  sharder=sharder)
    assert eng.model.tile_plans == dict(plan.tile_plans)
    assert eng.model is not model        # rebound, original untouched
    assert model.tile_plans == {}


def test_engine_output_invariant_under_tile_plans(rwkv_setup):
    """Greedy decode tokens must be identical whether the rwkv layers run
    on the jnp path, auto dispatch, or the forced Pallas kernel under an
    explicit head tile — the plan changes the schedule, never the math."""
    from repro.serving import ServingEngine

    cfg, model, params, sharder = rwkv_setup
    outs = []
    for tp in ({}, {"rwkv": {"impl": "auto"}},
               {"rwkv": {"impl": "pallas", "bh": cfg.rwkv.head_dim}}):
        plan = ServingPlan(arch="rwkv6-1.6b", max_batch=2, max_len=32,
                           tile_plans=tp)
        eng = ServingEngine.from_plan(plan, params, model=model,
                                      sharder=sharder)
        r = eng.submit([3, 5, 7], max_new_tokens=6)
        eng.run()
        outs.append(r.output)
    assert outs[0] == outs[1] == outs[2]


def test_tile_plan_reaches_lowered_program(rwkv_setup):
    """HLO-level proof the plan reaches the hardware: changing only the
    head tile changes the lowered decode program (different Pallas grid)
    while the logits stay bit-identical in interpret mode."""
    cfg, model, params, sharder = rwkv_setup
    prompts = jax.numpy.asarray([[3, 5, 7, 9]], jax.numpy.int32)
    cache, _ = model.prefill(params, {"tokens": prompts}, sharder,
                             max_len=16)
    tokens = jax.numpy.asarray([11], jax.numpy.int32)

    def lower_and_run(tp):
        m = model.with_tile_plans(tp)
        fn = jax.jit(lambda p, c, t: m.decode_step(p, c, t, sharder))
        text = fn.lower(params, cache, tokens).as_text()
        _, logits = fn(params, cache, tokens)
        return text, np.asarray(logits)

    hd = cfg.rwkv.head_dim
    text_jnp, logits_jnp = lower_and_run({})
    text_a, logits_a = lower_and_run({"rwkv": {"impl": "pallas"}})
    text_b, logits_b = lower_and_run({"rwkv": {"impl": "pallas",
                                               "bh": hd}})
    assert text_a != text_jnp            # kernel path actually engaged
    assert text_a != text_b              # bh reached the BlockSpec grid
    assert (logits_a == logits_b).all()  # ... without touching the math
    np.testing.assert_allclose(logits_a, logits_jnp, atol=2e-2, rtol=2e-2)
