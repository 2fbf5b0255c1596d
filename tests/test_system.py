"""End-to-end behaviour tests for the paper's system.

1.  The paper's pipeline: DeepBench-style RNN serving through the fused
    kernel path vs the BLAS baseline — same outputs, and the DSE picks a
    resident plan for on-chip-fit sizes.
2.  The framework pipeline: data -> train steps (loss goes down) ->
    checkpoint -> serve the trained weights through the engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import hw
from repro.core import dse
from repro.core.cells import RNNCellConfig, init_weights, quantize_weights, serve
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.testing import reduced_config, smoke_shape
from repro.train.loop import TrainLoopConfig, train


def test_deepbench_style_serving_kernel_vs_blas(key):
    cfg = RNNCellConfig("lstm", 256, timesteps=10, batch=1, precision="int8")
    w = quantize_weights(cfg, init_weights(cfg, key))
    x = jax.random.normal(jax.random.fold_in(key, 1), (10, 1, 256),
                          jnp.bfloat16)
    y_kernel = serve(cfg, w, x, impl="kernel")
    y_blas = serve(cfg, w, x, impl="blas")
    np.testing.assert_allclose(np.asarray(y_kernel, np.float32),
                               np.asarray(y_blas, np.float32),
                               atol=3e-2, rtol=3e-2)
    plan = dse.best_plan(cfg)
    assert plan.resident  # H=256 int8 weights trivially fit VMEM
    assert plan.vmem_bytes < hw.vmem_budget()


@pytest.mark.slow
def test_train_then_serve_pipeline(tmp_path, nosharder):
    # hymba starts far from the unigram entropy (norm-fused init), so a
    # dozen steps reliably reduce the loss even on synthetic data
    arch = "hymba-1.5b"
    model = build_model(reduced_config(arch))
    shape = smoke_shape("train", seq=32, batch=4)
    loop_cfg = TrainLoopConfig(total_steps=12, checkpoint_every=6,
                               checkpoint_dir=str(tmp_path / "ck"),
                               log_every=100, async_checkpoint=False)
    state, history = train(model, shape, nosharder, loop_cfg)
    losses = [h["loss"] for h in history]
    assert losses[-1] < losses[0], (losses[0], losses[-1])

    engine = ServingEngine.from_plan(
        ServingPlan(arch=arch, max_batch=2, max_len=48), state["params"],
        model=model, sharder=nosharder)
    reqs = [engine.submit([1, 2, 3, 4], max_new_tokens=4) for _ in range(3)]
    engine.run()
    assert all(r.done and len(r.output) == 4 for r in reqs)
