"""Continuous-batching engine: correctness of slot multiplexing."""

import jax
import numpy as np
import pytest

from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.testing import reduced_config


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.dist.sharding import Sharder
    return cfg, model, params, Sharder(None, {})


def _engine(setup, **knobs):
    cfg, model, params, sharder = setup
    knobs.setdefault("max_batch", 2)
    knobs.setdefault("max_len", 32)
    return ServingEngine.from_plan(ServingPlan(arch=cfg.name, **knobs),
                                   params, model=model, sharder=sharder)


def test_all_requests_complete(setup):
    eng = _engine(setup)
    reqs = [eng.submit([1, 2, 3, 4 + i], max_new_tokens=5) for i in range(5)]
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 5 for r in reqs)


def test_batched_equals_sequential(setup):
    """Greedy decoding of a request must not depend on its co-tenants."""
    prompt = [5, 9, 3, 7]
    solo = _engine(setup, max_batch=1)
    r_solo = solo.submit(list(prompt), max_new_tokens=6)
    solo.run()

    multi = _engine(setup)
    r_a = multi.submit(list(prompt), max_new_tokens=6)
    r_b = multi.submit([2, 4, 6, 8, 10], max_new_tokens=6)
    multi.run()
    assert r_a.output == r_solo.output


def test_slot_reuse_after_completion(setup):
    eng = _engine(setup)
    first = [eng.submit([1, 2, 3], max_new_tokens=3) for _ in range(2)]
    eng.run()
    second = eng.submit([4, 5, 6], max_new_tokens=3)
    eng.run()
    assert second.done and len(second.output) == 3


def test_max_len_truncates(setup):
    eng = _engine(setup)
    r = eng.submit(list(range(1, 20)), max_new_tokens=100)
    eng.run()
    assert r.done
    assert len(r.output) <= 32


# ------------------------------------------------- prompt-capacity boundary
# Regression tests for the old silent truncation: _admit used to drop the
# prompt tail to max_len - max_new_tokens - 1 tokens with no signal.


def test_prompt_at_capacity_accepted_and_fully_used(setup):
    """A prompt of exactly max_len - 1 tokens is admitted whole: its first
    greedy token matches the same prompt on a roomier engine, so the tail
    provably reached the model."""
    cfg = setup[0]
    prompt = [(7 * i) % cfg.vocab_size for i in range(31)]   # max_len - 1
    tight = _engine(setup, max_batch=1)
    r_tight = tight.submit(list(prompt), max_new_tokens=4)
    tight.run()
    roomy = _engine(setup, max_batch=1, max_len=64)
    r_roomy = roomy.submit(list(prompt), max_new_tokens=4)
    roomy.run()
    assert r_tight.done and not r_tight.truncated
    assert r_tight.output[0] == r_roomy.output[0]
    # 4 requested tokens can't follow a 31-token prompt in a 32-slot
    # cache: flagged at submit, not silently cut at the end of the run
    assert r_tight.capped and len(r_tight.output) == 2
    assert not r_roomy.capped and len(r_roomy.output) == 4


def test_prompt_past_capacity_rejected(setup):
    eng = _engine(setup)   # max_len = 32
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(list(range(32)), max_new_tokens=4)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=0)
    # the engine stays serviceable after a rejected submit
    ok = eng.submit(list(range(31)), max_new_tokens=2)
    eng.run()
    assert ok.done


def test_prompt_past_capacity_opt_in_truncation(setup, caplog):
    eng = _engine(setup, max_batch=1, truncate_prompts=True)
    with caplog.at_level("WARNING", logger="repro.serving"):
        r = eng.submit(list(range(40)), max_new_tokens=2)
    assert r.truncated and len(r.prompt) == 31
    assert any("truncating prompt" in m for m in caplog.messages)
    eng.run()
    assert r.done
