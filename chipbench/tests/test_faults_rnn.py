"""A run with the fused RNN path broken underneath comes out as not
correct: a kernel whose steps leave the state unchanged, and an answer
altered where it is produced."""

import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


def _patch(monkeypatch, change):
    import repro.kernels.fused_rnn.ops as ops

    orig = ops.serve

    def broken(*args, **kwargs):
        return change(orig(*args, **kwargs))

    monkeypatch.setattr(ops, "serve", broken)


def test_state_left_unchanged(root, monkeypatch):
    import jax.numpy as jnp

    _patch(monkeypatch, lambda y: jnp.broadcast_to(y[:1], y.shape))
    assert not tiny.run(root, "tiny-rnn.tiny-b1", seconds=2.0)["correct"]


def test_answer_altered(root, monkeypatch):
    _patch(monkeypatch, lambda y: y.at[-1, 0, 0].add(0.25))
    assert not tiny.run(root, "tiny-rnn.tiny-b1", seconds=2.0)["correct"]
