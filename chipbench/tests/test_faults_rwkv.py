"""A run with the timed serving path broken underneath comes out as not
correct: a decode step that leaves the recurrent state unchanged, and a
token altered where it is sampled."""

import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


def test_state_left_unchanged(root, monkeypatch):
    import repro.models.rwkv as rwkv

    orig = rwkv.linear_attention_step_planned

    def stuck(state, *args, **kwargs):
        y, _ = orig(state, *args, **kwargs)
        return y, state

    monkeypatch.setattr(rwkv, "linear_attention_step_planned", stuck)
    line = tiny.run(root, "tiny-rwkv.tiny-closed", seconds=2.0)
    assert not line["correct"]


def test_token_altered(root, monkeypatch):
    import repro.serving.engine as engine

    orig = engine.split_and_sample

    def altered(key, logits, cfg):
        key, tok = orig(key, logits, cfg)
        return key, (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "split_and_sample", altered)
    line = tiny.run(root, "tiny-rwkv.tiny-closed", seconds=2.0)
    assert not line["correct"]
