"""The tiny copy of the qwen2.5-14b chat cell: the program's run reads
correct and the control's does not, and a run with the served path broken
underneath comes out as not correct: a RoPE at the wrong position in
decode, a token altered where it is sampled, and (on four devices) a
decode that drops one cache shard's partial in the cross-device merge."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import tiny, tiny_qwen


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_qwen.make_root(tmp_path_factory.mktemp("qwen"))


def test_program_is_correct_and_control_is_not(root):
    line = tiny.run(root, tiny_qwen.CELL, seconds=2.0)
    assert line["correct"] and set(line["check"]) == {"mean_logit_gap"}
    line = tiny.run(root, tiny_qwen.CELL, seconds=2.0, control=True)
    check = line["check"]
    assert not line["correct"]
    assert check["mean_logit_gap"]["value"] > check["mean_logit_gap"]["limit"]
    assert check["program_mean_logit_gap"]["value"] <= \
        check["mean_logit_gap"]["limit"]


def test_rope_at_the_wrong_position(root, monkeypatch):
    import repro.models.blocks as blocks

    orig = blocks._attn_step

    def shifted(params, x, cfg, sharder, lengths, cache, *, window,
                positions=None, tile_plan=None):
        pos = (lengths[:, None] if positions is None else positions) + 1
        return orig(params, x, cfg, sharder, lengths, cache, window=window,
                    positions=pos, tile_plan=tile_plan)

    monkeypatch.setattr(blocks, "_attn_step", shifted)
    assert not tiny.run(root, tiny_qwen.CELL, seconds=2.0)["correct"]


def test_token_altered(root, monkeypatch):
    import repro.serving.engine as engine

    orig = engine.split_and_sample

    def altered(key, logits, cfg):
        key, tok = orig(key, logits, cfg)
        return key, (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "split_and_sample", altered)
    assert not tiny.run(root, tiny_qwen.CELL, seconds=2.0)["correct"]


FOUR_DEVICES = r"""
import json, os, sys, tempfile
from pathlib import Path
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from chipbench.tests import tiny, tiny_qwen
import repro.kernels.dispatch as dispatch
import repro.kernels.flash_attention.ops as ops

dispatch.resolve_impl = lambda entry: "pallas"     # interpret mode here
if sys.argv[1] == "drop":
    merge = ops.combine

    def dropped(m_p, l_p, acc_p, axis_names=()):
        if axis_names:      # the first shard's partial weighs nothing
            m_p = jnp.where(jax.lax.axis_index(axis_names) == 0, -1e30, m_p)
        return merge(m_p, l_p, acc_p, axis_names)

    ops.combine = dropped
root = tiny_qwen.make_root(Path(tempfile.mkdtemp()), chips=4)
line = tiny.run(root, tiny_qwen.CELL, seconds=2.0)
print(json.dumps({"correct": line["correct"], "count": line["device"]["count"]}))
"""


@pytest.mark.parametrize("fault,correct", [("none", True), ("drop", False)])
def test_four_devices(fault, correct):
    """Sharded over a 1 x 4 CPU mesh with the flash kernels in the path:
    the program reads correct, and a merge that drops the first cache
    shard's partial does not."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(tiny.ROOT / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", FOUR_DEVICES, fault],
                       cwd=tiny.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"correct": correct, "count": 4}
