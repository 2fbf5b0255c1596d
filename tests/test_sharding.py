"""Sharding rules engine: divisibility fallback, axis dedup, param trees."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.dist.sharding import Sharder, make_rules, make_sharder
from repro.models.params import ParamSpec


class FakeMesh:
    """Just enough Mesh surface for rule resolution tests."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _sharder(rules, shape=(("data", 16), ("model", 16))):
    s = Sharder.__new__(Sharder)
    s.mesh = FakeMesh(shape)
    s.rules = dict(rules)
    return s


def test_divisibility_fallback_drops_trailing_axes():
    s = _sharder({"batch": ("pod", "data")},
                 shape=(("pod", 2), ("data", 16), ("model", 16)))
    assert s.resolve("batch", 256) == ("pod", "data")   # 256 % 32 == 0
    assert s.resolve("batch", 32) == ("pod", "data")
    assert s.resolve("batch", 2) == ("pod",)            # falls back to pod
    assert s.resolve("batch", 1) is None                # fully replicated


def test_heads_fallback_to_replication():
    s = _sharder({"heads": ("model",)})
    assert s.resolve("heads", 40) is None   # 40 !| 16 -> replicate
    assert s.resolve("heads", 48) == ("model",)


def test_spec_never_reuses_mesh_axis():
    s = _sharder({"experts": ("model",), "mlp": ("model",)})
    spec = s.spec(("experts", None, "mlp"), (32, 1024, 512))
    # experts takes "model"; mlp must NOT reuse it
    assert spec[0] == "model"
    assert spec[2] is None


def test_rules_tables_cover_all_archs():
    for arch in ("qwen2.5-14b", "gemma2-9b", "rwkv6-1.6b", "hymba-1.5b",
                 "qwen3-moe-30b-a3b"):
        cfg = get_config(arch)
        for mode in ("train", "prefill", "decode"):
            rules = make_rules(cfg, mode)
            assert "batch" in rules and "mlp" in rules


def test_mesh_sharder_constrain_is_noop_without_mesh(nosharder):
    x = jnp.ones((4, 8))
    assert nosharder.constrain(x, "batch", None) is x


DRYRUN_SMALL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch import dryrun as dr
from repro.launch.mesh import make_test_mesh
from repro.testing import reduced_config, smoke_shape
from repro.models.lm import build_model
from repro.dist.sharding import make_sharder

mesh = make_test_mesh((2, 4), ("data", "model"))
cfg = reduced_config("gemma3-12b", n_microbatches=2)
model = build_model(cfg)
for shape in [smoke_shape("train", 16, 4), smoke_shape("prefill", 16, 4),
              smoke_shape("decode", 16, 4)]:
    sharder = make_sharder(cfg, mesh, shape.mode)
    if shape.mode == "train":
        res = dr.build_train_cell(model, shape, mesh, sharder, pieces=True)
    else:
        res = dr.build_serve_cell(model, shape, mesh, sharder, pieces=True)
    assert res["full"]["flops"] > 0
    assert res["full"]["collectives"]["n_ops"] > 0, shape.mode
print("OK")
"""


@pytest.mark.slow
def test_dryrun_machinery_on_8_fake_devices():
    """The dry-run builder (lower+compile+cost pieces) runs end to end on a
    small mesh in a subprocess with 8 fake devices."""
    r = subprocess.run([sys.executable, "-c", DRYRUN_SMALL],
                       capture_output=True, text=True, timeout=900,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
                       cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
