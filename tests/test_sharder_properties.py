"""Property-based Sharder tests (hypothesis).

Randomized meshes / rules / tensor shapes pin the resolution contract of
``repro.dist.sharding.Sharder`` (see its module docstring):

* spec axes honored — every mesh axis a spec assigns to a tensor dim comes
  from that dim's logical-axis rule, in rule order;
* the divisibility fallback never over-shards — an assigned shard count
  always divides the dimension;
* one mesh axis is never assigned to two dims of the same tensor;
* the mesh-less Sharder is a strict no-op.
"""

import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.dist.sharding import Sharder

AXIS_SIZES = (1, 2, 3, 4, 8, 16)
MESH_AXES = ("pod", "data", "model")
RULES = ((), ("model",), ("data",), ("pod", "data"), ("data", "model"),
         ("pod", "data", "model"), ("model", "data"))


class FakeMesh:
    """Just enough Mesh surface for rule resolution (as test_sharding.py)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _sharder(rules, sizes):
    s = Sharder.__new__(Sharder)
    s.mesh = FakeMesh(tuple(zip(MESH_AXES, sizes)))
    s.rules = dict(rules)
    return s


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@settings(max_examples=120, deadline=None)
@given(pod=st.sampled_from(AXIS_SIZES), data=st.sampled_from(AXIS_SIZES),
       model=st.sampled_from(AXIS_SIZES), rule=st.sampled_from(RULES),
       dim=st.integers(min_value=1, max_value=96))
def test_resolve_is_divisible_rule_prefix(pod, data, model, rule, dim):
    """resolve() returns a prefix of the rule whose shard count divides the
    dim — the fallback drops trailing axes, never over-shards, never
    invents axes."""
    s = _sharder({"x": rule}, (pod, data, model))
    r = s.resolve("x", dim)
    present = tuple(a for a in rule if a in s.mesh.shape)
    if r is None:
        # fallback exhausted: no non-empty prefix of the rule divides dim
        assert all(dim % _prod(s.mesh, present[:k])
                   for k in range(1, len(present) + 1)) or not present
    else:
        assert r == present[:len(r)]          # prefix, in rule order
        assert dim % _prod(s.mesh, r) == 0    # never over-shards


@settings(max_examples=120, deadline=None)
@given(pod=st.sampled_from(AXIS_SIZES), data=st.sampled_from(AXIS_SIZES),
       model=st.sampled_from(AXIS_SIZES),
       r0=st.sampled_from(RULES), r1=st.sampled_from(RULES),
       r2=st.sampled_from(RULES),
       d0=st.integers(min_value=1, max_value=64),
       d1=st.integers(min_value=1, max_value=64),
       d2=st.integers(min_value=1, max_value=64))
def test_spec_no_axis_reuse_and_axes_honored(pod, data, model, r0, r1, r2,
                                             d0, d1, d2):
    rules = {"a0": r0, "a1": r1, "a2": r2}
    s = _sharder(rules, (pod, data, model))
    shape = (d0, d1, d2)
    spec = s.spec(("a0", "a1", "a2"), shape)
    used = []
    for entry, logical, dim in zip(spec, ("a0", "a1", "a2"), shape):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        used.extend(axes)
        # honored: only axes the logical rule names, and divisibility holds
        assert set(axes) <= set(rules[logical])
        assert dim % _prod(s.mesh, axes) == 0
    assert len(used) == len(set(used)), f"mesh axis reused: {spec}"


@settings(max_examples=60, deadline=None)
@given(rule=st.sampled_from(RULES),
       dim=st.integers(min_value=1, max_value=64),
       with_rules=st.booleans())
def test_meshless_sharder_is_noop(rule, dim, with_rules):
    s = Sharder(None, {"x": rule} if with_rules else {})
    assert s.resolve("x", dim) is None
    assert s.sharding(("x",), (dim,)) is None
    x = jnp.ones((dim,))
    assert s.constrain(x, "x") is x
