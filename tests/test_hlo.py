"""HLO collective parser unit tests (synthetic lines + a real lowering)."""

import jax
import jax.numpy as jnp

from repro.launch.hlo import collective_summary, parse_collectives

SYNTH = """
  %all-reduce.5 = f32[864,5120]{1,0} all-reduce(%fusion.3), channel_id=1, replica_groups=[32,16]<=[512]T(1,0), use_global_device_ids=true, to_apply=%add
  %ag = bf16[16,4096]{1,0} all-gather(%p0), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%x), channel_id=3, replica_groups=[4,2]<=[8], to_apply=%add
  %a2a.1 = bf16[8,64]{1,0} all-to-all(%y), channel_id=4, replica_groups=[1,8]<=[8]
  %cp = f32[32]{0} collective-permute(%z), channel_id=5, source_target_pairs={{0,1},{1,0}}
  %ard = f32[4]{0} all-reduce-done(%ar-start)
"""


def test_parse_kinds_and_groups():
    ops = parse_collectives(SYNTH)
    kinds = [o.kind for o in ops]
    assert kinds == ["all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute"]
    ar, ag, rs, a2a, cp = ops
    assert ar.group_size == 16
    assert ar.out_bytes == 864 * 5120 * 4
    assert ar.ici_bytes == int(2 * 15 / 16 * ar.out_bytes)
    assert ag.group_size == 4
    assert ag.operand_bytes == ag.out_bytes // 4
    assert rs.group_size == 2
    assert rs.operand_bytes == rs.out_bytes * 2
    assert a2a.group_size == 8
    assert cp.ici_bytes == cp.out_bytes


def test_done_ops_not_double_counted():
    ops = parse_collectives(SYNTH)
    assert not any("done" in o.line for o in ops)


def test_summary_totals():
    s = collective_summary(parse_collectives(SYNTH))
    assert s["n_ops"] == 5
    assert s["ici_bytes"] > 0
    assert set(s["by_kind"]) == {"all-reduce", "all-gather", "reduce-scatter",
                                 "all-to-all", "collective-permute"}


def test_real_lowering_has_collectives():
    """An actually-compiled sharded matmul produces parseable collectives."""
    import os
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4,), ("model",))
f = jax.jit(lambda x, w: jax.nn.relu(x @ w).sum(),
            in_shardings=(NamedSharding(mesh, P(None, "model")),
                          NamedSharding(mesh, P("model", None))))
with mesh:
    txt = f.lower(jax.ShapeDtypeStruct((8, 16), jnp.float32),
                  jax.ShapeDtypeStruct((16, 8), jnp.float32)).compile().as_text()
import sys; sys.path.insert(0, "src")
from repro.launch.hlo import parse_collectives
ops = parse_collectives(txt)
assert any(o.kind == "all-reduce" for o in ops), [o.kind for o in ops]
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
