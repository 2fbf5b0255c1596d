"""Guards of the chip path, checked on the CPU: the smoke script refuses
to run without a TPU, the compile cache goes to one fixed place, the
hardware spec comes from the device kind, and kernel dispatch never
hides which implementation runs."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache, hw
from repro.kernels import dispatch
from repro.plan import planner

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def test_smoke_device_check_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.check_device()
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def _run_smoke(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script-alone"])
def test_smoke_script_fails_without_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu, and in a directory holding the script and
    nothing else of the repo, it exits non-zero with no result line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _run_smoke(script.parent, script)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("entries", [None, 0, 3],
                         ids=["off", "missing", "three"])
def test_smoke_cache_note_counts_entries(tmp_path, entries):
    """The smoke says whether its compile times start from a warm cache."""
    path = None if entries is None else tmp_path / "cache"
    if entries:
        path.mkdir()
        for i in range(entries):
            (path / f"entry{i}").write_bytes(b"")
    note = chip_smoke.cache_note(path and str(path))
    if entries is None:
        assert note == "compile cache: off"
    else:
        assert note == f"compile cache: {path} ({entries} entries at start)"


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


def test_compile_cache_leaves_env_setting_alone():
    assert compile_cache.cache_dir(
        {compile_cache.ENV_VAR: "/elsewhere/cache"}) is None


def test_compile_cache_defaults_to_checkout():
    path = compile_cache.cache_dir({})
    assert path == str(ROOT / ".jax_cache")
    assert (Path(path).parent / "chip_smoke.py").exists()


# ---------------------------------------------------------------------------
# hardware spec by device kind
# ---------------------------------------------------------------------------


class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_spec_for_known_device_kind():
    assert hw.spec_for_device(_Device("TPU v5 lite")) is hw.TPU_V5E
    assert hw.get_spec("tpu-v5e") is hw.TPU_V5E


def test_spec_for_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        hw.spec_for_device(_Device("TPU v9"))


def test_vmem_budget_is_the_kernels_limit():
    """128 MiB as the compiler reports it; the budget the DSE sizes tiles
    against is the vmem_limit_bytes every kernel requests."""
    assert hw.TPU_V5E.vmem_bytes == 128 * 2**20
    assert hw.vmem_budget(hw.TPU_V5E) == 96 * 2**20
    for path in ("fused_rnn/fused_rnn.py", "rwkv_step/rwkv_step.py",
                 "flash_attention/flash_attention.py",
                 "flash_attention/flash_decode.py",
                 "matmul_int8/matmul_int8.py"):
        src = (ROOT / "src/repro/kernels" / path).read_text()
        assert "vmem_limit_bytes=hw.vmem_budget()" in src, path


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def test_interpret_mode_only_on_cpu(monkeypatch):
    assert dispatch.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        dispatch.interpret_mode()


def test_kernel_impls_name_each_kind():
    tp = {"rwkv": {"bh": 64}, "attn": {"impl": "pallas"},
          "local": {"impl": "jnp"}}
    assert dispatch.kernel_impls(tp) == {
        "attn": "pallas", "local": "jnp", "rwkv": "jnp"}


def _resolve(argv):
    from repro.launch.serve import build_parser, resolve_plan

    parser = build_parser()
    return resolve_plan(parser.parse_args(argv), parser)


def test_cli_plan_on_cpu_keeps_no_tile_plans():
    from repro.launch.serve import kernel_banner

    plan = _resolve(["--arch", "rwkv6-1.6b", "--reduced"])
    assert not plan.tile_plans
    assert kernel_banner(plan) == "none -> jnp reference path (cpu)"


def test_cli_plan_on_tpu_gets_device_tile_plans(monkeypatch):
    """On a TPU a plan without tile plans gets the planner's for the
    attached chip, so the plain CLI serves through the kernels."""
    from repro.launch.serve import kernel_banner

    seen = []

    def spec(device):
        seen.append(device)
        return hw.TPU_V5E

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(hw, "spec_for_device", spec)
    plan = _resolve(["--arch", "rwkv6-1.6b", "--max-batch", "8"])
    assert seen, "the spec came from the attached device"
    assert dict(plan.tile_plans) == planner.tile_plans_for(
        "rwkv6-1.6b", 8, hw.TPU_V5E, max_len=plan.max_len)
    assert kernel_banner(plan).endswith("-> rwkv=pallas (cpu)")
