"""benchmarks/serving_load.py: determinism contract + document schema."""

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import serving_load as sl  # noqa: E402
from repro.configs import SERVING_LOAD_SWEEP, ServingLoadCell  # noqa: E402


def _assert_metrics_match(fresh, committed):
    """A fresh metrics block against a committed one: keys, counts,
    tokens and ticks exactly; floats to rel 1e-12, because the committed
    means were summed in sample order before ``math.fsum`` replaced the
    interpreter's ``sum``."""
    if isinstance(committed, dict):
        assert isinstance(fresh, dict) and set(fresh) == set(committed)
        for k in committed:
            _assert_metrics_match(fresh[k], committed[k])
    elif isinstance(committed, float) and not isinstance(fresh, bool):
        if math.isnan(committed):
            assert math.isnan(fresh)
        else:
            assert fresh == pytest.approx(committed, rel=1e-12, abs=0)
    else:
        assert fresh == committed and type(fresh) is type(committed)


def test_sweep_spans_three_families():
    assert {c.family for c in SERVING_LOAD_SWEEP} == {"dense", "moe", "rwkv"}
    assert len({c.arch for c in SERVING_LOAD_SWEEP}) >= 3


def test_sweep_cell_names_unique_and_dimensions_present():
    names = [c.name for c in SERVING_LOAD_SWEEP]
    assert len(names) == len(set(names))
    # the new committed benchmark dimensions: prompt distributions and the
    # overload scheduling scenario ride along without renaming base cells
    assert {c.prompt_dist for c in SERVING_LOAD_SWEEP} >= \
        {"uniform", "fixed", "lognormal", "bimodal"}
    overload = [c for c in SERVING_LOAD_SWEEP if c.deadline_slack is not None]
    assert {(c.policy, c.preempt) for c in overload} == \
        {("fcfs", False), ("edf", False), ("edf", True)}
    base = [c for c in SERVING_LOAD_SWEEP
            if c.policy == "fcfs" and not c.preempt
            and c.prompt_dist == "uniform" and c.heavy_decode is None
            and c.cache_layout == "dense"]
    assert all("/" not in c.name.replace(f"{c.arch}/", "", 1).replace(
        f"b{c.max_batch}/", "", 1) for c in base)   # historical names intact
    # PR 7: paged cells ride along, tagged by layout, never renaming the
    # dense twins they are compared against
    paged = [c for c in SERVING_LOAD_SWEEP if c.cache_layout != "dense"]
    assert paged and all(c.name.endswith("/paged16") for c in paged)


def test_smoke_registry_guard_detects_drift(monkeypatch):
    """The --smoke CI guard passes on the real registry and fails loudly
    when the scheduler registry and the CLI --policy choices diverge."""
    from repro.serving import scheduler as sched_mod

    sl._check_policy_registry()   # current surfaces agree
    monkeypatch.setitem(sched_mod.SCHEDULERS, "fake", sched_mod.FCFS)
    with pytest.raises(RuntimeError, match="drifted"):
        sl._check_policy_registry()


def test_committed_cells_embed_plans_and_auto_beats_default():
    """Plan-centric acceptance: every committed BENCH cell embeds a
    valid *resolved* plan dict, and the autotuned overload cell beats the
    hand-picked default design point on the same workload."""
    import json
    from pathlib import Path

    from repro.plan import io as plan_io

    doc = json.loads((Path(__file__).resolve().parent.parent /
                      "BENCH_serving.json").read_text())
    for c in doc["cells"]:
        plan = plan_io.from_dict(c["plan"])
        plan.validate()
        assert plan.buckets is not None        # resolved, not defaulted
        assert plan.arch == c["arch"]
        assert plan.max_batch == c["max_batch"]
    auto = [c for c in doc["cells"] if c["name"].endswith("/auto")]
    assert auto, "the sweep must record the autotuned overload cell"
    fcfs = next(c for c in doc["cells"]
                if c["name"] == "rwkv6-1.6b/b4/r0.8/heavy")
    for c in auto:
        assert c["plan"]["provenance"]["autotune"]["probes"]
        assert c["metrics"]["ttft"]["p95"] < fcfs["metrics"]["ttft"]["p95"]
        assert (c["metrics"]["slo"]["attainment"]
                > fcfs["metrics"]["slo"]["attainment"])


def test_committed_drift_cells_show_replan_beating_stale():
    """Observability acceptance: the committed drifting-workload cells
    embed valid plans; the replan's provenance records the profile
    fitted from the observed trace plus a trace summary, and the
    re-autotuned plan beats the stale calm-tuned plan on SLO
    attainment on the same drifted workload."""
    import json
    from pathlib import Path

    doc = json.loads((Path(__file__).resolve().parent.parent /
                      "BENCH_serving.json").read_text())
    cells = {c["name"]: c for c in doc["cells"]}
    stale = next(c for n, c in cells.items() if n.endswith("/drift-stale"))
    replan = next(c for n, c in cells.items()
                  if n.endswith("/drift-replan"))
    # the stale plan was tuned on calm deadline-free traffic: no deadline
    # policy, and its probe workload is not the drifted one
    assert stale["plan"]["policy"] == "fcfs"
    prov = replan["plan"]["provenance"]
    assert prov["autotune"]["probes"]
    obs = prov["observed_traffic"]
    assert obs["trace_summary"]["submits"] > 0
    assert obs["trace_summary"]["with_deadline"] > 0
    assert obs["fitted_profile"]["rate"] > 0
    # the drift the replan must react to: more capacity than the stale plan
    assert replan["plan"]["max_batch"] > stale["plan"]["max_batch"]
    assert (replan["metrics"]["slo"]["attainment"]
            > stale["metrics"]["slo"]["attainment"])


def test_committed_paged_twin_bit_exact_and_capacity_rises():
    """PR 7 acceptance, from the committed file alone: the paged twin of a
    base-grid cell carries a byte-identical metrics block (the block-table
    backing store changed no schedule), and the paged b8 capacity cells
    show heavy-tail workloads admitted with less queueing than the b4
    dense baseline on the same prompt distribution (virtual-clock
    schedules depend only on scheduling parameters, so the cells are
    directly comparable across archs)."""
    import json
    from pathlib import Path

    doc = json.loads((Path(__file__).resolve().parent.parent /
                      "BENCH_serving.json").read_text())
    cells = {c["name"]: c for c in doc["cells"]}
    dense = cells["qwen2.5-14b/b4/r1"]
    paged = cells["qwen2.5-14b/b4/r1/paged16"]
    assert paged["metrics"] == dense["metrics"]
    assert paged["plan"]["cache_layout"] == "paged:16"
    assert dense["plan"].get("cache_layout", "dense") == "dense"
    for dist in ("lognormal", "bimodal"):
        big = cells[f"qwen2.5-14b/b8/r1/{dist}/paged16"]
        small = cells[f"rwkv6-1.6b/b4/r1/{dist}"]
        assert big["metrics"]["completed"] == big["metrics"]["submitted"]
        assert (big["metrics"]["queue_wait"]["p95"]
                < small["metrics"]["queue_wait"]["p95"])


def test_committed_fragmentation_trajectory_contracts():
    """The committed BENCH_fragmentation.json memory trajectories uphold
    the PR 7 contracts offline: identical tokens-in-flight under both
    layouts (the schedule is layout-blind), paged bytes-resident never
    above dense at any sample, and the recorded peaks/savings consistent
    with their own trajectories."""
    import json
    from pathlib import Path

    from benchmarks import fig4_fragmentation as f4

    doc = json.loads((Path(__file__).resolve().parent.parent /
                      "BENCH_fragmentation.json").read_text())
    assert doc["schema"] == f4.SCHEMA
    cells = doc["cells"]
    assert len(cells) >= 4
    for c in cells:
        d, p = c["dense"], c["paged"]
        assert d["tokens_in_flight"] == p["tokens_in_flight"]
        assert all(pb <= db for pb, db in
                   zip(p["bytes_resident"], d["bytes_resident"]))
        assert d["peak_bytes"] == max(d["bytes_resident"])
        assert p["peak_bytes"] == max(p["bytes_resident"])
        assert c["peak_saving_bytes"] == d["peak_bytes"] - p["peak_bytes"]
    # the attention-bearing heavy-tail cells actually save at peak; the
    # pure-RNN cells tie exactly (recurrent state is never paged)
    saved = {c["name"]: c["peak_saving_bytes"] for c in cells}
    assert all(v > 0 for n, v in saved.items() if n.startswith("qwen"))
    assert all(v == 0 for n, v in saved.items() if n.startswith("rwkv"))


@pytest.mark.slow
def test_paged_rerun_reproduces_committed_dense_metrics():
    """Live half of the bit-exactness contract: re-running the committed
    dense base cell with a paged:16 backing store reproduces the committed
    dense metrics block byte-for-byte."""
    import dataclasses
    import json
    from pathlib import Path

    doc = json.loads((Path(__file__).resolve().parent.parent /
                      "BENCH_serving.json").read_text())
    committed = {c["name"]: c for c in doc["cells"]}
    dense_cell = next(c for c in SERVING_LOAD_SWEEP
                      if c.name == "qwen2.5-14b/b4/r1")
    paged_cell = ServingLoadCell(
        dense_cell.arch, dense_cell.family, dense_cell.max_batch,
        dense_cell.rate,
        plan=dataclasses.replace(dense_cell.plan, cache_layout="paged:16"))
    fresh = sl.run_cell(paged_cell, duration=doc["duration"],
                        seed=doc["seed"])
    _assert_metrics_match(fresh["metrics"],
                          committed["qwen2.5-14b/b4/r1"]["metrics"])


@pytest.mark.slow
def test_cell_metrics_identical_across_runs():
    """The acceptance contract: two same-seed virtual-clock runs of a cell
    produce byte-identical metrics (fresh engine each time)."""
    cell = ServingLoadCell("rwkv6-1.6b", "rwkv", 2, 0.5)
    a = sl.run_cell(cell, duration=12.0, seed=3)
    b = sl.run_cell(cell, duration=12.0, seed=3)
    assert a["metrics"] == b["metrics"]
    # a different seed must actually change the workload
    c = sl.run_cell(cell, duration=12.0, seed=4)
    assert c["metrics"] != a["metrics"]


@pytest.mark.slow
def test_refactor_matches_committed_trajectory():
    """The multi-layer refactor contract: a fresh run of a base-grid cell
    reproduces the committed BENCH_serving.json metrics block byte-for-
    byte (scheduler extraction + slot-state manager + overlapped prefill
    changed no FCFS schedule)."""
    import json
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "BENCH_serving.json"
    doc = json.loads(bench.read_text())
    committed = {c["name"]: c for c in doc["cells"]}
    cell = next(c for c in SERVING_LOAD_SWEEP
                if c.name == "rwkv6-1.6b/b2/r1")
    fresh = sl.run_cell(cell, duration=doc["duration"], seed=doc["seed"])
    _assert_metrics_match(fresh["metrics"], committed[cell.name]["metrics"])


@pytest.mark.slow
def test_overload_edf_improves_p95_ttft():
    """The overload acceptance: under the committed overload scenario the
    EDF cells beat FCFS on p95 TTFT, and preemptive EDF actually
    preempts, with every preempted request still completing."""
    cells = {(c.policy, c.preempt): c for c in SERVING_LOAD_SWEEP
             if c.deadline_slack is not None}
    built = sl._build("rwkv6-1.6b", True)
    out = {k: sl.run_cell(c, seed=0, _built=built)
           for k, c in cells.items()}
    fcfs = out[("fcfs", False)]["metrics"]
    for key in (("edf", False), ("edf", True)):
        m = out[key]["metrics"]
        assert m["ttft"]["p95"] < fcfs["ttft"]["p95"]
        assert m["completed"] == m["submitted"]
    assert out[("edf", True)]["sched"]["preemptions"] > 0
    assert out[("edf", True)]["sched"]["resumes"] == \
        out[("edf", True)]["sched"]["preemptions"]


@pytest.mark.slow
def test_sweep_document_schema(tmp_path):
    """A trimmed sweep (one cell per family) produces the BENCH_serving
    document shape the perf trajectory consumes."""
    seen, cells = set(), []
    for c in SERVING_LOAD_SWEEP:
        if c.family not in seen:
            seen.add(c.family)
            cells.append(c)
    doc = sl.sweep(fast=True, cells=cells, duration=10.0)
    assert doc["schema"] == sl.SCHEMA
    assert doc["families"] == ["dense", "moe", "rwkv"]
    assert len(doc["cells"]) == 3
    for c in doc["cells"]:
        m = c["metrics"]
        assert m["completed"] == m["submitted"] > 0
        for key in ("ttft", "tpot", "queue_wait"):
            assert {"p50", "p95", "p99", "mean", "n"} <= set(m[key])
        assert m["tokens_per_sec"] > 0
        # mean_util is the TRUE ratio and may exceed 1.0 on instant-admit
        # ticks (several one-token requests through one slot in one tick)
        assert m["mean_util"] > 0.0
        assert c["wall"]["seconds"] > 0
    # round-trips through the writer, and the deterministic view drops wall
    sl.write(doc, str(tmp_path / "BENCH_serving.json"))
    det = sl.deterministic_view(doc)
    assert "wall" not in det["cells"][0] and "metrics" in det["cells"][0]
