"""The control, the plain reference in the precision below the
configuration's put in the program's place, comes out as not correct
through the harness's own comparison; the program comes out as correct.
At a size a test run holds (the chip readings are in PERF.md)."""

import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("control"))


CELLS = [("tiny-rwkv.tiny-closed", "mean_logit_gap", "program_mean_logit_gap"),
         ("tiny-rnn.tiny-b1", "rnn_max_abs_gap", "program_max_abs_gap")]


@pytest.mark.parametrize("cell,number,program", CELLS)
def test_control_fails_and_program_passes(root, cell, number, program):
    line = tiny.run(root, cell, seconds=2.0, control=True)
    limit = line["check"][number]["limit"]
    assert not line["correct"]
    assert line["check"][number]["value"] > limit
    assert line["check"][program]["limit"] is None
    assert line["check"][program]["value"] <= limit


@pytest.mark.parametrize("cell,number,program", CELLS)
def test_program_run_is_correct(root, cell, number, program):
    line = tiny.run(root, cell, seconds=2.0)
    assert line["correct"]
    assert set(line["check"]) == {number}
    assert line["check"][number]["value"] <= line["check"][number]["limit"]
