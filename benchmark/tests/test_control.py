"""The control: the plain reference put in the program's place with one
stated guarantee broken — it accepts a commit once MORE THAN HALF of the
power has signed, where the configuration says more than two thirds. On
honest traffic its verdicts are the program's; `correct` has to come out
false all the same."""

import pytest

from benchmark import control, run
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("ctl")))


@pytest.mark.parametrize("cell,expect", [
    ("tinylight.sequential", {"sigs_verified_minus_needed", "warmup_refusal_height_delta"}),
    ("tinyfull.blocksync", {"sigs_asked_minus_needed", "warmup_refusal_faults"}),
])
@pytest.mark.parametrize("seed", [3000000043, 3000000047, 3000000053])
def test_weak_quorum_control_is_not_correct(root, cell, expect, seed):
    with control.weak_quorum():
        res = run.execute(root, cell, seed, 0.4, False, device=tiny.CPU_DEVICE)
    failed = {k for k, c in res["checks"].items() if not c["ok"]}
    assert res["correct"] is False and expect <= failed
    assert res["checks"]["verdict_mismatches"]["ok"]  # honest traffic reads the same
