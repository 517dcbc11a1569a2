"""The control, at a size a test run holds: the reference put in the
program's place one precision below what the configuration states
(float8 activations, bfloat16 scores under the round's sigmoid) comes
out not correct, and the reference against itself reads zero."""
import pytest

import bench_tiny as T


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_control_is_not_correct(family):
    from benchmarks.chip import control
    cell = T.cell(family, "fedtrain-r10")
    got = [r for n, r in control.readings(cell, T.SEED) if n == "control"]
    ctrl_train = next(r for r in got if "loss_gap" in r)
    ctrl_round = next(r for r in got if "round_mismatch" in r)
    assert any(ctrl_train[k] > T.LIMITS[k] for k in ctrl_train), ctrl_train
    assert ctrl_round["round_mismatch"] > T.LIMITS["round_mismatch"]


def test_reference_against_itself_reads_zero():
    from benchmarks.chip import checks
    cell = T.cell("dense", "fedtrain-r10")
    from benchmarks.chip import control
    b = control.batches_for(cell, T.SEED, 2)
    ref = checks.reference_train(cell, T.SEED, b)
    again = checks.reference_train(cell, T.SEED, b)
    nums = checks.compare_train(again, ref)
    assert nums == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
