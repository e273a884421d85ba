"""The cell check rehearsed on the CPU at ResNet n=1: a sound run of the
program passes with every gap at rounding, and the check fails the
reference's momentum rule in its velocity form and the bfloat16 control."""

import os
import sys
import time

import jax.numpy as jnp
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import check, harness, reference as ref  # noqa: E402

SEED = 2 ** 33 + 12345     # more than 32 bits: seeds may be that large


@pytest.fixture(scope="module")
def sound():
    cell = harness.rehearsal_cell("wrn16-8.sha-paper")
    out = harness.run_cell(cell, SEED, 6.0, False, time.perf_counter(),
                           use_kernel=True)
    return cell, out


def _recompare(cell, out, reference=None, candidate=None):
    rows, eval_rows = harness.cell_rows(cell, SEED)
    nums = check.compare_study(out["record"], cell.config, rows, eval_rows,
                               cell.traffic, reference or ref.Reference(),
                               candidate=candidate)
    checks = harness.judge(cell.limits, nums)
    return nums, all(c["pass"] for c in checks.values())


def test_rehearsal_passes_at_rounding(sound):
    cell, out = sound
    assert out["correct"], out["checks"]
    nums = out["compared"]
    assert nums["parts_uncompared"] == 0 and nums["unmatched_spans"] == 0
    assert nums["spans"] >= 2 and nums["results"] == 21
    # float32 summed in another order (patch products against XLA's
    # convolutions), over stages of up to 24 steps
    for name in ("change_gap", "mom_gap", "loss_gap"):
        assert nums[name] < 1e-4, (name, nums[name])
    assert out["checks"]["window_compiles"]["value"] == 0


def test_velocity_form_reference_fails(sound, monkeypatch):
    """m <- mu*m + lr*g, p <- p - m - lr*wd*p: the same parameters while lr
    is constant, a momentum buffer lr times the program's."""
    def velocity(p, m, g, lr, mu, wd):
        m = mu * m + lr * g
        return p - m - lr * wd * p, m

    monkeypatch.setattr(ref, "momentum_update", velocity)
    nums, ok = _recompare(*sound)
    assert not ok
    assert nums["mom_gap"] > 5.0


def test_bfloat16_control_fails(sound):
    cell, out = sound
    control = ref.Reference(jnp.bfloat16)
    nums, ok = _recompare(
        cell, out, candidate=lambda span, start, batches, hps:
        control.train(*start, batches, hps))
    assert not ok, nums


def test_calibration_readings_of_control_and_faults(sound):
    """The readings the limits are set from: a state left unchanged reads
    a whole leaf off, an answer altered 2% reads 2% in ``loss_gap``, and
    each fault is judged on the same compared study as the program."""
    from chipbench import calibrate

    cell, out = sound
    got = calibrate.fault_readings(cell, SEED, out["record"])
    assert set(got) == {"control", "half_batch", "unchanged", "altered"}
    assert got["unchanged"]["change_gap"] == pytest.approx(1.0)
    assert got["altered"]["loss_gap"] == pytest.approx(0.02, rel=1e-3)
    assert got["altered"]["change_gap"] == out["compared"]["change_gap"]
    assert got["half_batch"]["root_diff"] > cell.limits["root_diff"]
