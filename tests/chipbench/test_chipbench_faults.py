"""A run of a cell with the timed path broken underneath reads
``correct: false``: the harness's look for a chip is skipped and
everything else of a run is driven, at ResNet n=1 on the CPU."""

import os
import sys
import time

import jax
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import harness  # noqa: E402


class Unchanged(harness.SpanTrainer):
    """Every chunk executable returns the state it was given."""

    def _call_executable(self, key, build, donate, args):
        return args[0], 0.0


class _HalfBatch:
    def __init__(self, task):
        self.task = task

    def init(self, rng):
        return self.task.init(rng)

    def loss(self, params, batch):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return self.task.loss(params, half)


class HalfBatch(harness.SpanTrainer):
    """Training steps leave out half of the batch and take the mean over
    the rest (evaluation is untouched)."""

    def __init__(self, task, *a, **kw):
        super().__init__(_HalfBatch(task), *a, **kw)
        self._eval_fn = jax.jit(task.loss)


class AlteredAnswer(harness.SpanTrainer):
    """The loss each evaluation reports is 2% off where it is produced."""

    def evaluate(self, state, ctx):
        out = super().evaluate(state, ctx)
        out["loss"] *= 1.02
        return out


@pytest.mark.parametrize("trainer_cls, number", [
    (Unchanged, "change_gap"), (HalfBatch, "root_diff"),
    (AlteredAnswer, "loss_gap")])
def test_fault_reads_incorrect(trainer_cls, number):
    cell = harness.rehearsal_cell("wrn16-8.sha-paper")
    out = harness.run_cell(cell, 97, 6.0, False, time.perf_counter(),
                           trainer_cls=trainer_cls)
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]
