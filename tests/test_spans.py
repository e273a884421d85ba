"""The program's instrumentation: the span vocabulary, and the host-clock
counters of a result's wait (request to the start of the work unit that
serves it, then to the tuner)."""

import dataclasses

import jax

from repro.core import SearchPlanDB, Study, StudyService, StudySpec
from repro.core.engine import capture_session, restore_engine
from repro.core.hpseq import Constant, HpConfig
from repro.core.trainer import SimulatedTrainer
from repro.core.trial import Trial
from repro.core.tuners import GridTuner, SHATuner
from repro.utils.spans import SPANS, span


def const_trial(v, steps):
    return Trial(HpConfig({"lr": Constant(v)}), steps)


class Counting:
    """Counts the results a tuner is given."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.n_results = 0

    def on_result(self, trial, step, metrics):
        self.n_results += 1
        super().on_result(trial, step, metrics)


class CountingSHA(Counting, SHATuner):
    pass


class CountingGrid(Counting, GridTuner):
    pass


def test_span_vocabulary():
    assert len(set(SPANS)) == len(SPANS)
    assert all(n.startswith("hippo.") for n in SPANS)
    # outside a profiler session a span is an ordinary context manager
    with span("hippo.dispatch.unit", width=2, depth=1, steps=8) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_every_result_is_timed_once_instant_ones_at_zero():
    """A study, then the same study again: the second is answered from
    the plan (instant results, counted with no wait and no run)."""
    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(), n_workers=2)
    spec = StudySpec("m", "d", ("lr",))
    lrs = [0.01 * (i + 1) for i in range(4)]
    first = CountingGrid([const_trial(v, 60) for v in lrs])
    svc.submit(spec, first)
    svc.join()
    s = svc.stats
    timed, spent = s.results_timed, s.result_wait_seconds + \
        s.result_run_seconds
    assert timed == first.n_results > 0
    assert s.result_wait_seconds > 0 and s.result_run_seconds > 0
    again = CountingGrid([const_trial(v, 60) for v in lrs])
    svc.submit(spec, again)
    stats = svc.close()
    assert again.n_results == first.n_results
    assert stats.study("study-1").instant_results == again.n_results
    assert stats.results_timed == 2 * timed
    assert stats.result_wait_seconds + stats.result_run_seconds == spent
    # host-clock statistics stay out of the replay contract
    assert dataclasses.replace(stats, result_wait_seconds=1e9,
                               result_run_seconds=1e9,
                               results_timed=0) == stats


def test_restored_waiters_without_stamps_are_not_counted():
    db = SearchPlanDB()
    st = Study.create(db, "m", "d", ("lr",))
    trials = [const_trial(round(0.01 * (i + 1), 3), 120) for i in range(8)]
    tuner = CountingSHA(trials, min_steps=30, max_steps=120, eta=2)
    eng = st.engine(SimulatedTrainer(), n_workers=2)
    eng.admit(tuner)
    eng.step()          # the admission: the first rung's requests wait
    before = sum(len(ws) for ws in eng.aggregator.waiters.values())
    assert before == len(trials)
    restored = restore_engine(capture_session(eng), SimulatedTrainer())
    restored.drain()
    restored.finish()
    assert tuner.is_done()
    # the promotions made after the restore are counted, the first rung
    # (requested before it) is not
    assert restored.stats.results_timed == tuner.n_results - before > 0
