"""Engine corner paths: mid-chain kills, truncated-parent deferral,
trial-based salting, and checkpoint GC."""

import pytest

from repro.core import SearchPlan, SearchPlanDB, Study
from repro.core.engine import Aggregator, EngineStats, EventLoop, ExecutionEngine, Tuner
from repro.core.hpseq import Constant, HpConfig, MultiStep
from repro.core.trainer import BatchIncompatible, SimulatedTrainer
from repro.core.trial import Trial
from repro.core.tuners import GridTuner, SHATuner
from repro.train.checkpoint import CheckpointStore


def const_trial(v, steps):
    return Trial(HpConfig({"lr": Constant(v)}), steps)


# ---------------------------------------------------------------------------
# kill a trial while its chain is running: waiter cleanup
# ---------------------------------------------------------------------------


class KillOnFirstResult(Tuner):
    """Submits a short and a long trial on one node; kills the long one the
    moment the short result arrives (its tail stage is already running)."""

    def __init__(self, short, long):
        self.short, self.long = short, long
        self.got = []
        self._done = False

    def start(self, handle):
        self._handle = handle
        handle.submit(self.short)
        handle.submit(self.long)

    def on_result(self, trial, step, metrics):
        self.got.append((trial.trial_id, step))
        if trial.trial_id == self.short.trial_id:
            self._handle.kill(self.long)
            self._done = True

    def is_done(self):
        return self._done


def test_kill_mid_chain_cleans_waiters():
    plan = SearchPlan()
    short, long = const_trial(0.1, 50), const_trial(0.1, 150)
    eng = ExecutionEngine(plan, SimulatedTrainer(), n_workers=1)
    tuner = KillOnFirstResult(short, long)
    eng.run([tuner])
    # the long trial never observed a result after its kill
    assert all(tid != long.trial_id for tid, _ in tuner.got)
    # no wait-list entry still references the killed trial
    for ws in eng.aggregator.waiters.values():
        assert all(t.trial_id != long.trial_id for _, t in ws)
    assert plan.pending_requests() == []
    assert long.trial_id in eng.aggregator.killed


# ---------------------------------------------------------------------------
# _truncate + parent-not-produced early return in _execute_chain
# ---------------------------------------------------------------------------


def test_truncated_parent_defers_dependent_chain():
    """With a tight chain budget the shared prefix is cut before producing
    the branch's input state; the branch chain must defer to a later round
    (and the run must still complete losslessly)."""
    db = SearchPlanDB()
    st = Study.create(db, "m", "d", ("lr",))
    trials = [
        Trial(HpConfig({"lr": Constant(0.1)}), 50),                    # cut @50
        Trial(HpConfig({"lr": MultiStep(0.1, [100], values=[0.1, 0.05])}), 200),
        Trial(HpConfig({"lr": MultiStep(0.1, [100], values=[0.1, 0.02])}), 150),
    ]
    tuner = GridTuner(trials)
    stats = st.run(tuner, SimulatedTrainer(), n_workers=2,
                   max_steps_per_chain=40)
    assert tuner.is_done()
    assert stats.chains_deferred >= 1          # the early-return fired
    plan = db.get(st.key)
    assert plan.pending_requests() == []       # deferred work was rescheduled
    for t in trials:                           # every leaf got its metrics
        leaf = plan.nodes[plan.trial_paths[t.trial_id][-1]]
        assert leaf.metrics


# ---------------------------------------------------------------------------
# share=False salting: two identical studies must not dedup
# ---------------------------------------------------------------------------


class OneShot(Tuner):
    def __init__(self, trial):
        self.trial = trial
        self._done = False

    def start(self, handle):
        handle.submit(self.trial)

    def on_result(self, trial, step, metrics):
        self._done = True

    def is_done(self):
        return self._done


def test_trial_salting_prevents_cross_study_dedup():
    trial_a, trial_b = const_trial(0.1, 100), const_trial(0.1, 100)
    assert trial_a.trial_id == trial_b.trial_id   # identical configs

    shared = SearchPlan()
    eng = ExecutionEngine(shared, SimulatedTrainer(), n_workers=2, share=True)
    eng.run([OneShot(trial_a), OneShot(trial_b)])
    assert eng.stats.steps_run == 100             # stage mode dedups

    salted = SearchPlan()
    eng2 = ExecutionEngine(salted, SimulatedTrainer(), n_workers=2, share=False)
    eng2.run([OneShot(trial_a), OneShot(trial_b)])
    assert eng2.stats.steps_run == 200            # trial mode trains twice
    roots = salted.children[None]
    assert len(roots) == 2                        # distinct salted roots
    for nid in roots:
        assert len(salted.nodes[nid].trials) == 1


# ---------------------------------------------------------------------------
# checkpoint GC
# ---------------------------------------------------------------------------


def test_kill_evicts_only_unreferenced_nodes():
    plan = SearchPlan()
    t1 = const_trial(0.1, 100)
    t2 = Trial(HpConfig({"lr": MultiStep(0.1, [100], values=[0.1, 0.05])}), 200)
    root, _, _ = plan.submit(t1)
    leaf, _, _ = plan.submit(t2)          # shares the root node with t1
    store = CheckpointStore()
    cid_root = store.put(plan.path_key(root.node_id), 100, {"w": 1})
    plan.record_result(root.node_id, 100, cid_root, {"val_acc": 0.5})
    cid_leaf = store.put(plan.path_key(leaf.node_id), 200, {"w": 2})
    plan.record_result(leaf.node_id, 200, cid_leaf, {"val_acc": 0.6})

    stats = EngineStats()
    agg = Aggregator(plan, store, stats, EventLoop())
    agg.kill(t1.trial_id)
    # root still referenced by t2 — nothing evicted
    assert stats.ckpt_evictions == 0
    assert store.contains(cid_root)

    agg.kill(t2.trial_id)
    # now both nodes are orphaned: both checkpoints reclaimed
    assert stats.ckpt_evictions == 2
    assert not store.contains(cid_root) and not store.contains(cid_leaf)
    assert root.ckpts == {} and leaf.ckpts == {}


def test_sha_run_reclaims_loser_checkpoints():
    db = SearchPlanDB()
    st = Study.create(db, "m", "d", ("lr",))
    trials = [const_trial(round(0.01 * (i + 1), 3), 120) for i in range(8)]
    tuner = SHATuner(trials, min_steps=30, max_steps=120, eta=2)
    store = CheckpointStore()
    stats = st.run(tuner, SimulatedTrainer(), n_workers=4, store=store)
    assert tuner.is_done()
    assert store.puts > 0          # the caller's (initially empty, falsy)
    #                                store must actually be the one used
    assert stats.ckpt_evictions > 0
    assert len(store) == stats.ckpt_saves - stats.ckpt_evictions
    plan = db.get(st.key)
    for node in plan.nodes.values():       # dead nodes hold no checkpoints
        if node.refcount <= 0:
            assert node.ckpts == {}


# ---------------------------------------------------------------------------
# batched/fused backend calls: only BatchIncompatible falls back
# ---------------------------------------------------------------------------


class _RaisingBackend(SimulatedTrainer):
    """Batches sibling groups and fuses chains, but every such call raises
    ``exc`` (solo per-stage execution still works)."""

    supports_batched_stages = True
    supports_chain_fusion = True

    def __init__(self, exc, where):
        super().__init__()
        self.exc, self.where = exc, where

    def run_stages_batched(self, states, ctxs):
        if self.where == "batched":
            raise self.exc
        return super().run_stages_batched(states, ctxs)

    def run_chains_batched(self, states, chains):
        if self.where == "batched":
            raise self.exc
        return super().run_chains_batched(states, chains)

    def run_chain(self, state, ctxs):
        if self.where == "chain":
            raise self.exc
        return super().run_chain(state, ctxs)


def _forked_siblings_run(backend):
    """Three trials sharing 20 steps, then forking: one worker trains the
    prefix, and the three tails form a sibling group the next round."""
    plan = SearchPlan()
    trials = [Trial(HpConfig({"lr": MultiStep(0.1, [20], values=[0.1, v])}),
                    40) for v in (0.05, 0.02, 0.01)]
    eng = ExecutionEngine(plan, backend, n_workers=1, batch_siblings=True)
    return eng.run([GridTuner(trials)])


@pytest.mark.parametrize("where", ["batched", "chain"])
def test_value_error_from_batched_or_fused_call_propagates(where):
    """A ValueError inside a batched or fused backend call, such as the
    compiler refusing a kernel's lowering, is a fatal fault: it surfaces
    from the run instead of quietly turning into member-sequential or
    per-stage execution."""
    backend = _RaisingBackend(ValueError("kernel lowering refused"), where)
    with pytest.raises(ValueError, match="kernel lowering refused"):
        _forked_siblings_run(backend)


def test_batch_incompatible_runs_members_one_at_a_time():
    """The trainer's in-flight incompatibility signal still degrades a
    group to member-sequential execution with the same results."""
    ref = _forked_siblings_run(SimulatedTrainer())
    got = _forked_siblings_run(
        _RaisingBackend(BatchIncompatible("divergent batch sizes"),
                        "batched"))
    assert ref.batched_groups >= 1
    assert got.batched_groups == 0
    assert got.steps_run == ref.steps_run
    assert got.stages_run == ref.stages_run
