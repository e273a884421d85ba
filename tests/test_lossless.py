"""THE correctness property of stage-based execution: it is lossless.

Training a shared prefix once and forking the checkpoint must produce
bit-identical parameters and metrics to training every trial straight
through (real JAX training, deterministic pipeline, CPU floats).

The fused data plane adds two more execution paths — whole-stage chunk
executables and batched sibling groups — and both must stay bit-identical
to the seed per-step loop (``run_stage_stepwise``), including across
mid-chunk batch-size changes that force a fresh executable cache entry.
"""

import jax
import numpy as np
import pytest

from repro.core import (Constant, HpConfig, MultiStep, SearchPlanDB, StepLR,
                        Study)
from repro.core.searchplan import SearchPlan
from repro.core.trainer import StageContext
from repro.core.trial import Trial
from repro.core.tuners import GridTuner
from repro.data import DataPipeline, synthetic_cifar
from repro.models.resnet import ResNet
from repro.train.jax_trainer import JaxTrainer


@pytest.fixture(scope="module")
def setup():
    data = synthetic_cifar(256, seed=0)
    eval_data = synthetic_cifar(128, seed=1)
    task = ResNet(n=1, width=8)
    def pipe():
        return DataPipeline(data, batch_size=32, seed=3)
    # pin the CPU reference path: on an accelerator dev box the backend
    # gate would otherwise swap in the lax.scan body, which only promises
    # ~1-2 ulp — these tests assert bit equality
    backend = JaxTrainer(task, pipe, eval_data, default_optimizer="momentum",
                         backend="cpu")
    return backend


def straight_through(backend, trial, steps):
    """Run a trial solo, stage by stage along its own path."""
    plan = SearchPlan("solo-" + trial.trial_id)
    node, _, _ = plan.submit(trial, steps)
    state = backend.init_state()
    path = plan.path_to_root(node.node_id)
    for i, n in enumerate(path):
        stop = steps if i == len(path) - 1 else path[i + 1].start
        ctx = StageContext(n.node_id, n.desc, n.start, n.start, stop,
                           plan.path_key(n.node_id))
        state = backend.run_stage(state, ctx)
    return state, backend.evaluate(state, None)


def test_stage_execution_is_bitwise_lossless(setup):
    backend = setup
    trials = [
        Trial(HpConfig({"lr": Constant(0.05), "bs": Constant(32)}), 24),
        Trial(HpConfig({"lr": MultiStep(0.05, [12], values=[0.05, 0.005]),
                        "bs": Constant(32)}), 24),
        Trial(HpConfig({"lr": MultiStep(0.05, [12], values=[0.05, 0.01]),
                        "bs": MultiStep(32, [18], values=[32, 64])}), 24),
    ]

    db = SearchPlanDB()
    study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
    eng = study.engine(backend, n_workers=2)
    eng.run([GridTuner(list(trials))])
    plan = db.get(study.key)

    for t in trials:
        leaf = plan.nodes[plan.trial_paths[t.trial_id][-1]]
        merged_metrics = leaf.metrics[24]
        cid = leaf.ckpts[24]
        merged_params = eng.store.get(cid)["params"]

        solo_state, solo_metrics = straight_through(backend, t, 24)
        assert merged_metrics["loss"] == solo_metrics["loss"], t
        assert merged_metrics["val_acc"] == solo_metrics["val_acc"], t
        for a, b in zip(jax.tree.leaves(merged_params),
                        jax.tree.leaves(solo_state["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shared_prefix_checkpoint_is_shared(setup):
    backend = setup
    a = Trial(HpConfig({"lr": Constant(0.05), "bs": Constant(32)}), 20)
    b = Trial(HpConfig({"lr": MultiStep(0.05, [10], values=[0.05, 0.005]),
                        "bs": Constant(32)}), 20)
    db = SearchPlanDB()
    study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
    eng = study.engine(backend, n_workers=2)
    stats = eng.run([GridTuner([a, b])])
    # shared prefix [0,10) trained once: total steps < 40
    assert stats.steps_run == 30


def test_batch_size_change_resumes_pipeline_position(setup):
    """bs sequence changes batch shape mid-trial; the pipeline cursor must
    carry across the boundary (paper §5.1)."""
    backend = setup
    t = Trial(HpConfig({"lr": Constant(0.05),
                        "bs": MultiStep(32, [8], values=[32, 64])}), 16)
    state, metrics = straight_through(backend, t, 16)
    assert state["step"] == 16
    assert state["data"][3] == 64              # final batch size
    assert np.isfinite(metrics["loss"])


# ---------------------------------------------------------------------------
# fused data plane: all execution paths bit-identical to the per-step loop
# ---------------------------------------------------------------------------


def assert_states_identical(a, b):
    assert a["step"] == b["step"]
    assert tuple(a["data"]) == tuple(b["data"])
    for tree_a, tree_b in ((a["params"], b["params"]), (a["opt"], b["opt"])):
        la, lb = jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_scan_equals_stepwise_bitwise(setup):
    """Whole-stage fused execution == seed per-step loop, bit for bit —
    including a mid-chunk bs change (boundary at step 10, chunk length 8)
    that re-batches the pipeline and forces a new executable cache entry
    for the (64, ...) batch shape."""
    fused = setup
    assert fused.fused and fused.chunk_steps == 8
    stepwise = JaxTrainer(fused.task, fused.pipeline_factory,
                          {k: np.asarray(v) for k, v in fused.eval_batch.items()},
                          default_optimizer="momentum", fused=False,
                          backend="cpu")
    trials = [
        Trial(HpConfig({"lr": MultiStep(0.05, [7], values=[0.05, 0.01]),
                        "bs": Constant(32)}), 19),
        Trial(HpConfig({"lr": Constant(0.05),
                        "bs": MultiStep(32, [10], values=[32, 64])}), 16),
    ]
    for t in trials:
        fused_state, fused_metrics = straight_through(fused, t, t.total_steps)
        step_state, step_metrics = straight_through(stepwise, t, t.total_steps)
        assert_states_identical(fused_state, step_state)
        assert fused_metrics == step_metrics
    # the bs change split the stage into constant-shape runs: one executable
    # cache entry per batch shape
    batch_dims = set()
    for key in fused._chunk_fns:
        if key[0] == "fused":
            slab_sig = key[3]
            batch_dims.add({k: shape for k, shape, _ in slab_sig}["images"][0])
    assert {32, 64} <= batch_dims


def test_chain_fused_depth4_equals_stepwise_bitwise(setup):
    """Chain-fused execution (device-resident carry across stage
    boundaries, write-behind checkpoints) == seed per-step loop, bit for
    bit, on a depth-4 chain that includes a mid-chain ``report`` boundary
    (step 12) and a mid-chain batch-size change (step 16)."""
    fused = setup
    stepwise = JaxTrainer(fused.task, fused.pipeline_factory,
                          {k: np.asarray(v) for k, v in fused.eval_batch.items()},
                          default_optimizer="momentum", fused=False,
                          backend="cpu")

    trial = Trial(HpConfig({"lr": MultiStep(0.05, [8, 16],
                                            values=[0.05, 0.02, 0.01]),
                            "bs": MultiStep(32, [16], values=[32, 64])}), 24)

    class MidChainReportTuner(GridTuner):
        # both requests pending up front -> ONE chain with a report
        # boundary at 12 (stages [0,8)[8,12)*[12,16)[16,24)*)
        def start(self, handle):
            self.handle = handle
            for t in self.trials:
                handle.submit(t, upto=12)
                handle.submit(t)

        def on_result(self, t, step, metrics):
            if step == t.total_steps:
                super().on_result(t, step, metrics)

    db = SearchPlanDB()
    study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
    eng = study.engine(fused, n_workers=1)
    assert eng.chain_fusion
    tuner = MidChainReportTuner([trial])
    stats = eng.run([tuner])
    assert stats.chain_fused_stages >= 4
    assert stats.ckpt_async_writes >= 4
    assert eng.store.pending_writes == 0       # shutdown flush barrier

    plan = db.get(study.key)
    leaf = plan.nodes[plan.trial_paths[trial.trial_id][-1]]
    merged_params = eng.store.get(leaf.ckpts[24])["params"]
    solo_state, solo_metrics = straight_through(stepwise, trial, 24)
    assert leaf.metrics[24]["loss"] == solo_metrics["loss"]
    assert leaf.metrics[24]["val_acc"] == solo_metrics["val_acc"]
    for a, b in zip(jax.tree.leaves(merged_params),
                    jax.tree.leaves(solo_state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the mid-chain report observed the state a stepwise run sees at 12
    mid = plan.nodes[plan.trial_paths[trial.trial_id][1]]
    _, mid_metrics = straight_through(stepwise, trial, 12)
    assert mid.metrics[12] == mid_metrics


def test_batched_siblings_equal_stepwise_bitwise(setup):
    """Sibling-trial batching: a group of divergent siblings executed as ONE
    compiled call must reproduce each member's straight-through per-step
    training exactly."""
    fused = setup
    stepwise = JaxTrainer(fused.task, fused.pipeline_factory,
                          {k: np.asarray(v) for k, v in fused.eval_batch.items()},
                          default_optimizer="momentum", fused=False,
                          backend="cpu")
    trials = [
        Trial(HpConfig({"lr": MultiStep(0.05, [12], values=[0.05, v]),
                        "bs": Constant(32)}), 24)
        for v in (0.02, 0.01, 0.005)
    ]
    db = SearchPlanDB()
    study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
    # one worker: the prefix chain carries one sibling tail with it; the
    # other two meet as ready resume stages and batch as one group
    eng = study.engine(fused, n_workers=1)
    stats = eng.run([GridTuner(list(trials))])
    assert stats.batched_groups >= 1
    assert stats.batched_stages >= 2

    plan = db.get(study.key)
    for t in trials:
        leaf = plan.nodes[plan.trial_paths[t.trial_id][-1]]
        merged_params = eng.store.get(leaf.ckpts[24])["params"]
        solo_state, solo_metrics = straight_through(stepwise, t, 24)
        assert leaf.metrics[24]["loss"] == solo_metrics["loss"]
        for a, b in zip(jax.tree.leaves(merged_params),
                        jax.tree.leaves(solo_state["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_delta_encoded_store_is_bitwise_lossless(setup, tmp_path):
    """Checkpoint plane v2: real training through a directory store with
    delta-encoded boundary checkpoints (the dispatcher threads each
    boundary's fork-point cid as the delta parent) must restore leaves
    bit-identical to the per-step straight-through run — delta chains and
    zero-copy reads included."""
    fused = setup
    stepwise = JaxTrainer(fused.task, fused.pipeline_factory,
                          {k: np.asarray(v) for k, v in fused.eval_batch.items()},
                          default_optimizer="momentum", fused=False,
                          backend="cpu")
    trials = [
        Trial(HpConfig({"lr": MultiStep(0.05, [8], values=[0.05, v]),
                        "bs": Constant(32)}), 16)
        for v in (0.02, 0.01)
    ]
    from repro.train.checkpoint import CheckpointStore
    store = CheckpointStore(str(tmp_path / "ckpts"))
    db = SearchPlanDB()
    study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
    eng = study.engine(fused, n_workers=2, store=store)
    stats = eng.run([GridTuner(list(trials))])
    # sibling forks off a shared prefix -> boundary commits are deltas
    # (byte *reduction* is the bench's claim on partially-mutated states;
    # SGD touches every chunk, so here only the encoding path is asserted)
    assert store.delta_commits > 0
    assert stats.ckpt_delta_commits == store.delta_commits

    # cold reads straight off the blobs: drop every warm cache first
    store._read_cache.clear()
    plan = db.get(study.key)
    for t in trials:
        leaf = plan.nodes[plan.trial_paths[t.trial_id][-1]]
        restored = store.get(leaf.ckpts[16])
        solo_state, solo_metrics = straight_through(stepwise, t, 16)
        assert leaf.metrics[16]["loss"] == solo_metrics["loss"]
        assert_states_identical(
            {k: restored[k] for k in ("step", "data", "params", "opt")},
            solo_state)


def test_one_device_mesh_workers_bitwise_equal_thread_workers(setup):
    """Distribution plane v2: a fleet of width-1 worker meshes takes the
    backend's default (unsharded) execution path — leaf checkpoints and
    metrics are bit-identical to plain thread workers, while the engine
    still counts the mesh placements (and serves same-host resumes
    device-to-device)."""
    backend = setup
    trials = [
        Trial(HpConfig({"lr": MultiStep(0.05, [8], values=[0.05, v]),
                        "bs": Constant(32)}), 16)
        for v in (0.02, 0.005)
    ]

    def run(meshes):
        db = SearchPlanDB()
        study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
        eng = study.engine(backend, n_workers=2, worker_meshes=meshes)
        stats = eng.run([GridTuner(list(trials))])
        return db.get(study.key), eng, stats

    # one device here: both width-1 workers own device 0 (a fleet spread
    # over several devices is tests/test_meshplane.py's subprocess check)
    from repro.dist.meshes import WorkerMesh
    plan_t, eng_t, stats_t = run(None)
    plan_m, eng_m, stats_m = run([WorkerMesh.build([0])] * 2)

    assert stats_m.mesh_placements > 0
    assert stats_t.mesh_placements == 0
    assert stats_m.steps_run == stats_t.steps_run
    for t in trials:
        leaf = plan_m.trial_paths[t.trial_id][-1]
        assert plan_m.nodes[leaf].metrics[16] == plan_t.nodes[leaf].metrics[16]
        assert_states_identical(eng_m.store.get(plan_m.nodes[leaf].ckpts[16]),
                                eng_t.store.get(plan_t.nodes[leaf].ckpts[16]))
