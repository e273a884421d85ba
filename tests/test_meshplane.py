"""Distribution plane v2: mesh-aware workers (ROADMAP item 2).

A :class:`Worker` owns a device set (:class:`WorkerMesh`); placement
routes chains and sibling-chain groups through the scheduling policy's
hint and the backend's divisibility gate instead of hardwiring
``idle[0]``; boundary states hand off device-to-device between same-host
workers without a store round-trip.  These tests pin:

* the descriptor itself (validation, pickling, the planner helper);
* placement: policy hints trade batch width against shard width,
  incompatible meshes are rejected (and an all-incompatible fleet
  degrades to replicated execution instead of starving);
* the dispatcher bugfixes this plane flushed out — a deferred chain
  returns its worker to the in-round pool, sibling-group placement goes
  through the policy, and a dedup'd sibling resume is copied before
  fan-out;
* d2d handoff: host-local hits bypass the store (``d2d_handoffs``),
  cross-host and backend-declined transfers fall back to it, and the
  virtual-clock accounting is identical either way;
* fleet equivalences: a 1-device-mesh fleet replays a thread fleet's
  stats exactly, session snapshots round-trip the meshes, and (in a
  subprocess with forced host devices) a stage sharded over a 4-device
  mesh is bitwise-identical to the unsharded run.
"""

import dataclasses
import os
import pickle
import subprocess
import sys

import pytest

from repro.core import SearchPlanDB, Study, StudyService, StudySpec
from repro.core.engine.dispatch import Dispatcher, Worker
from repro.core.engine.engine import EngineStats
from repro.core.engine.events import EventLoop
from repro.core.hpseq import Constant, HpConfig, MultiStep
from repro.core.scheduler import CriticalPathScheduler
from repro.core.searchplan import SearchPlan
from repro.core.trainer import SimulatedTrainer, StageContext
from repro.core.trial import Trial
from repro.core.tuners import GridTuner
from repro.dist.meshes import WorkerMesh, plan_worker_meshes
from repro.train.checkpoint import CheckpointStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class BatchedSim(SimulatedTrainer):
    supports_batched_stages = True


class PickySim(BatchedSim):
    """Accepts only thread workers / trivial meshes — every real mesh is
    rejected by the placement gate."""

    def mesh_compatible(self, mesh, ctxs):
        return mesh is None or mesh.n_devices == 1


def make_dispatcher(plan, backend, workers, store=None, **kw):
    return Dispatcher(plan, backend, CriticalPathScheduler(),
                      store if store is not None else CheckpointStore(),
                      EventLoop(), EngineStats(), workers, **kw)


def sib_trial(tail_lr, total=40):
    return Trial(HpConfig({"lr": MultiStep(0.1, [20],
                                           values=[0.1, tail_lr])}), total)


def seeded_sibling_plan(store, values=(0.05, 0.02, 0.01)):
    """Three sibling trials forking at step 20, with the shared prefix
    already trained and checkpointed in ``store`` — the tails are a ready
    sibling group resuming from one cid."""
    backend = SimulatedTrainer()
    plan = SearchPlan()
    sibs = [sib_trial(v) for v in values]
    for t in sibs:
        plan.submit(t)
    shared = plan.trial_paths[sibs[0].trial_id][0]
    node = plan.node(shared)
    ctx = StageContext(node_id=shared, desc=node.desc,
                       node_start=node.start, start=0, stop=20,
                       path_key=plan.path_key(shared))
    state = backend.run_stage(backend.init_state(), ctx)
    cid = store.put(plan.path_key(shared), 20, state)
    plan.record_result(shared, 20, cid, None)
    return plan, sibs, shared, cid, state


def drain_boundary_cids(disp):
    """{(node_id, stop): cid} for every stage event the dispatcher posted."""
    out = {}
    while disp.events:
        ev = disp.events.pop()
        if ev.kind == "stage":
            out[(ev.payload["node_id"], ev.payload["stop"])] = \
                ev.payload["cid"]
    return out


# ---------------------------------------------------------------------------
# the descriptor
# ---------------------------------------------------------------------------


def test_worker_mesh_descriptor_basics():
    m = WorkerMesh.build([0, 1, 2, 3])
    assert m.n_devices == 4
    assert m.axes == (("data", 4),)
    assert m.sizes == {"data": 4}
    assert m.host == "host0"
    assert m.key == ((0, 1, 2, 3), (("data", 4),), "host0")

    m2 = WorkerMesh.build([0, 1, 2, 3], axes=(("data", 2), ("model", 2)))
    assert m2.sizes == {"data": 2, "model": 2}
    assert m2.key != m.key


def test_worker_mesh_validation():
    with pytest.raises(ValueError):
        WorkerMesh.build([])
    with pytest.raises(ValueError):
        # axis sizes must cover exactly the owned devices
        WorkerMesh.build([0, 1, 2], axes=(("data", 2),))


def test_worker_mesh_pickles():
    m = WorkerMesh.build([4, 5, 6, 7], axes=(("data", 2), ("model", 2)),
                         host="rack3")
    m2 = pickle.loads(pickle.dumps(m))
    assert m2 == m
    assert m2.key == m.key


def test_plan_worker_meshes():
    meshes = plan_worker_meshes(3, 2, host="hq")
    assert len(meshes) == 3
    assert [m.device_ids for m in meshes] == [(0, 1), (2, 3), (4, 5)]
    assert all(m.host == "hq" for m in meshes)
    # <=0 devices: a plain thread fleet
    assert plan_worker_meshes(2, 0) == (None, None)


def test_worker_width_accounting():
    assert Worker(0).devices == 1
    assert Worker(0).host == "host0"
    w = Worker(1, mesh=WorkerMesh.build([0, 1], host="h9"))
    assert w.devices == 2
    assert w.host == "h9"


# ---------------------------------------------------------------------------
# placement: hints, the gate, degradation
# ---------------------------------------------------------------------------


def test_solo_chain_takes_widest_mesh():
    """Default hint for a solo chain is "deep": devices go to sharding."""
    plan = SearchPlan()
    plan.submit(Trial(HpConfig({"lr": Constant(0.1)}), 30))
    narrow = Worker(0, mesh=WorkerMesh.build([0, 1]))
    wide = Worker(1, mesh=WorkerMesh.build([2, 3, 4, 5]))
    disp = make_dispatcher(plan, SimulatedTrainer(), [narrow, wide])
    disp.assign()
    assert not wide.idle
    assert narrow.idle
    assert disp.stats.steps_run == 30
    assert disp.stats.mesh_placements == 1
    # the mesh width is the accounting width
    assert disp.stats.gpu_seconds == pytest.approx(
        4 * (30 * 1.0 + 2.0 + 5.0))          # steps + save + eval


def test_sibling_group_takes_narrowest_mesh():
    """Default hint for a sibling group is "wide": the group already
    parallelizes across trials, so it yields the big mesh to others."""
    store = CheckpointStore()
    plan, sibs, shared, cid, _ = seeded_sibling_plan(store)
    wide = Worker(0, mesh=WorkerMesh.build([0, 1, 2, 3]))
    narrow = Worker(1, mesh=WorkerMesh.build([4, 5]))
    disp = make_dispatcher(plan, BatchedSim(), [wide, narrow], store=store,
                           batch_siblings=True)
    disp.assign()
    assert not narrow.idle
    assert wide.idle
    assert disp.stats.batched_groups == 1
    assert disp.stats.steps_run == 60        # 3 tails x 20
    assert disp.stats.mesh_placements == 1
    assert disp.stats.placement_rejections == 0


def test_incompatible_mesh_redirected_to_thread_worker():
    """The divisibility gate routes work away from meshes the backend
    cannot shard on — the old code would have dumped the group on
    ``idle[0]`` regardless."""
    store = CheckpointStore()
    plan, sibs, shared, cid, _ = seeded_sibling_plan(store)
    meshy = Worker(0, mesh=WorkerMesh.build([0, 1, 2, 3]))
    thread = Worker(1)
    disp = make_dispatcher(plan, PickySim(), [meshy, thread], store=store,
                           batch_siblings=True)
    disp.assign()
    assert meshy.idle
    assert not thread.idle
    assert disp.stats.batched_groups == 1
    assert disp.stats.placement_rejections >= 1
    assert disp.stats.mesh_placements == 0


def test_all_rejected_fleet_degrades_instead_of_starving():
    """When EVERY candidate fails the gate the narrowest mesh hosts the
    work anyway (replicated execution) — rejection must redirect, never
    wedge the plan."""
    store = CheckpointStore()
    plan, sibs, shared, cid, _ = seeded_sibling_plan(store)
    wide = Worker(0, mesh=WorkerMesh.build([0, 1, 2, 3]))
    narrow = Worker(1, mesh=WorkerMesh.build([4, 5]))
    disp = make_dispatcher(plan, PickySim(), [wide, narrow], store=store,
                           batch_siblings=True)
    disp.assign()
    assert disp.stats.steps_run == 60
    assert not narrow.idle                   # narrowest hosts it
    assert wide.idle
    assert disp.stats.placement_rejections == 2
    assert disp.stats.mesh_placements == 1


def test_homogeneous_fleet_places_first_idle():
    """Ties resolve to the earliest candidate: a homogeneous mesh fleet
    behaves exactly like the classic first-idle dispatcher."""
    plan = SearchPlan()
    plan.submit(Trial(HpConfig({"lr": Constant(0.1)}), 30))
    workers = [Worker(i, mesh=m) for i, m in enumerate(plan_worker_meshes(3, 2))]
    disp = make_dispatcher(plan, SimulatedTrainer(), workers)
    disp.assign()
    assert not workers[0].idle
    assert workers[1].idle and workers[2].idle


# ---------------------------------------------------------------------------
# dispatcher bugfixes
# ---------------------------------------------------------------------------


def test_deferred_chain_returns_worker_to_round_pool():
    """A chain deferred because its parent was truncated away must hand
    its worker back to the round — the refill then extracts other ready
    work.  The old code stranded the worker idle for the whole round."""
    plan = SearchPlan()
    t1 = Trial(HpConfig({"lr": MultiStep(0.1, [40, 80],
                                         values=[0.1, 0.05, 0.01])}), 120)
    t2 = Trial(HpConfig({"lr": MultiStep(0.1, [40, 80],
                                         values=[0.1, 0.05, 0.02])}), 120)
    other = Trial(HpConfig({"lr": Constant(0.3)}), 50)
    l1, _, _ = plan.submit(t1)
    l2, _, _ = plan.submit(t2)
    plan.submit(other)
    # profile the sibling leaves heavy so both 120-step chains outrank the
    # 50-step filler on the critical path
    plan.record_profile(l1.node_id, 10.0)
    plan.record_profile(l2.node_id, 10.0)

    disp = make_dispatcher(plan, SimulatedTrainer(), [Worker(0), Worker(1)],
                           max_steps_per_chain=40)
    disp.assign()
    # chain 1 = [A,B,C1] truncated to [A]; chain 2 = [C2] whose parent B
    # was cut -> deferred; the freed worker picks up the 50-step trial
    assert disp.stats.chains_deferred == 1
    assert disp.stats.steps_run == 90        # A (40) + other (50)
    assert all(not w.idle for w in disp.workers)


def test_sibling_resume_dedup_copies_before_fanout():
    """One resume load feeding several group members must be cloned per
    member: a backend that consumes its input in place (donation, mutable
    dict states) would otherwise corrupt its siblings' carries."""

    class ClobberingSim(BatchedSim):
        def run_stages_batched(self, states, ctxs):
            outs = []
            for s, c in zip(states, ctxs):
                outs.append(self.run_stage(s, c))
                s.clear()                    # consume the input in place
            return outs

    store = CheckpointStore()
    plan, sibs, shared, cid, fork_state = seeded_sibling_plan(store)
    # snapshot before dispatch: the in-memory store serves the seeded tree
    # by reference, and the first member is *allowed* to consume it
    fork_state = dict(fork_state)
    disp = make_dispatcher(plan, ClobberingSim(), [Worker(0)], store=store,
                           batch_siblings=True)
    disp.assign()                            # no KeyError: members got copies
    assert disp.stats.batched_groups == 1

    # and every member advanced from the *pristine* fork state
    cids = drain_boundary_cids(disp)
    ref = SimulatedTrainer()
    for t in sibs:
        leaf = plan.trial_paths[t.trial_id][-1]
        node = plan.node(leaf)
        ctx = StageContext(node_id=leaf, desc=node.desc,
                           node_start=node.start, start=20, stop=40,
                           path_key=plan.path_key(leaf))
        want = ref.run_stage(dict(fork_state), ctx)
        got = store.get(cids[(leaf, 40)])
        assert got["progress"] == want["progress"]
        assert got["step"] == 40


# ---------------------------------------------------------------------------
# d2d handoff
# ---------------------------------------------------------------------------


def resume_plan(store, progress=7.5, seed_store=True):
    """One 40-step trial checkpointed at 20 -> a single resume chain.
    Returns (plan, node_id, cid, fork_state)."""
    plan = SearchPlan()
    t = Trial(HpConfig({"lr": Constant(0.1)}), 40)
    leaf, _, _ = plan.submit(t)
    state = {"progress": progress, "step": 20}
    if seed_store:
        cid = store.put(plan.path_key(leaf.node_id), 20, state)
    else:
        cid = "d2d-only@20"
    plan.record_result(leaf.node_id, 20, cid, None)
    return plan, leaf.node_id, cid, state


def test_d2d_same_host_hit_bypasses_store():
    """A boundary state produced on the consumer's host is served from
    the device cache: the store is never asked (here it doesn't even hold
    the cid), yet clock/ckpt_loads accounting is the store path's."""
    store = CheckpointStore()
    plan, nid, cid, state = resume_plan(store, seed_store=False)
    worker = Worker(0, mesh=WorkerMesh.build([0], host="rack1"))
    disp = make_dispatcher(plan, SimulatedTrainer(), [worker], store=store)
    disp._d2d[cid] = (state, "rack1")
    disp.assign()
    assert disp.stats.d2d_handoffs == 1
    assert disp.stats.ckpt_misses == 0
    assert disp.stats.ckpt_loads == 1        # accounting identical to store
    assert disp.stats.steps_run == 20

    # the resumed computation really flowed from the handed-off state
    cids = drain_boundary_cids(disp)
    ref = SimulatedTrainer()
    node = plan.node(nid)
    ctx = StageContext(node_id=nid, desc=node.desc, node_start=node.start,
                       start=20, stop=40, path_key=plan.path_key(nid))
    want = ref.run_stage(dict(state), ctx)
    assert store.get(cids[(nid, 40)])["progress"] == want["progress"]
    # the new boundary is retained for the next same-host consumer
    assert cids[(nid, 40)] in disp._d2d


def test_d2d_cross_host_falls_back_to_store():
    store = CheckpointStore()
    plan, nid, cid, state = resume_plan(store)
    worker = Worker(0, mesh=WorkerMesh.build([0], host="rack2"))
    disp = make_dispatcher(plan, SimulatedTrainer(), [worker], store=store)
    disp._d2d[cid] = (state, "rack1")        # produced elsewhere
    disp.assign()
    assert disp.stats.d2d_handoffs == 0
    assert disp.stats.ckpt_loads == 1
    assert disp.stats.steps_run == 20


def test_d2d_backend_decline_falls_back_to_store():
    class NoTransferSim(SimulatedTrainer):
        def device_transfer(self, state, mesh):
            return None

    store = CheckpointStore()
    plan, nid, cid, state = resume_plan(store)
    worker = Worker(0, mesh=WorkerMesh.build([0], host="rack1"))
    disp = make_dispatcher(plan, NoTransferSim(), [worker], store=store)
    disp._d2d[cid] = (state, "rack1")
    disp.assign()
    assert disp.stats.d2d_handoffs == 0
    assert disp.stats.ckpt_loads == 1
    assert disp.stats.steps_run == 20


def test_d2d_disabled_on_thread_fleets():
    """Classic thread fleets never populate the device cache — their
    store-counter behavior stays bit-for-bit what it was."""
    store = CheckpointStore()
    plan, nid, cid, state = resume_plan(store)
    disp = make_dispatcher(plan, SimulatedTrainer(), [Worker(0)],
                           store=store)
    disp.assign()
    assert disp._d2d == {}
    assert disp.stats.d2d_handoffs == 0
    assert disp.stats.steps_run == 20


def test_d2d_cache_is_lru_bounded():
    store = CheckpointStore()
    plan, nid, cid, state = resume_plan(store)
    worker = Worker(0, mesh=WorkerMesh.build([0]))
    disp = make_dispatcher(plan, SimulatedTrainer(), [worker], store=store)
    for i in range(disp._d2d_cap + 5):
        disp._d2d_put(f"cid{i}", {"step": i}, worker)
    assert len(disp._d2d) == disp._d2d_cap
    assert "cid0" not in disp._d2d           # oldest evicted
    assert f"cid{disp._d2d_cap + 4}" in disp._d2d


# ---------------------------------------------------------------------------
# fleet equivalences
# ---------------------------------------------------------------------------


def _det(stats):
    """Deterministic cross-fleet view: wall timers, physical store
    counters and the mesh-plane counters themselves (d2d handoffs skip
    physical reads; placements only exist on mesh fleets)."""
    return dataclasses.replace(
        stats, ckpt_save_seconds=0.0, ckpt_load_seconds=0.0,
        ckpt_delta_bytes=0, ckpt_full_bytes=0, ckpt_logical_bytes=0,
        ckpt_bytes_written=0, ckpt_delta_commits=0, ckpt_delta_rebases=0,
        ckpt_mem_hits=0, ckpt_disk_hits=0, ckpt_remote_hits=0,
        ckpt_store_misses=0, ckpt_tier_promotions=0, ckpt_tier_demotions=0,
        ckpt_tmp_reclaimed=0, d2d_handoffs=0, mesh_placements=0)


def _grid_run(worker_meshes):
    db = SearchPlanDB()
    study = Study.create(db, "m", "d", ("lr",))
    trials = [sib_trial(v) for v in (0.05, 0.02, 0.01)] + \
             [Trial(HpConfig({"lr": Constant(0.3)}), 60)]
    eng = study.engine(SimulatedTrainer(), n_workers=3, batch_siblings=True)\
        if worker_meshes is None else \
        study.engine(SimulatedTrainer(), n_workers=3, batch_siblings=True,
                     worker_meshes=worker_meshes)
    stats = eng.run([GridTuner(trials)])
    return db.get(study.key), stats


def test_one_device_mesh_fleet_replays_thread_fleet():
    """width-1 meshes change nothing but the mesh-plane counters: the
    virtual clock, per-study breakdown, metrics and checkpoints replay the
    thread fleet exactly."""
    plan_t, stats_t = _grid_run(None)
    plan_m, stats_m = _grid_run(plan_worker_meshes(3, 1))
    assert stats_m.mesh_placements > 0
    assert stats_t.mesh_placements == 0
    assert _det(stats_m) == _det(stats_t)
    assert set(plan_m.nodes) == set(plan_t.nodes)
    for nid, node in plan_m.nodes.items():
        assert node.metrics == plan_t.nodes[nid].metrics
        assert set(node.ckpts) == set(plan_t.nodes[nid].ckpts)


def test_session_snapshot_round_trips_meshes(tmp_path):
    """Worker meshes survive snapshot/restore (session format v3) and the
    restored session finishes with the uninterrupted run's stats."""
    meshes = plan_worker_meshes(2, 2, host="hq")
    spec = StudySpec("m", "d", ("lr",))
    trials = [sib_trial(v, total=60) for v in (0.05, 0.02)]

    def fresh():
        svc = StudyService(SearchPlanDB(), SimulatedTrainer(), n_workers=2,
                           worker_meshes=meshes)
        svc.submit(spec, GridTuner(list(trials)))
        return svc

    ref = fresh().close()

    svc = fresh()
    svc.run_until(30.0)
    path = svc.snapshot(str(tmp_path / "sess.pkl"))
    svc2 = StudyService.restore(SearchPlanDB(), path, SimulatedTrainer())
    assert [w.mesh for w in svc2._engine.workers] == list(meshes)
    got = svc2.close()
    assert _det(got) == _det(ref)
    assert got.mesh_placements == ref.mesh_placements


# ---------------------------------------------------------------------------
# sharded execution is bitwise-lossless (subprocess: forced host devices)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax
assert jax.device_count() == 4, jax.device_count()
import numpy as np
from test_dataplane import tiny_backend, assert_states_identical
from repro.core import SearchPlanDB, Study
from repro.core.hpseq import HpConfig, MultiStep
from repro.core.trial import Trial
from repro.core.tuners import GridTuner
from repro.dist.meshes import WorkerMesh

def run(meshes):
    db = SearchPlanDB()
    study = Study.create(db, "m", "d", ("lr",))
    trials = [Trial(HpConfig({{"lr": MultiStep(0.1, [8],
                                               values=[0.1, v])}}), 16)
              for v in (0.05, 0.02, 0.01)]
    backend = tiny_backend(vectorize_groups=True)
    # one worker: the fork checkpoint lands first, so the sibling tails
    # form a ready group next round instead of chaining off in-round state
    eng = study.engine(backend, n_workers=1, batch_siblings=True,
                       worker_meshes=meshes)
    stats = eng.run([GridTuner(trials)])
    return db.get(study.key), stats, backend, eng, trials

# thread fleet reference, then one 4-device mesh per worker
plan_t, stats_t, backend_t, eng_t, trials = run(None)
mesh = WorkerMesh.build([0, 1, 2, 3])
plan_m, stats_m, backend_m, eng_m, _ = run([mesh])

assert stats_m.mesh_placements > 0, "no stage ever ran on the mesh"
assert stats_m.batched_groups >= 1, "sibling group did not batch"
assert stats_m.steps_run == stats_t.steps_run
# the backend really materialized + compiled against the mesh: the live
# Mesh is cached and mesh-keyed executables exist alongside none-keyed
assert backend_m._meshes, "set_mesh never materialized a jax Mesh"
assert any(k[0] == "fused" and k[-2] == mesh.key
           for k in backend_m._chunk_fns), "no mesh-keyed solo executable"
assert any(k[0] == "group" and k[-3] == mesh.key
           for k in backend_m._chunk_fns), "no mesh-keyed group executable"

# bitwise: every leaf checkpoint identical between the fleets
for t in trials:
    leaf = plan_m.trial_paths[t.trial_id][-1]
    cid_m = plan_m.nodes[leaf].ckpts[16]
    cid_t = plan_t.nodes[leaf].ckpts[16]
    assert_states_identical(eng_m.store.get(cid_m), eng_t.store.get(cid_t))
    assert plan_m.nodes[leaf].metrics[16] == plan_t.nodes[leaf].metrics[16]
print("SHARDED-BITWISE-OK")
"""


def test_sharded_mesh_execution_bitwise_equals_thread_fleet(tmp_path):
    """A 4-device mesh worker shards the carry (fsdp over ``data``) while
    the sibling group vmaps across trials within the mesh — and the leaf
    checkpoints are bit-identical to the unsharded thread fleet.  Runs in
    a subprocess: the forced host-device count must precede jax import."""
    script = tmp_path / "sharded_bitwise.py"
    script.write_text(_SHARDED_SCRIPT.format(
        src=os.path.join(REPO, "src"), tests=os.path.join(REPO, "tests")))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARDED-BITWISE-OK" in proc.stdout


def test_jax_backend_divisibility_gate():
    """The placement gate reuses the PR 3 divisibility rule via
    ``jax.eval_shape`` — no devices are materialized, so it runs on the
    default single-CPU jax."""
    from test_dataplane import tiny_backend

    tb = tiny_backend()
    four = WorkerMesh.build([0, 1, 2, 3])     # 16x4 / 4-vector shard on 4
    three = WorkerMesh.build([0, 1, 2], axes=(("data", 3),))
    assert tb.mesh_compatible(four, []) is True
    assert tb.mesh_compatible(three, []) is False   # 3 divides nothing
    assert tb.mesh_compatible(None, []) is True
    # cached per mesh key
    assert tb._mesh_ok[four.key] is True
    assert tb._mesh_ok[three.key] is False


_FLEET_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax
assert jax.device_count() == 4, jax.device_count()
from test_dataplane import tiny_backend, assert_states_identical
from repro.core import SearchPlanDB, Study
from repro.core.hpseq import Constant, HpConfig
from repro.core.trial import Trial
from repro.core.tuners import GridTuner
from repro.dist.meshes import plan_worker_meshes

def run(meshes):
    db = SearchPlanDB()
    study = Study.create(db, "m", "d", ("lr",))
    trials = [Trial(HpConfig({{"lr": Constant(v)}}), 16)
              for v in (0.1, 0.05, 0.02, 0.01)]
    backend = tiny_backend()
    placed = {{}}   # worker mesh key -> devices its boundary states live on
    run_chain = backend._run_fused_chain

    def spy(states, chains):
        out = run_chain(states, chains)
        devs = placed.setdefault(backend._mesh_key, set())
        for member in out:
            for st in member:
                for leaf in jax.tree.leaves((st["params"], st["opt"])):
                    devs |= set(leaf.devices())
        return out

    backend._run_fused_chain = spy
    # four independent trials, no batching: one chain per worker
    eng = study.engine(backend, n_workers=4, batch_siblings=False,
                       worker_meshes=meshes)
    eng.run([GridTuner(trials)])
    return db.get(study.key), eng, trials, placed

plan_t, eng_t, trials, placed_t = run(None)
assert set(placed_t) == {{None}}, placed_t
meshes = plan_worker_meshes(4, 1)
plan_m, eng_m, _, placed_m = run(meshes)
devices = jax.devices()
for m in meshes:
    assert placed_m.get(m.key) == {{devices[m.device_ids[0]]}}, (m, placed_m)
assert len(set.union(*placed_m.values())) == 4, placed_m
for t in trials:
    leaf = plan_m.trial_paths[t.trial_id][-1]
    assert_states_identical(eng_m.store.get(plan_m.nodes[leaf].ckpts[16]),
                            eng_t.store.get(plan_t.nodes[leaf].ckpts[16]))
print("FLEET-SPREAD-OK")
"""


def test_one_chip_worker_fleet_spreads_over_devices(tmp_path):
    """A fleet of four 1-device worker meshes places each worker's carry
    and boundary states on the device that worker owns (four distinct
    devices, not device 0 four times), bit-identical to thread workers.
    Runs in a subprocess: the forced host-device count must precede jax
    import."""
    script = tmp_path / "fleet_spread.py"
    script.write_text(_FLEET_SCRIPT.format(
        src=os.path.join(REPO, "src"), tests=os.path.join(REPO, "tests")))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "FLEET-SPREAD-OK" in proc.stdout
