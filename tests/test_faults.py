"""Fault plane: deterministic injection, retry/quarantine/degradation
failure domains, and crash-consistent session snapshots.

The load-bearing property throughout: faults change *when* work runs, never
*what* it computes — every faulty run must finish with leaf checkpoints
bitwise-identical to the fault-free run, with the retry waste accounted in
``wasted_gpu_seconds`` and kept out of the sharing studies' fair-share
charges.
"""

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (FatalStageError, FaultInjector, SearchPlanDB,
                        StudyService, StudySpec, TransientStageError,
                        WorkerCrashed)
from repro.core.engine import (capture_session, load_latest_session,
                               migrate_session, restore_engine, save_session,
                               save_session_rotated, session_rotation)
from repro.core.faults import is_transient, raw_store
from repro.core.hpseq import Constant, Exponential, StepLR, Warmup
from repro.core.trainer import SimulatedTrainer
from repro.core.tuners import GridSearchSpace, GridTuner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = StudySpec("m", "d", ("lr", "bs"))


def _space(n_lr: int = 3) -> GridSearchSpace:
    lrs = [StepLR(0.1, 0.1, [30]), StepLR(0.1, 0.1, [40]),
           Warmup(5, 0.1, Exponential(0.1, 0.95))][:n_lr]
    return GridSearchSpace(fns={"lr": lrs,
                                "bs": [Constant(64), Constant(128)]})


def det(stats):
    """Deterministic view (same contract as test_service.det): physical
    wall timers and physical-store counters vary run to run; everything
    else must replay exactly."""
    import dataclasses
    return dataclasses.replace(
        stats, ckpt_save_seconds=0.0, ckpt_load_seconds=0.0,
        ckpt_delta_bytes=0, ckpt_full_bytes=0, ckpt_logical_bytes=0,
        ckpt_bytes_written=0, ckpt_delta_commits=0, ckpt_delta_rebases=0,
        ckpt_mem_hits=0, ckpt_disk_hits=0, ckpt_remote_hits=0,
        ckpt_store_misses=0, ckpt_tier_promotions=0, ckpt_tier_demotions=0,
        ckpt_tmp_reclaimed=0, d2d_handoffs=0)


def run_session(injector=None, *, n_workers=4, steps=80, second_study=True,
                backend=None, **engine_kw):
    """Two-study fair-share session; returns (stats, leaves, service)."""
    db = SearchPlanDB()
    svc = StudyService(db, backend or SimulatedTrainer(horizon=steps),
                       n_workers=n_workers, policy="fair_share",
                       fault_injector=injector, **engine_kw)
    svc.submit(SPEC, GridTuner(_space().trials(steps)))
    if second_study:
        svc.submit(SPEC, GridTuner(_space().trials(steps)[:4]), at=200.0)
    stats = svc.close()
    eng = svc._engine
    store = raw_store(eng.store)
    leaves = {}
    for nid, node in eng.plan.nodes.items():
        for step, cid in node.ckpts.items():
            try:
                leaves[(nid, step)] = store.get(cid)
            except KeyError:
                pass                       # GC'd interior boundary
    return stats, leaves, svc


def assert_leaves_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert set(a[k]) == set(b[k])
        for name in a[k]:
            np.testing.assert_array_equal(np.asarray(a[k][name]),
                                          np.asarray(b[k][name]))


# ---------------------------------------------------------------------------
# injector determinism
# ---------------------------------------------------------------------------

def _drain_schedule(inj, n=200):
    out = []
    for i in range(n):
        try:
            inj.before_execute(f"s{i}")
        except Exception as e:
            out.append(type(e).__name__)
    return out, list(inj.log)


def test_same_seed_same_schedule():
    a = FaultInjector(42, stage_fault_rate=0.2, crash_rate=0.1)
    b = FaultInjector(42, stage_fault_rate=0.2, crash_rate=0.1)
    sched_a, log_a = _drain_schedule(a)
    sched_b, log_b = _drain_schedule(b)
    assert sched_a == sched_b and log_a == log_b
    assert a.injected == b.injected > 0


def test_different_seed_different_schedule():
    a = FaultInjector(1, stage_fault_rate=0.2, crash_rate=0.1)
    b = FaultInjector(2, stage_fault_rate=0.2, crash_rate=0.1)
    assert _drain_schedule(a)[0] != _drain_schedule(b)[0]


def test_max_faults_bounds_schedule():
    inj = FaultInjector(0, stage_fault_rate=1.0, max_faults=3)
    fired, _ = _drain_schedule(inj, 50)
    assert len(fired) == 3 and inj.injected == 3


def test_outage_window_counts_once():
    inj = FaultInjector(0, outage_rate=1.0, outage_ops=3)
    from repro.core import StoreOutageError
    for _ in range(3):                    # the fired op + 2 window ops
        with pytest.raises(StoreOutageError):
            inj.on_store_op("get", "cid")
    assert inj.injected == 1 and inj.by_kind == {"outage": 1}


def test_fault_taxonomy():
    assert is_transient(TransientStageError("x"))
    assert is_transient(WorkerCrashed("x"))
    assert not is_transient(FatalStageError("x"))
    assert not is_transient(ValueError("x"))
    # injected faults must NOT alias the dispatcher's fall-back signal
    assert not isinstance(TransientStageError("x"), ValueError)


# ---------------------------------------------------------------------------
# the acceptance run: faults injected, session completes bitwise-equal
# ---------------------------------------------------------------------------

def test_faulty_session_bitwise_equals_fault_free():
    """Seeded schedule of worker crashes + transient stage failures + a
    store outage: the multi-study session completes, retries happened,
    every final leaf is bitwise-equal to the fault-free run, and the
    retry waste never lands in the sharing studies' fair-share charges."""
    ref, leaves_ref, _ = run_session(None)
    inj = FaultInjector(11, stage_fault_rate=0.25, crash_rate=0.15,
                        outage_rate=0.02, outage_ops=2)
    got, leaves_got, _ = run_session(inj)

    assert inj.injected > 0 and got.faults_injected == inj.injected
    assert {"stage", "crash", "outage"} <= set(inj.by_kind)
    assert got.stage_retries > 0
    assert got.stage_failures >= got.stage_retries
    assert got.wasted_gpu_seconds > 0

    assert got.steps_run == ref.steps_run
    assert_leaves_equal(leaves_ref, leaves_got)

    # useful work is conserved: waste is charged to wasted_gpu_seconds
    # only, so the per-study fair-share totals still sum to the fault-free
    # total (the split between studies may shift — faults move stages
    # across the second study's admission time)
    total_ref = sum(s.gpu_seconds for s in ref.by_study.values())
    total_got = sum(s.gpu_seconds for s in got.by_study.values())
    assert total_got == pytest.approx(total_ref)
    # global gpu_seconds may exceed the fault-free run slightly: retries
    # re-load their boundary checkpoint, and load stalls are charged to
    # the global counter (never to a study)
    assert got.gpu_seconds >= total_got


def test_crash_heavy_run_quarantines_and_completes():
    inj = FaultInjector(3, crash_rate=0.45, stage_fault_rate=0.1)
    got, leaves_got, _ = run_session(inj, n_workers=2, second_study=False)
    ref, leaves_ref, _ = run_session(None, n_workers=2, second_study=False)
    assert inj.by_kind.get("crash", 0) > 0
    assert got.workers_quarantined > 0
    assert got.steps_run == ref.steps_run
    assert_leaves_equal(leaves_ref, leaves_got)


def test_straggler_completes_but_slower():
    inj = FaultInjector(5, straggler_rate=1.0, straggler_factor=4.0)
    got, leaves_got, _ = run_session(inj, second_study=False)
    ref, leaves_ref, _ = run_session(None, second_study=False)
    assert inj.by_kind.get("straggler", 0) > 0
    assert got.stage_failures == 0            # performance fault only
    assert got.steps_run == ref.steps_run
    assert got.gpu_seconds > ref.gpu_seconds  # slowdown is real + accounted
    assert_leaves_equal(leaves_ref, leaves_got)


def test_fatal_fault_propagates():
    class FatalOnce(FaultInjector):
        def __init__(self):
            super().__init__(0)
            self._armed = True

        def before_execute(self, site):
            if self._armed:
                self._armed = False
                self._record("fatal", site)
                raise FatalStageError(f"injected fatal at {site}")

    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(horizon=80), n_workers=2,
                       fault_injector=FatalOnce())
    svc.submit(SPEC, GridTuner(_space(1).trials(80)))
    with pytest.raises(FatalStageError):
        svc.close()


def test_retry_budget_is_consecutive_not_cumulative():
    """``max_stage_retries`` bounds consecutive failures of one unit: a
    unit that fails, recovers, and fails again later must not accrue
    attempts across unrelated incidents until a recoverable fault is
    misclassified as exhausted."""

    class EveryOtherAttempt(FaultInjector):
        """Fail every other execution attempt, forever — far more total
        faults per unit than max_stage_retries, never two in a row."""

        def __init__(self):
            super().__init__(0)
            self._flip = False

        def before_execute(self, site):
            self._flip = not self._flip
            if self._flip:
                self._record("stage", site)
                raise TransientStageError(f"injected at {site}")

    inj = EveryOtherAttempt()
    # the session completes — without the consecutive-reset, attempt
    # counts accrue across incidents and this raises TransientStageError
    got, leaves_got, svc = run_session(inj, n_workers=2, second_study=False)
    ref, leaves_ref, _ = run_session(None, n_workers=2, second_study=False)
    disp = svc._engine.dispatcher
    assert got.stage_retries > disp.max_stage_retries
    # this schedule forces recompute-on-miss (a retry whose resume
    # checkpoint was GC'd re-derives from an earlier boundary), so total
    # steps may exceed the fault-free run — but every terminal leaf is
    # still bitwise-identical
    assert got.steps_run >= ref.steps_run
    terminal = {k for k in leaves_ref if k[1] == 80}
    assert terminal and terminal <= set(leaves_got)
    assert_leaves_equal({k: leaves_ref[k] for k in terminal},
                        {k: leaves_got[k] for k in terminal})


def test_retry_exhaustion_propagates():
    inj = FaultInjector(0, stage_fault_rate=1.0)   # every attempt fails
    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(horizon=80), n_workers=2,
                       fault_injector=inj)
    svc.submit(SPEC, GridTuner(_space(1).trials(80)))
    with pytest.raises(TransientStageError):
        svc.close()


def test_batched_group_degrades_to_solo():
    """A transient fault inside a batched sibling-group call degrades the
    group to per-member solo execution instead of failing it wholesale."""
    import test_chainfusion as cf

    def run(inj):
        db = SearchPlanDB()
        svc = StudyService(db, cf.BatchedChainSimTrainer(horizon=48),
                           n_workers=1, fault_injector=inj,
                           batch_siblings=True)
        svc.submit(StudySpec("m", "d", ("lr",)),
                   GridTuner([cf.seq_trial(0.1 - 0.01 * i, 0.01, steps=48,
                                           boundary=24) for i in range(4)]))
        stats = svc.close()
        eng = svc._engine
        store = raw_store(eng.store)
        leaves = {(nid, st): store.get(cid)
                  for nid, node in eng.plan.nodes.items()
                  for st, cid in node.ckpts.items() if store.contains(cid)}
        return stats, leaves

    ref, leaves_ref = run(None)
    assert ref.batched_groups > 0, "scenario never batched"

    class GroupFault(FaultInjector):
        """Deterministically fail the first batched-group attempt."""
        def __init__(self):
            super().__init__(0)
            self._armed = True

        def before_execute(self, site):
            if self._armed and site.startswith(("group:", "group-chain:")):
                self._armed = False
                self._record("stage", site)
                raise TransientStageError(f"injected group fault at {site}")

    inj = GroupFault()
    got, leaves_got = run(inj)
    assert inj.injected == 1
    assert got.groups_degraded == 1
    assert got.steps_run == ref.steps_run
    assert_leaves_equal(leaves_ref, leaves_got)


def test_store_outage_only_run_completes():
    inj = FaultInjector(9, outage_rate=0.15, outage_ops=2)
    got, leaves_got, _ = run_session(inj, second_study=False)
    ref, leaves_ref, _ = run_session(None, second_study=False)
    assert inj.by_kind.get("outage", 0) > 0
    assert got.stage_retries > 0
    assert got.steps_run == ref.steps_run
    assert_leaves_equal(leaves_ref, leaves_got)


def test_faulty_jax_run_bitwise_equals_fault_free():
    """test_lossless-style, on the real JaxTrainer: a faulty run's leaf
    states (params, optimizer, data cursor) are bit-identical to the
    fault-free run's — retry from the boundary checkpoint replays the
    exact same computation."""
    from test_dataplane import assert_states_identical, tiny_backend
    from repro.core import Study
    from repro.core.hpseq import HpConfig, MultiStep
    from repro.core.trial import Trial

    def run(inj):
        db = SearchPlanDB()
        study = Study.create(db, "m", "d", ("lr",))
        trials = [Trial(HpConfig({"lr": MultiStep(0.1, [8],
                                                  values=[0.1, v])}), 16)
                  for v in (0.05, 0.02, 0.01)]
        eng = study.engine(tiny_backend(), n_workers=2, fault_injector=inj)
        stats = eng.run([GridTuner(trials)])
        return db.get(study.key), eng, stats, trials

    plan_ref, eng_ref, ref, trials = run(None)
    inj = FaultInjector(2, stage_fault_rate=0.3, crash_rate=0.2)
    plan_got, eng_got, got, _ = run(inj)
    assert inj.injected > 0, "seed drew no faults — pick another"
    assert got.stage_retries > 0
    assert got.steps_run >= ref.steps_run

    store_ref = raw_store(eng_ref.store)
    store_got = raw_store(eng_got.store)
    for t in trials:
        leaf_ref = plan_ref.trial_paths[t.trial_id][-1]
        leaf_got = plan_got.trial_paths[t.trial_id][-1]
        assert_states_identical(
            store_ref.get(plan_ref.nodes[leaf_ref].ckpts[16]),
            store_got.get(plan_got.nodes[leaf_got].ckpts[16]))
        assert (plan_ref.nodes[leaf_ref].metrics[16]
                == plan_got.nodes[leaf_got].metrics[16])


# ---------------------------------------------------------------------------
# retry-bitwise assertion (the in-band verifier)
# ---------------------------------------------------------------------------

def test_assert_retry_identical():
    """With an injector attached, every re-put of a committed checkpoint
    is compared bit-for-bit against the committed blob: identical trees
    count in ``retries_verified``; a divergent recompute is an engine bug
    and must raise."""
    inj = FaultInjector(0)
    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(horizon=40), n_workers=1,
                       fault_injector=inj)
    svc.submit(SPEC, GridTuner(_space(1).trials(40)[:1]))
    svc.close()
    eng = svc._engine
    disp = eng.dispatcher
    store = raw_store(eng.store)

    nid, node = next(iter(eng.plan.nodes.items()))
    step, cid = next(iter(node.ckpts.items()))
    committed = store.get(cid)
    path_key = eng.plan.path_key(nid)
    assert store.ckpt_id(path_key, step) == cid

    before = inj.retries_verified
    disp._assert_retry_identical(path_key, step, committed)
    assert inj.retries_verified == before + 1

    mutated = {k: (np.asarray(v) + 1 if np.issubdtype(
        np.asarray(v).dtype, np.number) else v)
        for k, v in committed.items()}
    with pytest.raises(RuntimeError, match="retry"):
        disp._assert_retry_identical(path_key, step, mutated)

    # unknown checkpoint: nothing committed yet, nothing to verify
    disp._assert_retry_identical("no-such-path", 999, committed)
    assert inj.retries_verified == before + 1


# ---------------------------------------------------------------------------
# session snapshots: unique tmp, v2/v3 migration, rotation + fallback
# ---------------------------------------------------------------------------

def _small_session():
    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(horizon=80), n_workers=2)
    svc.submit(SPEC, GridTuner(_space(1).trials(80)))
    for _ in range(4):
        svc.step()
    return svc, capture_session(svc._engine)


def test_save_session_tmp_is_process_unique(tmp_path, monkeypatch):
    _, state = _small_session()
    seen = []
    real_replace = os.replace

    def spy(src, dst):
        seen.append(src)
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", spy)
    save_session(state, str(tmp_path / "s.pkl"))
    assert len(seen) == 1
    # two concurrent writers (two processes, or two threads of one) must
    # never share a tmp name
    assert f".tmp.{os.getpid()}." in seen[0]


def test_save_session_cleans_tmp_on_failure(tmp_path, monkeypatch):
    _, state = _small_session()

    def boom(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        save_session(state, str(tmp_path / "s.pkl"))
    assert list(tmp_path.iterdir()) == []


def test_v2_and_v3_snapshots_migrate():
    svc, state = _small_session()
    # v2: 3-tuple worker rows, none of the newer stats fields
    state.version = 2
    state.workers = [(w[0], w[1], w[2]) for w in state.workers]
    for f in ("stage_failures", "stage_retries", "workers_quarantined",
              "groups_degraded", "faults_injected", "wasted_gpu_seconds"):
        delattr(state.stats, f)
    m = migrate_session(state)
    assert m.version >= 4
    # v5 rows: (wid, busy, idle, mesh, failures, quarantines, q_until,
    # draining) — mesh, fault record and the front-door draining flag are
    # all backfilled
    assert all(len(row) == 8 for row in m.workers)
    assert m.workers[0][3] is None          # mesh backfilled
    assert m.workers[0][7] is False         # draining backfilled
    assert m.stats.stage_retries == 0 and m.stats.wasted_gpu_seconds == 0.0

    eng = restore_engine(m, SimulatedTrainer(horizon=80))
    assert [w.failures for w in eng.workers] == [0, 0]

    # v3: 4-tuple rows (mesh present, no fault-plane columns)
    _, state3 = _small_session()
    state3.version = 3
    state3.workers = [w[:4] for w in state3.workers]
    m3 = migrate_session(state3)
    assert all(len(row) == 8 for row in m3.workers)

    _, state1 = _small_session()
    state1.version = 1
    with pytest.raises(ValueError):
        migrate_session(state1)


def test_rotation_keeps_n_and_falls_back_on_corruption(tmp_path):
    _, state = _small_session()
    base = str(tmp_path / "sess.pkl")
    for _ in range(5):
        save_session_rotated(state, base, keep=3)
    slots = session_rotation(base)
    assert [seq for seq, _ in slots] == [5, 4, 3]     # newest first, keep=3

    # newest truncated -> falls back to the previous slot
    newest = slots[0][1]
    with open(newest, "r+b") as f:
        f.truncate(64)
    loaded, path = load_latest_session(base)
    assert path == slots[1][1]
    assert loaded.version == state.version

    # newest garbage (unpicklable), second truncated -> third still wins
    with open(newest, "wb") as f:
        f.write(b"not a pickle")
    with open(slots[1][1], "r+b") as f:
        f.truncate(10)
    loaded, path = load_latest_session(base)
    assert path == slots[2][1]

    # everything corrupt -> a FileNotFoundError naming the failures
    with open(slots[2][1], "wb") as f:
        f.write(b"nope")
    with pytest.raises(FileNotFoundError):
        load_latest_session(base)


def test_restore_latest_resumes_to_identical_stats(tmp_path):
    ref, _, _ = run_session(None, n_workers=2, second_study=False)

    base = str(tmp_path / "sess.pkl")
    db = SearchPlanDB()
    svc = StudyService(db, SimulatedTrainer(horizon=80), n_workers=2)
    svc.enable_auto_snapshot(base, every=25.0, keep=3)
    svc.submit(SPEC, GridTuner(_space().trials(80)))
    for _ in range(12):                    # interrupt mid-drain
        svc.step()
    assert session_rotation(base), "auto-snapshot never fired"
    del svc                                # the crash

    svc2 = StudyService.restore_latest(SearchPlanDB(), base,
                                       SimulatedTrainer(horizon=80))
    got = svc2.close()
    assert det(got) == det(ref)


# ---------------------------------------------------------------------------
# crash consistency end-to-end: SIGKILL mid-drain, restore, finish
# ---------------------------------------------------------------------------

_KILLED_SCRIPT = """
import os, signal, sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
from test_faults import SPEC, _space
from repro.core import SearchPlanDB, StudyService
from repro.core.trainer import SimulatedTrainer
from repro.core.tuners import GridTuner

svc = StudyService(SearchPlanDB(), SimulatedTrainer(horizon=80),
                   n_workers=2, policy="fair_share")
svc.enable_auto_snapshot({base!r}, every=25.0, keep=3)
svc.submit(SPEC, GridTuner(_space().trials(80)))
svc.submit(SPEC, GridTuner(_space().trials(80)[:4]), at=200.0)
n = 0
while svc.step():
    n += 1
    if n == {kill_after}:
        os.kill(os.getpid(), signal.SIGKILL)   # no atexit, no flush
raise SystemExit("ran to completion before the kill point")
"""


def test_sigkill_then_restore_finishes_identically(tmp_path):
    """SIGKILL mid-drain (no graceful path at all), then restore from the
    newest readable rotation slot and finish: final EngineStats — by_study
    included — match an uninterrupted run."""
    ref, _, _ = run_session(None, n_workers=2)

    base = str(tmp_path / "sess.pkl")
    script = tmp_path / "killed.py"
    script.write_text(_KILLED_SCRIPT.format(
        src=os.path.join(REPO, "src"), tests=os.path.join(REPO, "tests"),
        base=base, kill_after=14))
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    assert session_rotation(base), "no snapshot survived the kill"

    svc = StudyService.restore_latest(SearchPlanDB(), base,
                                      SimulatedTrainer(horizon=80))
    got = svc.close()
    assert det(got) == det(ref)
    assert {k: (v.gpu_seconds, v.steps_run, v.instant_results)
            for k, v in got.by_study.items()} == \
           {k: (v.gpu_seconds, v.steps_run, v.instant_results)
            for k, v in ref.by_study.items()}


def test_sigterm_graceful_shutdown_snapshot(tmp_path):
    """satellite (c): the launcher's SIGTERM handler takes a final
    snapshot to --session before exiting; the snapshot resumes to the
    uninterrupted totals."""
    sess = str(tmp_path / "term.pkl")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    argv = [sys.executable, "-m", "repro.launch.serve_studies",
            "--studies", "2", "--steps", "60", "--workers", "2",
            "--arrival-gap", "600", "--sec-per-step", "10",
            "--session", sess, "--throttle", "0.25"]
    proc = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        import time
        # the handler goes in after the imports, which take seconds on a
        # loaded machine: wait until the process catches SIGTERM
        # (SigCgt in /proc), then let a few throttled steps run
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and proc.poll() is None:
            with open(f"/proc/{proc.pid}/status") as f:
                caught = next(int(l.split()[1], 16) for l in f
                              if l.startswith("SigCgt:"))
            if caught >> (signal.SIGTERM - 1) & 1:
                break
            time.sleep(0.05)
        time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        proc.kill()
    assert proc.returncode == 0, out[-2000:]
    assert "final snapshot" in out
    assert os.path.exists(sess)

    # the launcher is gateway-driven now: the final snapshot is a v5
    # gateway envelope holding every live session
    from repro.frontdoor import StudyGateway
    gw = StudyGateway.restore(
        SearchPlanDB(), sess,
        SimulatedTrainer(base_seconds_per_step=10.0, horizon=60))
    gw.join()
    [(_, got)] = gw.close()

    db = SearchPlanDB()
    ref_svc = StudyService(db, SimulatedTrainer(base_seconds_per_step=10.0,
                                                horizon=60), n_workers=2)
    spec = StudySpec("resnet20", "cifar10", ("lr", "bs"))
    from repro.launch.serve_studies import _space as launcher_space
    for i in range(2):
        ref_svc.submit(spec, GridTuner(launcher_space(i, 60).trials(60)),
                       at=i * 600.0)
    ref = ref_svc.close()
    assert det(got) == det(ref)


# ---------------------------------------------------------------------------
# launcher fault-injection surface
# ---------------------------------------------------------------------------

def test_serve_studies_inject_faults(monkeypatch, capsys):
    from repro.launch import serve_studies
    monkeypatch.setattr(sys, "argv",
                        ["serve_studies", "--studies", "2", "--workers", "4",
                         "--steps", "60", "--arrival-gap", "600",
                         "--sec-per-step", "10",
                         "--inject-faults", "7",
                         "--fault-rates", "0.3,0.15,0.02"])
    serve_studies.main()
    out = capsys.readouterr().out
    assert "fault plane:" in out
    assert "served:" in out
