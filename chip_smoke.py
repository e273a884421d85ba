#!/usr/bin/env python3
"""Chip smoke: one real ResNet-56 hyper-parameter study on a TPU, through
the engine's normal path (``StudyService`` -> ``Dispatcher`` ->
``JaxTrainer`` chunk executables).

The model is ``ResNet(n=9, width=16)``: ResNet-56 at the paper's widths
(16/32/64 channels, 32x32x3 inputs), random weights from ``--seed``,
synthetic CIFAR-shaped data from ``--seed``, batch 128, and the fused
optimizer kernel compiled by Mosaic (``JaxTrainer``'s TPU default).

The study is a successive-halving search (``SHATuner``, eta 2, rungs at
8/16/32 steps) over two learning-rate schedules x four static momentum
values, shaped so that it takes every execution path of the engine:

* rung 1: the two schedules of one momentum share their first 8 steps,
  so four solo chains run (one per momentum);
* rung 2: the best two momenta go on, and each one's two schedules fork
  at step 8: two batched sibling groups (the member-stacked kernel);
* rung 3: the best two trials cross their learning-rate decay at step
  24: fused chains of two stages.

It also checks the paper's invariant on the chip: the best trial, trained
as a shared prefix and then inside a batched group, matches the same trial
trained straight through solo.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the worker-fleet phase, four chips

``--chips 4`` runs the same study three ways and nothing else: on one
chip (the reference), on a fleet of four 1-chip worker meshes, and on one
4-chip worker mesh.

Without a TPU the script exits non-zero and prints no result: it has no
CPU mode (``tests/test_chip_smoke.py`` rehearses its study on the CPU).
Its last line is one JSON object, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import MultiStep, SearchPlanDB, StudyService, StudySpec  # noqa: E402
from repro.core.trainer import StageContext  # noqa: E402
from repro.core.tuners import GridSearchSpace, SHATuner  # noqa: E402
from repro.data import DataPipeline, synthetic_cifar  # noqa: E402
from repro.dist.meshes import WorkerMesh, plan_worker_meshes  # noqa: E402
from repro.models.resnet import ResNet  # noqa: E402
from repro.train.jax_trainer import JaxTrainer  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

RUNGS = (8, 16, 32)                # SHA rungs: min 8, max 32, eta 2
FORK, DECAY = 8, 24                # schedule change points (steps)
LR0, LR_TAILS = 0.05, (0.05, 0.01)  # shared start, then one per schedule
MOMENTA = (0.9, 0.6, 0.3, 0.0)     # static: one stage-tree root each
# 4 solo stages x 8 + 2 groups x 2 members x 8 + 2 finishers x 16 steps
EXPECTED_STEPS = 4 * 8 + 4 * 8 + 2 * 16

# Agreement between two runs of one trial that went through different
# programs (solo vs member-stacked group, one chip vs a mesh), as the
# largest parameter difference over the largest reference parameter (the
# norm gains, ~1).  Bitwise is not the bar on a TPU: f32 convolutions run
# with bf16 operands at default precision, so two programs that fuse or
# accumulate differently differ by up to ~2^-8 (4e-3) of a gradient per
# step; 8 steps at lr <= 0.05 with momentum <= 0.9 bound the parameters'
# drift near 1e-2.  2e-2 holds that, and a member that read another
# member's learning rate (0.05 vs 0.01) or momentum (0.9 vs 0.6) would
# fail it: on the CPU those 8 steps move the parameters by 8e-2 at
# ResNet-8 and 3e-1 at ResNet-20, and deeper nets move further.  On a
# v5e chip, ResNet-56: forked prefix vs straight through 6.2e-4; the
# 4-chip mesh worker vs one chip 2.2e-3 after 32 steps; four 1-chip
# workers vs one chip 0 (the same programs).
PARAM_RTOL = 2e-2


class PlacementRecorder(JaxTrainer):
    """``JaxTrainer`` that notes which devices hold the boundary states it
    returns (per worker mesh key) and which execution paths it took."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.placed = {}          # worker mesh key -> set of devices
        self.paths = set()        # ("solo"|"group", chain depth)
        self.group_calls = 0      # batched calls attempted
        self.last = None          # the newest boundary states

    def _run_fused_chain(self, states, chains):
        if len(states) > 1:
            self.group_calls += 1
        out = super()._run_fused_chain(states, chains)
        self.paths.add(("group" if len(states) > 1 else "solo",
                        len(chains[0])))
        devs = self.placed.setdefault(self._mesh_key, set())
        for member in out:
            for st in member:
                for leaf in jax.tree.leaves((st["params"], st["opt"])):
                    devs.update(leaf.devices())
        self.last = out
        return out


class RecordingSHA(SHATuner):
    """``SHATuner`` that keeps every result it is given."""

    def __init__(self, trials, **kw):
        super().__init__(trials, **kw)
        self.index = {t.trial_id: i for i, t in enumerate(trials)}
        self.history = []         # (trial index, step, metrics)

    def on_result(self, trial, step, metrics):
        self.history.append((self.index[trial.trial_id], step,
                             dict(metrics)))
        super().on_result(trial, step, metrics)


def build_trainer(n: int = 9, batch: int = 128,
                  seed: int = 0) -> PlacementRecorder:
    """ResNet(n, width 16) over synthetic CIFAR from ``seed``: 32 batches of
    training data (one epoch per trial path) and 4 batches to evaluate."""
    data = synthetic_cifar(32 * batch, seed=seed)
    eval_data = synthetic_cifar(4 * batch, seed=seed + 1)
    return PlacementRecorder(
        ResNet(n=n, width=16),
        lambda: DataPipeline(data, batch_size=batch, seed=seed + 2),
        eval_data, default_optimizer="momentum", seed=seed)


def study_trials():
    lrs = [MultiStep(LR0, [FORK, DECAY], values=[LR0, v, v / 10])
           for v in LR_TAILS]
    space = GridSearchSpace(fns={"lr": lrs},
                            static={"momentum": list(MOMENTA)})
    return space.trials(RUNGS[-1])


def run_study(trainer: PlacementRecorder, n_workers: int = 1,
              worker_meshes=None) -> dict:
    """One SHA study through ``StudyService``; returns what the checks
    read.  The wall time ends after every boundary state is ready."""
    trials = study_trials()
    tuner = RecordingSHA(trials, min_steps=RUNGS[0], max_steps=RUNGS[-1],
                         eta=2, objective="loss", mode="min")
    db = SearchPlanDB()
    spec = StudySpec("resnet", "synthetic-cifar", ("lr", "momentum"))
    svc = StudyService(db, trainer, n_workers=n_workers,
                       worker_meshes=worker_meshes)
    trainer.paths, trainer.group_calls = set(), 0     # this run's own
    comp0 = trainer.compile_seconds
    t0 = time.perf_counter()
    svc.submit(spec, tuner)
    stats = svc.close()
    jax.block_until_ready(trainer.last)
    wall = time.perf_counter() - t0
    return {"stats": stats, "wall": wall,
            "compile": trainer.compile_seconds - comp0,
            "paths": trainer.paths, "group_calls": trainer.group_calls,
            "tuner": tuner, "trials": trials, "plan": db.get(spec.key),
            "store": svc.engine.store}


def finishers(res: dict) -> dict:
    """trial index -> its loss at every rung, for the trials that reached
    the last rung."""
    losses = {}
    for i, step, m in res["tuner"].history:
        losses.setdefault(i, {})[step] = m["loss"]
    return {i: ls for i, ls in losses.items() if RUNGS[-1] in ls}


def best_trial(res: dict) -> int:
    done = finishers(res)
    return min(done, key=lambda i: done[i][RUNGS[-1]])


def boundary_params(res: dict, i: int, step: int):
    """The trial's boundary checkpoint at ``step``, as the engine stored
    it."""
    plan, trial = res["plan"], res["trials"][i]
    for nid in plan.trial_paths[trial.trial_id]:
        cid = plan.node(nid).ckpts.get(step)
        if cid is not None:
            return res["store"].get(cid)["params"]
    raise KeyError(f"trial {i} has no checkpoint at step {step}")


def straight_through(trainer: JaxTrainer, res: dict, i: int, stop: int):
    """Train trial ``i`` from scratch to ``stop`` in one solo chain on the
    default device, with no checkpoint in between."""
    plan, trial = res["plan"], res["trials"][i]
    path = [plan.node(nid) for nid in plan.trial_paths[trial.trial_id]]
    ctxs = []
    for node, nxt in zip(path, path[1:] + [None]):   # root first
        hi = stop if nxt is None else min(stop, nxt.start)
        if node.start >= hi:
            break
        ctxs.append(StageContext(node.node_id, node.desc, node.start,
                                 node.start, hi, plan.path_key(node.node_id)))
    trainer.set_mesh(None)
    return trainer.run_chain(trainer.init_state(), ctxs)[-1]["params"]


def rel_err(got, ref) -> float:
    """Largest parameter difference over the largest reference magnitude."""
    got_l = [np.asarray(x, np.float64) for x in jax.tree.leaves(got)]
    ref_l = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref)]
    scale = max(float(np.abs(r).max()) for r in ref_l)
    return max(float(np.abs(g - r).max()) for g, r in zip(got_l, ref_l)) \
        / scale


def study_failures(trainer: PlacementRecorder, res: dict) -> list:
    """What must hold after any run of the study, on any backend."""
    st, bad = res["stats"], []
    if st.kernel_fallbacks != 0:
        bad.append(f"kernel_fallbacks {st.kernel_fallbacks}")
    if trainer.use_kernel and st.kernel_calls <= 0:
        bad.append("the fused optimizer kernel was never traced")
    if st.batched_groups < 1:
        bad.append("no sibling group ran batched")
    if st.batched_groups != res["group_calls"] or st.groups_degraded:
        bad.append(f"{res['group_calls']} batched calls but "
                   f"{st.batched_groups} groups ran batched "
                   f"({st.groups_degraded} degraded)")
    if st.chain_fused_stages <= 0:
        bad.append("no chain-fused stage")
    if ("solo", 1) not in res["paths"]:
        bad.append("no solo chain")
    if not any(depth > 1 for _, depth in res["paths"]):
        bad.append("no fused chain of two or more stages")
    if st.steps_run != EXPECTED_STEPS:
        bad.append(f"steps_run {st.steps_run} != {EXPECTED_STEPS}")
    losses = [m["loss"] for _, _, m in res["tuner"].history]
    if not losses or not all(math.isfinite(x) for x in losses):
        bad.append(f"non-finite loss among {losses}")
    if len(finishers(res)) != 2:
        bad.append(f"{len(finishers(res))} trials finished, expected 2")
    return bad


def forked_prefix_error(trainer: JaxTrainer, res: dict) -> float:
    """The paper's invariant: the best trial's state after rung 2 (a
    shared prefix, then a batched sibling group) against the same trial
    trained straight through solo."""
    i = best_trial(res)
    forked = boundary_params(res, i, RUNGS[1])
    straight = straight_through(trainer, res, i, RUNGS[1])
    return rel_err(forked, straight)


def report(name: str, res: dict) -> None:
    st = res["stats"]
    print(f"[{name}] steps_run {st.steps_run} stages_run {st.stages_run} "
          f"batched_groups {st.batched_groups} batched_stages "
          f"{st.batched_stages} chain_fused_stages {st.chain_fused_stages} "
          f"kernel_calls {st.kernel_calls} kernel_fallbacks "
          f"{st.kernel_fallbacks} mesh_placements {st.mesh_placements} "
          f"d2d_handoffs {st.d2d_handoffs}")
    i = best_trial(res)
    ls = finishers(res)[i]
    print(f"[{name}] best trial {i}: loss {ls[RUNGS[0]]:.6f} at step "
          f"{RUNGS[0]} -> {ls[RUNGS[-1]]:.6f} at step {RUNGS[-1]}")
    print(f"[{name}] wall_s {res['wall']:.3f} (compile_s "
          f"{res['compile']:.3f} inside it)")


def one_chip(seed: int) -> list:
    trainer = build_trainer(seed=seed)
    warm = run_study(trainer)            # set-up: compiles every executable
    print(f"setup: study wall_s {warm['wall']:.3f}, chunk compile_s "
          f"{warm['compile']:.3f}")
    res = run_study(trainer)             # timed: must compile nothing
    report("1 chip", res)
    bad = study_failures(trainer, warm) + study_failures(trainer, res)
    if res["compile"] != 0.0:
        bad.append(f"compiled {res['compile']:.3f}s inside the timed run")
    placed = set().union(*trainer.placed.values())
    print(f"boundary states on {sorted(str(d) for d in placed)}")
    if {d.platform for d in placed} != {"tpu"}:
        bad.append(f"boundary states off the TPU: {placed}")
    err = forked_prefix_error(trainer, res)
    print(f"forked prefix vs straight through: rel_err {err:.3e} "
          f"(tolerance {PARAM_RTOL:.1e})")
    if not err <= PARAM_RTOL:
        bad.append(f"forked-prefix rel_err {err:.3e} > {PARAM_RTOL:.1e}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak_bytes_in_use {stats['peak_bytes_in_use']}")
    return bad


def fleet_ways(seed: int, n: int = 9, batch: int = 128):
    """The same study on one chip, on four 1-chip worker meshes, and on
    one 4-chip worker mesh.  Returns (name, trainer, result, meshes)."""
    ways = [("1 chip", 1, None),
            ("4 x 1-chip workers", 4, list(plan_worker_meshes(4, 1))),
            ("1 x 4-chip worker", 1, [WorkerMesh.build([0, 1, 2, 3])])]
    out = []
    for name, n_workers, meshes in ways:
        trainer = build_trainer(n=n, batch=batch, seed=seed)
        res = run_study(trainer, n_workers=n_workers, worker_meshes=meshes)
        out.append((name, trainer, res, meshes))
    return out


def fleet_failures(ways) -> list:
    devices = jax.devices()
    bad = []
    ref = ways[0][2]
    ref_done = finishers(ref)
    for name, trainer, res, meshes in ways:
        bad += [f"[{name}] {b}" for b in study_failures(trainer, res)]
        owned = {None: {devices[0]}}
        for m in meshes or []:
            owned[m.key] = {devices[d] for d in m.device_ids}
        for key, devs in trainer.placed.items():
            if not devs <= owned.get(key, set()):
                bad.append(f"[{name}] worker {key} holds states on {devs}")
        if res["stats"].steps_run != ref["stats"].steps_run:
            bad.append(f"[{name}] steps_run {res['stats'].steps_run} != "
                       f"{ref['stats'].steps_run}")
        done = finishers(res)
        if sorted(done) != sorted(ref_done):
            bad.append(f"[{name}] trials {sorted(done)} finished, "
                       f"reference {sorted(ref_done)}")
            continue
        for i in done:
            err = rel_err(boundary_params(res, i, RUNGS[-1]),
                          boundary_params(ref, i, RUNGS[-1]))
            print(f"[{name}] trial {i} final params vs 1 chip: rel_err "
                  f"{err:.3e}")
            if not err <= PARAM_RTOL:
                bad.append(f"[{name}] trial {i} rel_err {err:.3e}")
    fleet = ways[1][1]
    spread = set().union(*fleet.placed.values())
    if len(spread) != 4:
        bad.append(f"4 x 1-chip fleet used {len(spread)} devices")
    return bad


def four_chips(seed: int) -> list:
    ways = fleet_ways(seed)
    for name, trainer, res, meshes in ways:
        report(name, res)
        for key, devs in sorted(trainer.placed.items(), key=str):
            ids = key[0] if key else "default"
            print(f"[{name}] worker devices {ids}: states on "
                  f"{sorted(str(d) for d in devs)}")
    return fleet_failures(ways)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the worker-fleet phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script has no CPU mode", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    print(f"platform {dev.platform} device_kind {dev.device_kind} "
          f"device_count {len(devices)}")
    print(f"compile cache {enable_compile_cache()}")

    bad = one_chip(args.seed) if args.chips == 1 else four_chips(args.seed)
    if bad:
        for b in bad:
            print(f"FAIL {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
