"""One run of one cell: set-up, the measured window, the traced reading
and the check.

The window drives the users' path: each study is submitted with
``StudyService.submit`` and runs through the dispatcher, the stage tree
and the ``JaxTrainer`` chunk executables on one worker.  Studies run back
to back (a closed loop), each with its own plan, its own initial weights
and data order drawn from the run's seed and its index, so no stage is
served from an earlier study.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name in ``BENCHMARK.json``:
``chipbench/traffic/<traffic>.json``, the configuration's ``file``,
``chipbench/limits/<workload>.json`` and ``chipbench/metrics/<metric>.py``.
"""

from __future__ import annotations

import gc
import importlib
import json
import random
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from chipbench import check, peaks, reference as ref, shapes, trace
from chipbench.traffic import study_seed, study_trials
from repro.core import SearchPlanDB, StudyService, StudySpec
from repro.core.trainer import StageContext
from repro.core.tuners import SHATuner
from repro.data import DataPipeline
from repro.models.resnet import ResNet
from repro.train.jax_trainer import JaxTrainer

ROOT = Path(__file__).resolve().parents[1]

__all__ = ["Cell", "load_cell", "rehearsal_cell", "cell_rows", "SpanTrainer",
           "run_cell", "set_up", "measure", "judge", "ROOT"]


# ------------------------------------------------------------------ cells
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (root / "chipbench" / "limits" / f"{workload}.json").read_text())
    return Cell(workload, int(w["chips"]), config, traffic, limits,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)])


def rehearsal_cell(workload: str, n: int = 1, width: int = 8,
                   batch: int = 8, eval_rows: int = 16,
                   root: Path = ROOT) -> Cell:
    """``workload`` with its model, batch and held-out rows cut to a size
    the CPU runs in seconds: the rehearsal of a cell before the chip.
    The traffic's studies, rungs and trials are kept.  (Narrower nets
    train chaotically enough that float32 round-off, summed in another
    order by the reference, grows past rounding within a stage.)"""
    cell = load_cell(workload, root)
    cell.config = dict(cell.config, n=n, width=width)
    cell.traffic = dict(cell.traffic, batch_size=batch, eval_rows=eval_rows)
    return cell


# ------------------------------------------------------- the traced trainer
class SpanTrainer(JaxTrainer):
    """``JaxTrainer`` whose calls are named host spans in the profiler's
    trace, and which keeps the boundary states of the study it is told to
    record (``record``, with ``plan`` to name each stage's parent)."""

    record: Optional[check.StudyRecord] = None
    plan = None

    def _keep(self, chains, outs):
        if self.record is None:
            return
        for ch, out in zip(chains, outs):
            for j, (ctx, st) in enumerate(zip(ch, out)):
                parent = self.plan.node(ctx.node_id).parent
                self.record.spans[(ctx.node_id, ctx.stop)] = check.Span(
                    ctx.node_id, parent, ctx.start, ctx.stop, len(chains),
                    j, len(ch), st)

    def run_stage(self, state, ctx):
        with jax.profiler.TraceAnnotation("chipbench.run_stage"):
            out = super().run_stage(state, ctx)
        self._keep([[ctx]], [[out]])
        return out

    def run_stages_batched(self, states, ctxs):
        with jax.profiler.TraceAnnotation("chipbench.run_stages_batched"):
            outs = super().run_stages_batched(states, ctxs)
        self._keep([[c] for c in ctxs], [[o] for o in outs])
        return outs

    def run_chain(self, state, ctxs):
        with jax.profiler.TraceAnnotation("chipbench.run_chain"):
            out = super().run_chain(state, ctxs)
        self._keep([list(ctxs)], [out])
        return out

    def run_chains_batched(self, states, chains):
        with jax.profiler.TraceAnnotation("chipbench.run_chains_batched"):
            outs = super().run_chains_batched(states, chains)
        self._keep([list(c) for c in chains], outs)
        return outs

    def evaluate(self, state, ctx):
        with jax.profiler.TraceAnnotation("chipbench.evaluate"):
            return super().evaluate(state, ctx)


class _Handle:
    """The tuner's handle, noting when each trial-rung was made
    runnable."""

    def __init__(self, handle, made):
        self._h, self._made = handle, made

    def submit(self, trial, upto=None):
        self._made[(trial.trial_id, upto)] = time.perf_counter()
        self._h.submit(trial, upto)

    def kill(self, trial):
        self._h.kill(trial)


class TimedSHA(SHATuner):
    """``SHATuner`` that notes, for every result it is given, when the
    trial-rung was made runnable and when the result arrived."""

    def __init__(self, trials, plan, **kw):
        super().__init__(trials, **kw)
        self.plan = plan
        self.made: Dict = {}
        self.last_step: Dict[str, int] = {}
        # (trial id, step, steps since the trial's last result, loss,
        #  made runnable, arrived, the trial's path)
        self.results: List[tuple] = []

    def start(self, handle):
        super().start(_Handle(handle, self.made))

    def on_result(self, trial, step, metrics):
        now = time.perf_counter()
        tid = trial.trial_id
        prev = self.last_step.get(tid, 0)
        if step > prev:
            self.last_step[tid] = step
            self.results.append((
                tid, step, step - prev, float(metrics["loss"]),
                self.made.get((tid, step), now), now,
                tuple((nid, self.plan.node(nid).start)
                      for nid in self.plan.trial_paths.get(tid, ()))))
        super().on_result(trial, step, metrics)


# ------------------------------------------------------------------ the run
class _Compiles:
    """Counts the programs JAX compiles (``n``: each XLA compile or cache
    load), and its compilation cache's hits and misses."""

    NAMES = {"/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        self.counts = {v: 0 for v in self.NAMES.values()}
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *args, **kw):
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    @property
    def n(self) -> int:
        return self.counts["compiles"]


def cell_rows(cell: Cell, seed: int):
    """The run's training rows (one epoch per trial: the last rung's steps
    times the batch) and held-out rows, from ``seed``."""
    tr = cell.traffic
    rows = ref.cifar_rows(tr["tuner"]["rungs"][-1] * tr["batch_size"], seed)
    return rows, ref.cifar_rows(int(tr["eval_rows"]), study_seed(seed, -1, 1))


class _Bench:
    """The state one run keeps: data, trainer and the window's tally."""

    def __init__(self, cell: Cell, seed: int, use_kernel, trainer_cls):
        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.batch = int(tr["batch_size"])
        self.rows, self.eval_rows = cell_rows(cell, seed)
        self.shuffle = 0
        self.trainer = trainer_cls(
            ResNet(n=cfg["n"], width=cfg["width"],
                   num_classes=cfg["classes"]),
            lambda: DataPipeline(self.rows, batch_size=self.batch,
                                 seed=self.shuffle),
            self.eval_rows, default_optimizer=tr["optimizer"],
            seed=0, use_kernel=use_kernel)

    def reseed(self, seed: int) -> None:
        """New data from ``seed`` for the same trainer and executables."""
        self.seed = seed
        self.rows, self.eval_rows = cell_rows(self.cell, seed)
        self.trainer.eval_batch = {k: jax.numpy.asarray(v)
                                   for k, v in self.eval_rows.items()}

    def study(self, index: int, deadline: float = float("inf"),
              record: bool = False):
        """Run study ``index`` through a fresh ``StudyService`` until it is
        done or ``deadline`` passes.  Returns (tuner, stats, record)."""
        tr = self.cell.traffic
        init_seed = study_seed(self.seed, index, 2)
        self.shuffle = study_seed(self.seed, index, 3)
        self.trainer.seed = init_seed
        trials = study_trials(tr)
        db = SearchPlanDB()
        spec = StudySpec(self.cell.config["name"],
                         f"synthetic-cifar-{index}", ("lr", "momentum"))
        plan = db.get(spec.key)
        t = tr["tuner"]
        tuner = TimedSHA([x for x, _ in trials], plan,
                         min_steps=t["rungs"][0], max_steps=t["rungs"][-1],
                         eta=t["eta"], objective=t["objective"],
                         mode=t["mode"])
        rec = None
        if record:
            rec = check.StudyRecord(init_seed, self.shuffle)
            rec.fns = {x.trial_id: f for x, f in trials}
        self.trainer.record, self.trainer.plan = rec, plan
        svc = StudyService(db, self.trainer, n_workers=1)
        fut = svc.submit(spec, tuner)
        while fut.status in ("queued", "running"):
            if not svc.step() or time.perf_counter() >= deadline:
                break
        done = fut.done()
        stats = svc.close() if done else svc.engine.finish()
        self.trainer.record = None
        if rec is not None:
            rec.done = done
            rec.results = [(r[0], r[1], r[3], r[6]) for r in tuner.results]
        return tuner, stats, rec

    def warm(self, phases=None, t0: float = 0.0):
        """Set-up: one study through the window's own path, then one call
        of every sibling-group width the traffic can run (width 1: a solo
        stage of two chunks, whose second chunk donates its carry).
        ``phases`` gets the clock at the end of each part."""
        phases = {} if phases is None else phases
        self.study(-2)
        phases["warm_study_s"] = time.perf_counter() - t0
        tr = self.cell.traffic
        k = self.trainer.chunk_steps
        desc = {"hps": {"lr": {"kind": "const", "value": 0.01},
                        "momentum": {"kind": "const", "value": 0.9}},
                "static": {"optimizer": tr["optimizer"], "wd": tr["wd"]}}
        for m in tr["group_widths"]:
            ctx = StageContext("warm", desc, 0, 0, k * (2 if m == 1 else 1),
                               "warm")
            states = [self.trainer.init_state() for _ in range(m)]
            out = self.trainer.run_stages_batched(states, [ctx] * m)
            jax.block_until_ready([o["params"] for o in out])
            phases[f"width_{m}_s"] = time.perf_counter() - t0


def _p90(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs), 90, method="linear"))


def device_info(chips: int) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, use_kernel: Optional[bool] = None,
             trainer_cls=SpanTrainer, candidate=None) -> Dict[str, Any]:
    """Set up, measure for ``seconds``, check.  Returns the result line
    (as a dict) and, under ``"checks"``, each number compared with its
    limit.  ``candidate`` replaces the program's answers in the check
    (the control and the planted faults use it)."""
    bench = set_up(cell, seed, t_start, use_kernel, trainer_cls)
    return measure(bench, seconds, traced, candidate)


def set_up(cell: Cell, seed: int, t_start: float,
           use_kernel: Optional[bool] = None, trainer_cls=SpanTrainer):
    """Everything before the window: data from ``seed``, the trainer, the
    warm-up.  ``bench.setup_s`` is the time since ``t_start``."""
    compiles = _Compiles()
    phases = {"to_run_cell_s": time.perf_counter() - t_start}
    bench = _Bench(cell, seed, use_kernel, trainer_cls)
    phases["data_s"] = time.perf_counter() - t_start
    bench.warm(phases, t_start)
    gc.collect()
    bench.setup_s = time.perf_counter() - t_start
    phases.update(compiles.counts)
    bench.compiles, bench.phases = compiles, phases
    return bench


def measure(bench, seconds: float, traced: bool, candidate=None
            ) -> Dict[str, Any]:
    """The window and the check, on a set-up ``bench``."""
    cell, seed, compiles = bench.cell, bench.seed, bench.compiles
    setup_s, phases = bench.setup_s, bench.phases
    pick = random.Random(study_seed(seed, -3, 5))
    kept: Optional[check.StudyRecord] = None
    n_done = 0
    results, stats_l = [], []
    compiles0, exes0 = compiles.n, len(bench.trainer._chunk_fns)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    index = 0
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() < deadline:
            tuner, stats, rec = bench.study(index, deadline, record=True)
            results += tuner.results
            stats_l.append(stats)
            if rec.done:
                n_done += 1
                if pick.random() * n_done < 1.0:    # reservoir of one
                    kept = rec
            index += 1
    t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    window_compiles = (compiles.n - compiles0
                       + len(bench.trainer._chunk_fns) - exes0)
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    window_s = t1 - t0
    trial_steps = sum(r[2] for r in results)
    member_steps = sum(s.steps_run for s in stats_l)
    out: Dict[str, Any] = {"window_s": window_s, "studies": index,
                           "studies_done": n_done, "results": len(results),
                           "member_steps": member_steps,
                           "trial_steps": trial_steps,
                           "setup_s": setup_s, "setup_phases": phases,
                           "failed": sum(s.stage_failures for s in stats_l)}
    metrics: Dict[str, Dict[str, Any]] = {}
    if not traced:
        vals = {"trial_samples_per_s": trial_steps * bench.batch / window_s,
                "result_p90_s": _p90([r[5] - r[4] for r in results])
                if results else None,
                "setup_s": setup_s}
        for m in cell.end_to_end:
            if vals.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    else:
        view = _layer_view(cell, bench, stats_l, results, tdir, window_s,
                           member_steps, trial_steps)
        shutil.rmtree(tdir, ignore_errors=True)
        out["busy_s"], out["trace_window_s"] = view.busy_s, view.window_s
        out["breakdown"] = view.breakdown
        for m in cell.per_layer:
            mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
            v = mod.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["memory_peak_bytes"] = peak

    # ---- the check: after the window, with the program's state freed
    kept_host = None
    if kept is not None:
        kept.spans = {k: check.Span(s.node, s.parent, s.start, s.stop,
                                    s.group, s.pos, s.depth,
                                    jax.device_get(s.state))
                      for k, s in kept.spans.items()}
        kept_host = kept
    del stats_l
    gc.collect()
    t2 = time.perf_counter()
    nums: Dict[str, Any] = {}
    if kept_host is not None:
        nums = check.compare_study(
            kept_host, cell.config, bench.rows, bench.eval_rows, cell.traffic,
            ref.Reference(), candidate=candidate)
    out["reference_s"] = time.perf_counter() - t2
    out["compared"] = nums
    out["record"] = kept_host
    out["checks"] = judge(cell.limits, nums, window_compiles)
    _join_writers()
    out["correct"] = all(c["pass"] for c in out["checks"].values())
    for c in out["checks"].values():
        del c["pass"]
    return out


def judge(limits: Dict[str, Any], nums: Dict[str, Any],
          window_compiles: int = 0) -> Dict[str, Dict[str, Any]]:
    """Each number compared, with its limit and whether it is within it.
    A study that was never compared fails every number."""
    checks = {}
    for name in ("loss_gap", "change_gap", "mom_gap", "root_diff",
                 "parts_uncompared", "unmatched_spans"):
        v = nums.get(name, float("inf"))
        checks[name] = {"value": v, "limit": limits[name],
                        "pass": v <= limits[name]}
    checks["window_compiles"] = {"value": window_compiles, "limit": 0,
                                 "pass": window_compiles == 0}
    return checks


def _join_writers(timeout: float = 30.0) -> None:
    """Wait for the checkpoint stores' write-behind threads, which retire
    a few seconds after their last write, so none is cut off at exit."""
    for t in threading.enumerate():
        if t.name == "ckpt-writer":
            t.join(timeout)


# ------------------------------------------------------- per-layer reading
@dataclass
class LayerView:
    """What a per-layer metric reader reads."""

    counters: Dict[str, float]
    member_steps: int
    trial_steps: int
    batch: int
    params: int
    flops_per_sample: float
    busy_s: float
    window_s: float
    span_s: float
    opt_kernel_s: Optional[float]
    peak: Dict[str, float]
    breakdown: Dict[str, Any]


def _layer_view(cell, bench, stats_l, results, tdir, window_s, member_steps,
                trial_steps) -> LayerView:
    keys = ("stages_run", "batched_stages", "ckpt_saves", "steps_run")
    counters = {k: sum(getattr(s, k) for s in stats_l) for k in keys}
    counters["ckpt_save_seconds"] = sum(s.ckpt_save_seconds for s in stats_l)
    tr = trace.load(tdir)
    win = tr.span("chipbench.window")
    cfg = cell.config
    return LayerView(
        counters=counters, member_steps=member_steps,
        trial_steps=trial_steps, batch=bench.batch,
        params=shapes.resnet_params(cfg["n"], cfg["width"], cfg["classes"]),
        flops_per_sample=shapes.resnet_train_flops(
            cfg["n"], cfg["width"], cfg["classes"]),
        busy_s=tr.busy_seconds(win), window_s=(win[1] - win[0]) * 1e-9,
        span_s=tr.span_cover_seconds(win, prefix="chipbench.",
                                     exclude=("chipbench.window",)),
        opt_kernel_s=tr.op_seconds(win, shapes.OPT_KERNEL_MATCH),
        peak=peaks.lookup(jax.devices()[0].device_kind),
        breakdown=tr.breakdown(win, exclude=("chipbench.window",)))
