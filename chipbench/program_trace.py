#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and the readings taken
from them.

The program opens a ``hippo.*`` span at each layer boundary
(:data:`repro.utils.spans.SPANS`): the service, the engine, the
dispatcher's rounds and work units, the checkpoint plane, the trainer's
host-side steps.  They land in the profiler's trace on the clock of the
device's operations, so each idle gap of the device can be put down to
the layer the host was in.  :class:`ProgramTrace` is a
:class:`chipbench.trace.Trace` that also keeps those spans, each with its
host thread and stats, and the device's ``jit_hippo_*`` modules:

* :meth:`ProgramTrace.self_seconds`: the union of a set of spans less the
  part their children on the same thread cover;
* :meth:`ProgramTrace.idle_by_span`: each idle interval of the first busy
  device, put down to the innermost ``hippo.*`` span open on the main
  thread at each instant, and what no program span covers to the
  innermost ``chipbench.*`` span;
* :func:`readings`: ``dispatch_ms_per_round``,
  ``host_feed_ms_per_member_step`` and ``result_wait_share``.

Run as a script, it traces one cell's window and prints those readings
with the per-layer metrics of ``BENCHMARK.json`` read from the same trace
(no check of the answers; ``run.py`` does that):

    python3 chipbench/program_trace.py --workload wrn16-8.sha-paper \\
        --seed 7 --seconds 30

Without a TPU it exits 2 and prints nothing.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import warnings  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import trace  # noqa: E402

__all__ = ["ProgramTrace", "load", "readings", "OUTSIDE", "FEED", "main"]

Interval = Tuple[float, float]
# (name, start ns, end ns, host thread, stats)
Span = Tuple[str, float, float, str, Dict[str, Any]]

PROGRAM = "hippo."
HARNESS = "chipbench."
WINDOW = "chipbench.window"
OUTSIDE = "outside the program"
# the trainer's host-side work around each executable: the data plane's
# share of the host
FEED = ("hippo.trainer.prepare", "hippo.trainer.feed",
        "hippo.trainer.launch", "hippo.trainer.snapshot")
# what a dispatcher round calls into, which is not the dispatcher's own time
ROUND_CHILDREN = ("hippo.trainer.", "hippo.ckpt.", "hippo.tuner.")
# a device plane's line of the modules (programs) it ran
MODULES_LINE = "XLA Modules"


def _total(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _intersect(a: Sequence[Interval], b: Sequence[Interval]
               ) -> List[Interval]:
    """The intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(spans: Sequence[Span], win: Interval
               ) -> List[Tuple[float, float, Optional[str]]]:
    """``win`` cut into pieces, each named by the innermost of ``spans``
    (spans of one thread, so nested) open over it, or None."""
    events = []
    for k, (n, a, b, _, _) in enumerate(spans):
        a, b = max(a, win[0]), min(b, win[1])
        if b > a:
            # at one instant: closes first, then opens outer before inner
            events += [(a, 1, -b, k), (b, 0, 0.0, k)]
    events.sort()
    out, open_, t = [], [], win[0]
    for when, opens, _, k in events:
        if when > t:
            out.append((t, when, spans[open_[-1]][0] if open_ else None))
            t = when
        if opens:
            open_.append(k)
        else:
            open_.remove(k)
    if t < win[1]:
        out.append((t, win[1], None))
    return out


def _attribute(pieces, gaps: Sequence[Interval]) -> Dict[Optional[str], float]:
    """Seconds of ``gaps`` under each name of ``pieces`` (both sorted)."""
    out: Dict[Optional[str], float] = {}
    i = 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        k = i
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                name = pieces[k][2]
                out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
            k += 1
    return out


@dataclass
class ProgramTrace(trace.Trace):
    """A :class:`chipbench.trace.Trace` with the program's spans
    (``program``), the device's ``(module, start, end)`` events
    (``modules``), the host spans of the benchmark with their threads
    (``harness``) and the thread that ran the window (``main``)."""

    program: List[Span] = field(default_factory=list)
    harness: List[Span] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    main: Optional[str] = None

    def _on(self, spans: Sequence[Span], thread: Optional[str]
            ) -> List[Span]:
        return [s for s in spans if thread is None or s[3] == thread]

    def self_seconds(self, win: Interval, names: Sequence[str],
                     children: Sequence[str]) -> float:
        """Time in ``win`` inside the spans called ``names`` less the part
        that spans whose names start with one of ``children`` cover on the
        same thread."""
        names, children = set(names), tuple(children)
        total = 0.0
        for thread in {s[3] for s in self.program if s[0] in names}:
            own = trace.union(trace.clip(
                [(a, b) for n, a, b, t, _ in self.program
                 if t == thread and n in names], *win))
            kids = trace.union(trace.clip(
                [(a, b) for n, a, b, t, _ in self.program
                 if t == thread and n.startswith(children)
                 and n not in names], *win))
            total += _total(own) - _total(_intersect(own, kids))
        return total * 1e-9

    def cover_seconds(self, win: Interval, names: Sequence[str]) -> float:
        """Time in ``win`` the main thread spent inside the spans called
        ``names``."""
        names = set(names)
        iv = [(a, b) for n, a, b, _, _ in self._on(self.program, self.main)
              if n in names]
        return _total(trace.union(trace.clip(iv, *win))) * 1e-9

    def seconds_by_name(self, win: Interval) -> Dict[str, float]:
        """Summed duration in ``win`` of each program span name."""
        out: Dict[str, float] = {}
        for n, a, b, _, _ in self.program:
            lo, hi = max(a, win[0]), min(b, win[1])
            if hi > lo:
                out[n] = out.get(n, 0.0) + (hi - lo) * 1e-9
        return out

    def span_counts(self, win: Interval) -> Dict[str, int]:
        """Program spans that start in ``win``, by name."""
        return dict(Counter(n for n, a, _, _, _ in self.program
                            if win[0] <= a < win[1]))

    def idle_by_span(self, win: Interval) -> Dict[str, Any]:
        """The device's idle seconds in ``win`` put down to the innermost
        program span open on the main thread (``by_span``; :data:`OUTSIDE`
        where none is), the part of them under some program span
        (``covered``), and the seconds outside the program by the
        innermost benchmark span (``outside``)."""
        gaps = self.idle_gaps(win)
        idle = _total(gaps) * 1e-9
        pieces = _innermost(self._on(self.program, self.main), win)
        by = _attribute(pieces, gaps)
        out_s = by.pop(None, 0.0)
        if out_s:
            by[OUTSIDE] = out_s
        spare = [(a, b) for a, b, n in pieces if n is None]
        outside = _attribute(_innermost(self._on(self.harness, self.main),
                                        win),
                             _intersect(gaps, spare))
        outside = {(n or "no span"): s for n, s in outside.items()}
        return {"idle_s": idle,
                "covered": 1.0 - out_s / idle if idle > 0 else None,
                "by_span": dict(sorted(by.items(), key=lambda x: -x[1])),
                "outside": dict(sorted(outside.items(),
                                       key=lambda x: -x[1]))}


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: what
    :func:`chipbench.trace.load` reads, and the program's spans, the
    benchmark's spans with their threads and the device's modules."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = ProgramTrace()
    with warnings.catch_warnings():
        # the first read of an event's stats builds a native type, which
        # warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        _read_planes(pd, tr)
    if tr.main is None and tr.program:
        tr.main = Counter(s[3] for s in tr.program).most_common(1)[0][0]
    return tr


def _read_planes(pd, tr: ProgramTrace) -> None:
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            ops = tr.devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    tr.modules.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}#{i}"
                for e in line.events:
                    n = e.name
                    if n.startswith(PROGRAM) or n.startswith(HARNESS):
                        s = (n, e.start_ns, e.start_ns + e.duration_ns,
                             thread, dict(e.stats))
                        if n.startswith(PROGRAM):
                            tr.program.append(s)
                        else:
                            tr.harness.append(s)
                            tr.host.append(s[:3])
                            if n == WINDOW:
                                tr.main = thread
                    elif line.name.startswith("tf_XLA"):
                        # the CPU runs modules on its client's threads
                        mod = dict(e.stats).get("hlo_module")
                        if mod:
                            tr.modules.append(
                                (mod, e.start_ns, e.start_ns + e.duration_ns))


def readings(tr: ProgramTrace, win: Interval, counters: Dict[str, float],
             member_steps: int) -> Dict[str, Optional[float]]:
    """The three readings of the program's layers.

    * ``dispatch_ms_per_round``: self time of ``hippo.dispatch.round`` (less
      the trainer, checkpoint and tuner spans inside it) per scheduling
      round (``EngineStats.rounds``), in ms;
    * ``host_feed_ms_per_member_step``: time the main thread spent in the
      trainer's host-side work around the executables (:data:`FEED`) per
      member-step, in ms;
    * ``result_wait_share``: of a result's time from request to tuner, the
      share spent before the work unit that serves it started
      (``result_wait_seconds / (result_wait_seconds +
      result_run_seconds)``).

    Each is None where there is nothing to read (no span, no round, no
    timed result)."""
    out: Dict[str, Optional[float]] = {}
    rounds = counters.get("rounds", 0)
    names = {s[0] for s in tr.program}
    out["dispatch_ms_per_round"] = (
        tr.self_seconds(win, ["hippo.dispatch.round"], ROUND_CHILDREN)
        * 1e3 / rounds if rounds and "hippo.dispatch.round" in names
        else None)
    out["host_feed_ms_per_member_step"] = (
        tr.cover_seconds(win, FEED) * 1e3 / member_steps
        if member_steps and names & set(FEED) else None)
    wait = counters.get("result_wait_seconds", 0.0)
    run = counters.get("result_run_seconds", 0.0)
    out["result_wait_share"] = (
        wait / (wait + run)
        if counters.get("results_timed", 0) and wait + run > 0 else None)
    return out


COUNTERS = ("stages_run", "batched_stages", "ckpt_saves", "steps_run",
            "ckpt_save_seconds", "rounds", "result_wait_seconds",
            "result_run_seconds", "results_timed")


def summed(stats_l) -> Dict[str, float]:
    """The counters the readings use, summed over a window's studies (a
    counter the program lacks reads 0)."""
    return {k: sum(getattr(s, k, 0) for s in stats_l) for k in COUNTERS}


# ------------------------------------------------------------------ the run
def traced_window(bench, seconds: float, tdir: str):
    """Studies back to back for ``seconds`` under the profiler, as the
    harness's window runs them (without recording states for the check).
    Returns (results, stats of each study, window seconds).

    The profiler's Python tracer (an event for every Python call, on by
    default and in ``run.py --trace 1``) is left off: the program's spans
    need only the host tracer, and the Python tracer inflates the spans of
    pure-Python layers (on a TPU v5e host the dispatcher round's self time
    read 1.6-1.7 ms per round with it, 1.15-1.16 ms without, at the same
    window throughput)."""
    import jax

    results, stats_l = [], []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    deadline, index = t0 + seconds, 0
    with jax.profiler.TraceAnnotation(WINDOW):
        while time.perf_counter() < deadline:
            tuner, stats, _ = bench.study(index, deadline)
            results += tuner.results
            stats_l.append(stats)
            index += 1
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return results, stats_l, window_s


def report(cell, bench, results, stats_l, window_s, tr: ProgramTrace
           ) -> Dict[str, Any]:
    """The JSON line of one traced window."""
    import jax
    import numpy as np

    from chipbench import harness, peaks, shapes

    win = tr.span(WINDOW)
    counters = summed(stats_l)
    member_steps = int(counters["steps_run"])
    trial_steps = sum(r[2] for r in results)
    cfg = cell.config
    view = harness.LayerView(
        counters=counters, member_steps=member_steps,
        trial_steps=trial_steps, batch=bench.batch,
        params=shapes.resnet_params(cfg["n"], cfg["width"], cfg["classes"]),
        flops_per_sample=shapes.resnet_train_flops(
            cfg["n"], cfg["width"], cfg["classes"]),
        busy_s=tr.busy_seconds(win), window_s=(win[1] - win[0]) * 1e-9,
        span_s=tr.span_cover_seconds(win, prefix=HARNESS,
                                     exclude=(WINDOW,)),
        opt_kernel_s=tr.op_seconds(win, shapes.OPT_KERNEL_MATCH),
        peak=peaks.lookup(jax.devices()[0].device_kind), breakdown={})
    metrics: Dict[str, Any] = {}
    for m in cell.per_layer:
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        metrics[m["name"]] = mod.read(view)
    metrics.update(readings(tr, win, counters, member_steps))
    metrics["trial_samples_per_s"] = trial_steps * bench.batch / window_s
    metrics["result_p90_s"] = (float(np.percentile(
        [r[5] - r[4] for r in results], 90)) if results else None)
    by_name = tr.seconds_by_name(win)
    counts = tr.span_counts(win)
    return {
        "metrics": metrics,
        "idle_by_program_span": tr.idle_by_span(win),
        "spans": {"count": sum(counts.values()), "by_name": counts,
                  "seconds": by_name},
        "compile_s": by_name.get("hippo.trainer.compile", 0.0),
        "modules": sorted({n.split("(")[0] for n, _, _ in tr.modules
                           if "hippo" in n}),
        "results_wait": {
            "program_s": counters["result_wait_seconds"]
            + counters["result_run_seconds"],
            "program_results": counters["results_timed"],
            "tuner_s": sum(r[5] - r[4] for r in results),
            "tuner_results": len(results)},
        "device": dict(harness.device_info(cell.chips),
                       busy_s=view.busy_s, window_s=view.window_s),
        "diagnostics": {"window_s": window_s, "studies": len(stats_l),
                        "results": len(results),
                        "member_steps": member_steps,
                        "trial_steps": trial_steps,
                        "setup_s": bench.setup_s},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench.run import use_checkout_cache
    use_checkout_cache()
    import jax

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"program_trace: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    bench = harness.set_up(cell, args.seed, T_START)
    tdir = tempfile.mkdtemp(prefix="chipbench-program-")
    try:
        results, stats_l, window_s = traced_window(bench, args.seconds, tdir)
        line = report(cell, bench, results, stats_l, window_s, load(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    harness._join_writers()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
