"""The comparison that decides ``correct``.

The window's answers are the boundary states the program's stages return
and the evaluation results the tuner receives.  One study is sampled from
the seed among those the window finished, and of its stages a sample is
drawn from the seed: first every stage that adds a part of the path the
traffic names (``root``, ``resumed``, ``group``, ``chain_mid``), then more
while their steps stay within the traffic file's ``check_steps``.  Each is
recomputed by the plain reference (:mod:`chipbench.reference`) from the
state it should have started from:

* a stage at step 0 from the reference's own initialisation;
* any later stage from the boundary state the program returned at that
  step on the stage's own path (the stage before it on the same node, or
  its parent node's last stage): a fork that resumed from the wrong
  checkpoint, or a chain that carried the wrong state across a boundary,
  then disagrees with the reference.

Each span is one stage (8 to 24 steps), so round-off is not amplified over
a whole trial.  The numbers compared:

* ``change_gap``: per stage, the gap between the norms of the program's
  and the reference's parameter change over the stage, leaf by leaf, over
  the reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf, the largest over stages (the median leaf is
  kept in ``per_span``);
* ``mom_gap``: the same for the momentum buffer at the stage's end;
* ``root_diff``: for the stages that start at step 0 (both sides from the
  same initialisation and an empty buffer, one chunk), the norm of the
  *difference* of the two momentum buffers, leaf by leaf over the larger
  of the reference leaf's norm and the median leaf's, the median over
  leaves.  Gaps of norms cannot see a gradient taken over other rows
  (half of the batch left out reads within 5x of sound runs on the chip);
  a difference can.
* ``loss_gap``: over every result the tuner received, the gap between
  the loss it was given and the reference's loss of the program's state
  at that step, over the reference's loss;
* ``parts_uncompared``: parts of the path the traffic names that the
  compared stages and results never took;
* ``unmatched_spans``: stages or results the record cannot place on a
  trial's path.

Leaves whose reference gradient at the stage's first step is under a
thousandth of the median leaf's are left out of both gaps (their change is
round-off).
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from chipbench import reference as ref

__all__ = ["Span", "StudyRecord", "leaf_norms", "leaf_gaps",
           "node_at", "pick_spans", "compare_study"]


@dataclass
class Span:
    """One stage as the program ran it: ``[start, stop)`` of ``node`` (whose
    parent node is ``parent``), the boundary state it returned, and how it
    ran: ``group`` members in the call, position ``pos`` of ``depth``
    stages in a fused chain."""

    node: str
    parent: Optional[str]
    start: int
    stop: int
    group: int
    pos: int
    depth: int
    state: Any = None


@dataclass
class StudyRecord:
    """What the check reads of one study."""

    init_seed: int
    shuffle_seed: int
    spans: Dict[Tuple[str, int], Span] = field(default_factory=dict)
    # (trial id, step, loss the tuner got, the trial's path as
    #  (node id, the node's first step) pairs)
    results: List[Tuple[str, int, float, Tuple[str, ...]]] = \
        field(default_factory=list)
    fns: Dict[str, Dict[str, Dict]] = field(default_factory=dict)
    done: bool = False


def _leaves(tree) -> Dict[str, np.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def leaf_norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree).items()}


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              keep: Sequence[str]) -> Dict[str, float]:
    """``|got - want|`` of each kept leaf over the larger of its own
    ``want`` and the median leaf's."""
    med = float(np.median([want[k] for k in keep]))
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keep}


def _diff(a, b) -> Dict[str, float]:
    la, lb = _leaves(a), _leaves(b)
    return {k: float(np.linalg.norm(la[k] - lb[k])) for k in la}


def _rel_diff(got, want, keep: Sequence[str]) -> float:
    """Median over the kept leaves of the norm of ``got - want`` over the
    larger of the leaf's ``want`` norm and the median leaf's."""
    d = _diff(got, want)
    w = leaf_norms(want)
    med = float(np.median([w[k] for k in keep]))
    return float(np.median([d[k] / max(w[k], med, 1e-30) for k in keep]))


def node_at(path, step: int) -> Optional[str]:
    """The node of ``path`` that trains step ``step - 1``: the last whose
    first step is before ``step``."""
    found = None
    for nid, first in path:
        if first < step:
            found = nid
    return found


def _trial_of(record: StudyRecord, span: Span) -> Optional[str]:
    """A trial that reached ``span.stop`` or beyond through ``span``."""
    for tid, step, _, path in record.results:
        if step >= span.stop and node_at(path, span.stop) == span.node:
            return tid
    return None


def _start_state(record: StudyRecord, span: Span, init):
    """The state ``span`` should start from, as recorded, or None."""
    if span.start == 0:
        return init
    prev = record.spans.get((span.node, span.start))
    if prev is None and span.parent is not None:
        prev = record.spans.get((span.parent, span.start))
    if prev is None:
        return None
    return prev.state


def _parts(span: Span) -> set:
    out = {"root" if span.start == 0 else "resumed"}
    if span.group > 1:
        out.add("group")
    if span.pos < span.depth - 1:
        out.add("chain_mid")
    return out


def pick_spans(cands: List[Tuple[Span, Any]], budget: int, seed: int):
    """The spans to compare, drawn from ``seed``: in a seeded order, first
    every span that adds a part of the path not yet covered, then more
    while their steps stay within ``budget``."""
    order = list(cands)
    random.Random(seed).shuffle(order)
    chosen, covered, steps = [], set(), 0
    for c in order:
        if not _parts(c[0]) <= covered:
            chosen.append(c)
            covered |= _parts(c[0])
            steps += c[0].stop - c[0].start
    for c in order:
        n = c[0].stop - c[0].start
        if c not in chosen and steps + n <= budget:
            chosen.append(c)
            steps += n
    return sorted(chosen, key=lambda c: (c[0].start, c[0].node))


def compare_study(record: StudyRecord, model: Dict[str, Any],
                  rows: Dict[str, np.ndarray], eval_rows: Dict[str, np.ndarray],
                  traffic: Dict[str, Any], reference: ref.Reference,
                  candidate: Optional[Callable] = None) -> Dict[str, Any]:
    """Compare one recorded study with the reference.

    ``candidate(span, start_state, batches, hps)``, when given, stands in
    for the program: it returns the (params, momentum) the span should
    be judged on (the control, or a planted fault).  Otherwise the
    program's recorded boundary state is judged.  Returns the numbers and
    the parts of the path that were compared."""
    n, bs = len(rows["labels"]), traffic["batch_size"]
    wd = float(traffic["wd"])
    init_p = ref.init_params(record.init_seed, model["n"], model["width"],
                             model["classes"])
    init = {"params": init_p,
            "opt": {"m": jax.tree.map(np.zeros_like, init_p)}}
    missing, cands = [], []
    for key, span in record.spans.items():
        tid = _trial_of(record, span)
        start = _start_state(record, span, init)
        if tid is None or start is None:
            missing.append(key)
        else:
            cands.append((span, (tid, start)))
    chosen = pick_spans(cands, int(traffic["check_steps"]),
                        record.shuffle_seed)
    change_gap = mom_gap = root_diff = 0.0
    parts, steps_compared, per_span = set(), 0, []
    for span, (tid, start) in chosen:
        fns = record.fns[tid]
        steps = range(span.start, span.stop)
        idx = [ref.step_rows(n, bs, record.shuffle_seed, s) for s in steps]
        batches = [(rows["images"][i], rows["labels"][i]) for i in idx]
        hps = [(ref.hp_value(fns["lr"], s), ref.hp_value(fns["momentum"], s),
                wd) for s in steps]
        p0 = start["params"]
        m0 = start["opt"]["m"] if start.get("opt") else \
            jax.tree.map(np.zeros_like, p0)
        want_p, want_m, g0 = reference.train(p0, m0, batches, hps,
                                             first_grad=True)
        if candidate is not None:
            got_p, got_m = candidate(span, (p0, m0), batches, hps)
        else:
            got_p, got_m = span.state["params"], span.state["opt"]["m"]
        g_norms = leaf_norms(g0)
        g_med = float(np.median(list(g_norms.values())))
        keep = [k for k, v in g_norms.items() if v >= 1e-3 * g_med]
        dc = leaf_gaps(_diff(got_p, p0), _diff(want_p, p0), keep)
        dm = leaf_gaps(leaf_norms(got_m), leaf_norms(want_m), keep)
        wc, wm = max(dc, key=dc.get), max(dm, key=dm.get)
        change_gap = max(change_gap, dc[wc])
        mom_gap = max(mom_gap, dm[wm])
        if span.start == 0:
            root_diff = max(root_diff, _rel_diff(got_m, want_m, keep))
        med_c = float(np.median(list(dc.values())))
        med_m = float(np.median(list(dm.values())))
        per_span.append({"span": [span.start, span.stop, span.group,
                                  span.pos, span.depth],
                         "change_median": med_c, "change_worst": dc[wc],
                         "change_leaf": wc, "mom_median": med_m,
                         "mom_worst": dm[wm], "mom_leaf": wm,
                         "left_out": len(g_norms) - len(keep)})
        steps_compared += len(steps)
        parts |= _parts(span)
    loss_gap, results_compared = 0.0, 0
    for tid, step, loss, path in record.results:
        span = record.spans.get((node_at(path, step), step))
        if span is None:
            missing.append((tid, step))
            continue
        want = reference.evaluate(span.state["params"], eval_rows["images"],
                                  eval_rows["labels"])
        loss_gap = max(loss_gap, abs(loss - want) / abs(want))
        results_compared += 1
        parts.add("eval")
    uncompared = sorted(set(traffic["requires"]) - parts)
    return {"change_gap": change_gap, "mom_gap": mom_gap,
            "root_diff": root_diff, "loss_gap": loss_gap,
            "parts_uncompared": len(uncompared),
            "uncompared": uncompared, "spans": len(chosen),
            "spans_run": len(record.spans), "steps": steps_compared,
            "results": results_compared, "unmatched_spans": len(missing),
            "per_span": per_span}
