"""The one generator of study traffic: it reads a traffic file's
parameters and makes each study's trials and tuner for the program.

A traffic file (``chipbench/traffic/<name>.json``) holds:

* ``tuner``: ``{"kind": "sha", "eta": ..., "rungs": [...],
  "objective": ..., "mode": ...}``;
* ``batch_size``, ``optimizer``, ``wd``: fixed for every trial;
* ``trials``: ``{"kind": "grid", "lr": [fn, ...], "momentum": [fn,
  ...]}``, the same trials in every study;
* ``eval_rows``: rows of the held-out batch each evaluation reads;
* ``group_widths``: the sibling-group widths the traffic can run, so
  set-up compiles those and no others;
* ``requires``: the parts of the path the check must find in the study
  it compares (``root``, ``resumed``, ``group``, ``chain_mid``, ``eval``);
* ``check_steps``: how many steps of that study's stages the reference
  recomputes, beyond those it needs to cover ``requires``.

A hyper-parameter function ``fn`` is ``{"kind": ...}`` with the fields
:func:`chipbench.reference.hp_value` reads.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from repro.core.hpseq import (Constant, CosineWarmRestarts, Cyclic,
                              Exponential, HpConfig, MultiStep, Warmup)
from repro.core.trial import Trial

__all__ = ["program_fn", "study_trials", "study_seed"]


def study_seed(seed: int, index: int, salt: int = 0) -> int:
    """A 31-bit seed for study ``index`` of a run (``salt`` separates the
    uses of one study's seed)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), salt,
                                 index & 0xFFFFFFFF])
    return int(ss.generate_state(1)[0] >> 1)


def program_fn(fn: Dict[str, Any]):
    """The program's hyper-parameter function for a traffic-file ``fn``."""
    kind = fn["kind"]
    if kind == "constant":
        return Constant(float(fn["value"]))
    if kind == "multistep":
        return MultiStep(fn["values"][0], fn["milestones"],
                         values=fn["values"])
    if kind == "warmup":
        return Warmup(fn["steps"], fn["target"], program_fn(fn["then"]))
    if kind == "exponential":
        return Exponential(fn["base"], fn["gamma"])
    if kind == "cosine_restarts":
        return CosineWarmRestarts(fn["base"], t_0=fn["period"])
    if kind == "cyclic":
        return Cyclic(fn["low"], fn["high"], step_size_up=fn["up"])
    raise ValueError(f"unknown hyper-parameter function {kind!r}")


def study_trials(traffic: Dict[str, Any]
                 ) -> List[Tuple[Trial, Dict[str, Dict]]]:
    """The trials of one study, each with its hyper-parameter functions as
    the traffic file writes them (what the reference evaluates)."""
    spec = traffic["trials"]
    total = traffic["tuner"]["rungs"][-1]
    if spec["kind"] != "grid":
        raise ValueError(f"unknown trial kind {spec['kind']!r}")
    fns = [{"lr": lr, "momentum": mom}
           for lr in spec["lr"] for mom in spec["momentum"]]
    static = {"optimizer": traffic["optimizer"], "wd": traffic["wd"]}
    out = []
    for f in fns:
        cfg = HpConfig({k: program_fn(v) for k, v in f.items()}, static)
        out.append((Trial(cfg, total), f))
    return out
