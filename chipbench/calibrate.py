#!/usr/bin/env python3
"""The readings each limit of a cell is set from, on the chip, in one
process.

    python3 chipbench/calibrate.py --workload wrn16-8.sha-paper \\
        --seeds 201-212 --faults 3 --seconds 6

One set-up (the first seed's), then for each seed new data, a short
window and the check give the program's readings of every number
compared.  For the
first ``--faults`` seeds the same compared study is also judged with, in
the program's place:

* ``control``: the reference in bfloat16 (the precision below the
  configuration's float32);
* ``half_batch``: the reference trained on half of each batch, the mean
  taken over the rest;
* ``unchanged``: every stage returning the state it was given;
* ``altered``: every loss the tuner received 2% off.

Prints one JSON line per seed, then one with the largest program reading
and the smallest control and fault readings of each number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

NUMBERS = ("change_gap", "mom_gap", "root_diff", "loss_gap")


def _seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def fault_readings(cell, seed, record):
    """The control's and the planted faults' readings on ``record``."""
    import jax.numpy as jnp

    from chipbench import check, harness, reference as ref

    rows, eval_rows = harness.cell_rows(cell, seed)
    f32, bf16 = ref.Reference(), ref.Reference(jnp.bfloat16)

    def half(span, start, batches, hps):
        cut = [(x[:len(x) // 2], y[:len(y) // 2]) for x, y in batches]
        return f32.train(*start, cut, hps)

    cands = {
        "control": lambda span, start, b, h: bf16.train(*start, b, h),
        "half_batch": half,
        "unchanged": lambda span, start, b, h: start,
    }
    out = {}
    for name, cand in cands.items():
        nums = check.compare_study(record, cell.config, rows, eval_rows,
                                   cell.traffic, f32, candidate=cand)
        out[name] = {k: nums[k] for k in NUMBERS}
    altered = check.StudyRecord(record.init_seed, record.shuffle_seed,
                                record.spans,
                                [(t, s, loss * 1.02, p)
                                 for t, s, loss, p in record.results],
                                record.fns, record.done)
    nums = check.compare_study(altered, cell.config, rows, eval_rows,
                               cell.traffic, f32)
    out["altered"] = {k: nums[k] for k in NUMBERS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 201-212")
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    from chipbench.run import use_checkout_cache
    use_checkout_cache()
    import jax

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    program = {k: [] for k in NUMBERS}
    worst = {}
    seeds = _seeds(args.seeds)
    bench = harness.set_up(cell, seeds[0], T_START)
    for i, seed in enumerate(seeds):
        bench.reseed(seed)
        out = harness.measure(bench, args.seconds, False)
        row = {"seed": seed, "correct": out["correct"],
               "program": {k: out["compared"].get(k) for k in NUMBERS},
               "parts_uncompared": out["compared"].get("parts_uncompared"),
               "window_compiles": out["checks"]["window_compiles"]["value"],
               "results": out["results"], "setup_s": out["setup_s"],
               "setup_phases": out["setup_phases"],
               "reference_s": out["reference_s"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        row["per_span"] = out["compared"].get("per_span")
        for k in NUMBERS:
            program[k].append(row["program"][k])
        if i < args.faults and out["record"] is not None:
            t1 = time.perf_counter()
            row["faults"] = fault_readings(cell, seed, out["record"])
            row["faults_s"] = time.perf_counter() - t1
            for f, nums in row["faults"].items():
                for k, v in nums.items():
                    worst.setdefault(f, {}).setdefault(k, v)
                    worst[f][k] = min(worst[f][k], v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_max": {k: max(v) for k, v in program.items()},
                      "faults_min": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
