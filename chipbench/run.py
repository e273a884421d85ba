#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload wrn16-8.sha-paper --seed 7 \\
        --seconds 30 --trace 0

Runs the cell named in ``BENCHMARK.json`` on the chip this process
finds: set-up (data and weights from ``--seed``, every executable the
cell's traffic can use warmed up, from JAX's compilation cache in
``.jax_cache/`` at the checkout's root where it is warm), then studies
back to back for ``--seconds``, then the check against the plain
reference.  ``--trace 1`` traces the window and reports the per-layer
metrics in place of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``: each number compared with its
limit, which also end standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
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


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache in ``.jax_cache/`` at the
    checkout's root, every program in it (set before JAX is imported, so
    that it holds whatever the environment says)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_cache()
    import jax

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    line = {"correct": out["correct"], "attempted": out["results"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": dict(harness.device_info(cell.chips),
                           memory_peak_bytes=out["memory_peak_bytes"])}
    if args.trace:
        line["device"]["busy_s"] = out["busy_s"]
        line["device"]["window_s"] = out["trace_window_s"]
        line["breakdown"] = out["breakdown"]
    line["diagnostics"] = {k: out[k] for k in (
        "window_s", "studies", "studies_done", "results", "member_steps",
        "trial_steps", "reference_s", "setup_phases")}
    line["diagnostics"]["compared"] = out["compared"]
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
