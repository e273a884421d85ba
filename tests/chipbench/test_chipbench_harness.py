"""The harness: it refuses to report without a TPU, needs the program,
and finds every cell, traffic mix and per-layer metric by its name, so a
cell can be added from data files alone."""

import importlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from chipbench import harness  # noqa: E402

BENCH = json.loads(Path(ROOT, "BENCHMARK.json").read_text())


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         BENCH["workloads"][0]["name"], "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    r = _run(ROOT)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_every_metric_has_a_reader_that_finds_nothing_in_an_empty_view():
    view = harness.LayerView(
        counters={"stages_run": 0, "batched_stages": 0, "ckpt_saves": 0,
                  "ckpt_save_seconds": 0.0, "steps_run": 0},
        member_steps=0, trial_steps=0, batch=128, params=1, flops_per_sample=1,
        busy_s=0.0, window_s=0.0, span_s=0.0, opt_kernel_s=None,
        peak={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0}, breakdown={})
    for m in BENCH["per_layer"]:
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        assert mod.read(view) is None, m["name"]


def test_a_cell_added_from_data_files_alone(tmp_path):
    """A new traffic file, a limits file and entries in BENCHMARK.json are
    all a new cell needs; the harness runs it unchanged."""
    bench = json.loads(json.dumps(BENCH))
    traffic = json.loads(
        Path(ROOT, "chipbench", "traffic", "sha-paper.json").read_text())
    traffic.update(tuner=dict(traffic["tuner"], eta=2, rungs=[8, 16, 32]),
                   requires=["root", "resumed", "group", "eval"])
    (tmp_path / "chipbench" / "traffic").mkdir(parents=True)
    (tmp_path / "chipbench" / "limits").mkdir()
    (tmp_path / "chipbench" / "configs").mkdir()
    (tmp_path / "chipbench" / "traffic" / "sha-eta2.json").write_text(
        json.dumps(traffic))
    shutil.copy(os.path.join(ROOT, "chipbench", "limits",
                             "wrn16-8.sha-paper.json"),
                tmp_path / "chipbench" / "limits" / "wrn16-8.sha-eta2.json")
    shutil.copy(os.path.join(ROOT, "chipbench", "configs", "wrn16-8.json"),
                tmp_path / "chipbench" / "configs")
    bench["workloads"].append({"name": "wrn16-8.sha-eta2",
                               "config": "wrn16-8", "traffic": "sha-eta2",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.rehearsal_cell("wrn16-8.sha-eta2", root=tmp_path)
    assert cell.traffic["tuner"]["eta"] == 2
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in BENCH["end_to_end"]]
    out = harness.run_cell(cell, 31, 6.0, False, time.perf_counter())
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], (name, c)
    assert out["correct"]
    assert out["results"] >= 11            # 8 + 4 + 2 + 1 results a study
    assert set(out["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
