"""The program's spans in a trace: the reductions on small hand-made
traces, and one study of the cell's rehearsal traced on the CPU."""

import os
import sys

import jax
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import harness, program_trace as pt  # noqa: E402
from repro.utils.spans import SPANS  # noqa: E402

DEV = "/device:TPU:0"
MAIN, OTHER = "/host:CPU#0", "/host:CPU#1"


def _span(name, a, b, thread=MAIN):
    return (name, a, b, thread, {})


@pytest.fixture
def small():
    """A 1000 ns window on the main thread: a round [0, 400) holding a
    unit [100, 400), which holds a launch [100, 150) and a feed [200,
    250); a tuner callback [500, 600); nothing from 600 on.  The writer
    thread commits over [300, 900).  The device runs [120, 180) and
    [420, 480)."""
    return pt.ProgramTrace(
        devices={DEV: [("%fusion.1", 120, 180), ("%fusion.2", 420, 480)]},
        host=[("chipbench.window", 0, 1000)],
        program=[_span("hippo.dispatch.round", 0, 400),
                 _span("hippo.dispatch.unit", 100, 400),
                 _span("hippo.trainer.launch", 100, 150),
                 _span("hippo.trainer.feed", 200, 250),
                 _span("hippo.tuner.on_result", 500, 600),
                 _span("hippo.ckpt.write", 300, 900, OTHER)],
        harness=[_span("chipbench.window", 0, 1000),
                 _span("chipbench.evaluate", 640, 700)],
        main=MAIN)


def test_self_seconds_leaves_out_children_on_the_same_thread(small):
    win = (0, 1000)
    # 400 of round, less launch and feed (100); the writer thread's commit
    # overlaps the round but is not its child
    assert small.self_seconds(win, ["hippo.dispatch.round"],
                              pt.ROUND_CHILDREN) == pytest.approx(300e-9)
    assert small.self_seconds(win, ["hippo.dispatch.round"], ()) == \
        pytest.approx(400e-9)
    # clipped to the window
    assert small.self_seconds((0, 120), ["hippo.dispatch.round"],
                              pt.ROUND_CHILDREN) == pytest.approx(100e-9)
    assert small.self_seconds(win, ["hippo.no.such"], ()) == 0.0


def test_idle_by_span_takes_the_innermost_main_thread_span(small):
    out = small.idle_by_span((0, 1000))
    # idle: [0,120) [180,420) [480,1000) = 120 + 240 + 520 ns
    assert out["idle_s"] == pytest.approx(880e-9)
    by = out["by_span"]
    assert by["hippo.dispatch.round"] == pytest.approx(100e-9)   # [0,100)
    assert by["hippo.trainer.launch"] == pytest.approx(20e-9)    # [100,120)
    # [180,200) + [250,400) inside the unit, [200,250) in the feed
    assert by["hippo.dispatch.unit"] == pytest.approx(170e-9)
    assert by["hippo.trainer.feed"] == pytest.approx(50e-9)
    assert by["hippo.tuner.on_result"] == pytest.approx(100e-9)
    # [400,420) [480,500) [600,1000): the writer thread's commit, open over
    # part of it, claims none
    assert by[pt.OUTSIDE] == pytest.approx(440e-9)
    assert "hippo.ckpt.write" not in by
    assert sum(by.values()) == pytest.approx(out["idle_s"])
    assert out["covered"] == pytest.approx(1 - 440 / 880)
    # what no program span covers, by the benchmark's innermost span
    assert out["outside"] == {"chipbench.window": pytest.approx(380e-9),
                              "chipbench.evaluate": pytest.approx(60e-9)}


def test_idle_by_span_of_a_device_that_never_ran(small):
    small.devices = {}
    out = small.idle_by_span((0, 1000))
    assert out["idle_s"] == pytest.approx(1000e-9)
    assert sum(out["by_span"].values()) == pytest.approx(1000e-9)


def test_readings_and_their_none_cases(small):
    win = (0, 1000)
    counters = {"rounds": 2, "result_wait_seconds": 1.0,
                "result_run_seconds": 3.0, "results_timed": 5}
    r = pt.readings(small, win, counters, member_steps=4)
    assert r["dispatch_ms_per_round"] == pytest.approx(300e-9 * 1e3 / 2)
    # launch and feed: 100 ns over 4 member-steps
    assert r["host_feed_ms_per_member_step"] == pytest.approx(
        100e-9 * 1e3 / 4)
    assert r["result_wait_share"] == pytest.approx(0.25)
    # nothing to read: no round, no member-step, no timed result, or a
    # program without these spans and counters (the parent's)
    none = pt.readings(small, win, {"rounds": 0, "results_timed": 0}, 0)
    assert none == {"dispatch_ms_per_round": None,
                    "host_feed_ms_per_member_step": None,
                    "result_wait_share": None}
    bare = pt.ProgramTrace(host=[("chipbench.window", 0, 1000)])
    assert set(pt.readings(bare, win, {}, 4).values()) == {None}


def test_counts_and_seconds_by_name(small):
    win = (0, 1000)
    assert small.span_counts(win)["hippo.dispatch.unit"] == 1
    assert sum(small.span_counts((0, 150)).values()) == 3
    assert small.seconds_by_name(win)["hippo.ckpt.write"] == \
        pytest.approx(600e-9)


def _nested_in(inner, outer):
    return [s for s in inner
            if not any(o[3] == s[3] and o[1] <= s[1] and s[2] <= o[2]
                       for o in outer)]


def test_a_rehearsal_study_traced_on_the_cpu(tmp_path):
    """One study of the cell's rehearsal under the profiler: every program
    span appears, nested as the layers call each other; the device's
    modules carry the executables' names; and the program's timing of
    each result agrees with the tuner's."""
    cell = harness.rehearsal_cell("wrn16-8.sha-paper")
    bench = harness._Bench(cell, 2147483701, None, harness.SpanTrainer)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        tuner, stats, _ = bench.study(0)
    jax.profiler.stop_trace()
    harness._join_writers()
    tr = pt.load(str(tmp_path))
    win = tr.span("chipbench.window")

    names = {s[0] for s in tr.program}
    assert names == set(SPANS), set(SPANS) ^ names
    by = {n: [s for s in tr.program if s[0] == n] for n in names}
    assert {s[3] for s in by["hippo.ckpt.write"]} != {tr.main}
    trainer = [s for s in tr.program if s[0].startswith("hippo.trainer.")]
    assert not _nested_in(trainer, by["hippo.dispatch.unit"])
    assert not _nested_in(by["hippo.dispatch.unit"],
                          by["hippo.dispatch.round"])
    assert not _nested_in(by["hippo.dispatch.round"],
                          by["hippo.engine.step"])
    assert not _nested_in(by["hippo.engine.step"], by["hippo.service.step"])
    unit = by["hippo.dispatch.unit"][0][4]
    assert set(unit) == {"width", "depth", "steps"}
    assert {m[0] for m in tr.modules} & {"jit_hippo_chunk",
                                         "jit_hippo_group"}
    assert "jit_hippo_eval" in {m[0] for m in tr.modules}

    c = pt.summed([stats])
    assert c["results_timed"] == len(tuner.results)
    program = c["result_wait_seconds"] + c["result_run_seconds"]
    users = sum(r[5] - r[4] for r in tuner.results)
    assert program == pytest.approx(users, rel=0.05)
    r = pt.readings(tr, win, c, int(c["steps_run"]))
    assert all(v is not None and v > 0 for v in r.values()), r
    assert r["result_wait_share"] < 1.0
