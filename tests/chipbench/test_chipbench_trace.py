"""The reduction from a profiler trace to the per-layer metrics, on a
small hand-made trace and on one recorded here."""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import harness, shapes, trace  # noqa: E402
from chipbench.metrics import (control_plane_share,  # noqa: E402
                               device_idle_share, device_ms_per_member_step,
                               mfu, opt_update_roofline)

DEV = "/device:TPU:0"
# the fused momentum kernel as a TPU trace names it (HLO text, cut)
KERNEL = (
    "%closed_call.1000 = (f32[1,1,128]{2,1,0:T(1,128)S(1)}, "
    "f32[1,1,128]{2,1,0:T(1,128)S(1)}) custom-call("
    "f32[1,1,128]{2,1,0:T(1,128)S(1)} %copy-done.814, "
    "f32[1,1,128]{2,1,0:T(1,128)S(1)} %pad_bitcast_fusion.460, "
    "f32[1,1,128]{2,1,0:T(1,128)S(1)} %copy-done.809, "
    "f32[1]{0:T(128)} %dynamic_slice.30, f32[1]{0:T(128)} %bitcast.2763, "
    "f32[1]{0:T(128)} %dynamic_slice.31), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints=')


@pytest.fixture
def small():
    """A 1000 ns window: ops busy on [0, 150) and [300, 400), the
    optimizer kernel 100 ns of it; a loop holds the first two, [0, 200);
    host spans cover [0, 200) and [250, 450)."""
    return trace.Trace(
        devices={DEV: [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 100),
                       (KERNEL, 50, 150),
                       ("convolution.7", 300, 400),
                       ("%while.2 = (s32[]) while((s32[]) %t)", 0, 200)]},
        host=[("chipbench.window", 0, 1000), ("chipbench.run_chain", 0, 200),
              ("chipbench.evaluate", 250, 450)])


def test_busy_union_idle_and_kernel_time(small):
    win = small.span("chipbench.window")
    assert win == (0, 1000)
    assert small.busy_seconds(win) == pytest.approx(300e-9)
    assert small.op_seconds(win, shapes.OPT_KERNEL_MATCH) == \
        pytest.approx(100e-9)
    assert small.op_seconds(win, "no_such_op") is None
    assert small.span_cover_seconds(
        win, "chipbench.", exclude=("chipbench.window",)) == \
        pytest.approx(400e-9)
    assert small.idle_gaps(win) == [(200, 300), (400, 1000)]
    # a window cut inside the first op clips it
    assert small.busy_seconds((50, 1000)) == pytest.approx(250e-9)


def test_breakdown_names_gaps_by_host_span(small):
    bd = small.breakdown(small.span("chipbench.window"),
                         exclude=("chipbench.window",))
    assert {n for n, _ in bd["device_ops"]} == {
        "%fusion.1", "%closed_call.1000", "convolution.7"}     # no loop
    assert sum(s for _, s in bd["device_ops"]) == pytest.approx(300e-9)
    assert bd["idle_gaps"][0] == ["chipbench.evaluate", pytest.approx(600e-9)]
    assert bd["idle_gaps"][1][1] == pytest.approx(100e-9)


def test_layer_metrics_from_small_trace(small):
    win = small.span("chipbench.window")
    view = harness.LayerView(
        counters={}, member_steps=2, trial_steps=4, batch=128, params=1000,
        flops_per_sample=1e6, busy_s=small.busy_seconds(win),
        window_s=(win[1] - win[0]) * 1e-9,
        span_s=small.span_cover_seconds(win, "chipbench.",
                                        ("chipbench.window",)),
        opt_kernel_s=small.op_seconds(win, shapes.OPT_KERNEL_MATCH),
        peak={"hbm_bytes_per_s": 1e12, "bf16_flops": 1e15}, breakdown={})
    assert device_idle_share.read(view) == pytest.approx(0.7)
    assert control_plane_share.read(view) == pytest.approx(0.6)
    assert device_ms_per_member_step.read(view) == pytest.approx(150e-6)
    # 2 member-steps x 20 B x 1000 params at 1e12 B/s over 100 ns
    assert opt_update_roofline.read(view) == pytest.approx(40.0)
    assert mfu.read(view) == pytest.approx(
        100 * 2 * 128 * 1e6 / (1e-6 * 1e15))
    view.opt_kernel_s = None
    assert opt_update_roofline.read(view) is None


def test_recorded_trace_keeps_host_spans(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.run_chain"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))
    tr = trace.load(str(tmp_path))
    lo, hi = tr.span("chipbench.window")
    a, b = tr.span("chipbench.run_chain")
    assert lo <= a < b <= hi
    # the CPU has no device plane: nothing to read, never a zero share
    assert tr.busy_seconds((lo, hi)) == 0.0
    assert tr.op_seconds((lo, hi), shapes.OPT_KERNEL_MATCH) is None
