"""Kernels: the fused momentum update's share of its roofline, in %.

The update moves reads of parameters, gradients and momentum and writes
of parameters and momentum, float32, per member-step
(:func:`chipbench.shapes.momentum_update_bytes`); the least time the
chip could take for them is those bytes over its HBM bandwidth, and the
share is that over the kernel's summed device time in the trace."""

from chipbench import shapes


def read(view):
    if not view.opt_kernel_s or not view.member_steps:
        return None
    need = shapes.momentum_update_bytes(view.params) * view.member_steps
    return 100.0 * need / view.peak["hbm_bytes_per_s"] / view.opt_kernel_s
