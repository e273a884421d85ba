"""Device: model operations of the member-steps run in the traced window
over the window's seconds times the chip's bf16 peak, in %.  Model
operations per sample: :func:`chipbench.shapes.resnet_train_flops`."""


def read(view):
    if view.window_s <= 0 or not view.member_steps:
        return None
    done = view.member_steps * view.batch * view.flops_per_sample
    return 100.0 * done / (view.window_s * view.peak["bf16_flops"])
