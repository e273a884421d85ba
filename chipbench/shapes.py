"""Operations and bytes of the work a cell runs, from its shapes alone.

* :func:`resnet_train_flops`: model operations per training sample of
  the CIFAR ResNet family (6n+2 layers, widths w/2w/4w): 2 x the
  multiply-adds of every convolution and the head, x 3 for the forward
  and the backward pass.  A convolution's multiply-adds are those of its
  taps that fall inside the image (``SAME`` padding adds zeros, not
  work).  Norms, activations and the optimizer are not counted.
* :func:`momentum_update_bytes`: what the momentum update must move for
  one member-step: reads of parameters, gradients and momentum and
  writes of parameters and momentum, float32, without padding.
"""

from __future__ import annotations

__all__ = ["resnet_params", "resnet_forward_macs", "resnet_train_flops",
           "momentum_update_bytes", "OPT_KERNEL_MATCH"]

# The fused momentum kernel's operations in a TPU trace, by their HLO text:
# a Mosaic custom call over three (members, rows, 128) float32 arrays
# (parameters, gradients, momentum) and three per-member scalars
# (learning rate, weight decay, momentum).
_LANES = r"f32\[\d+,\d+,128\]\S* %\S+, "
OPT_KERNEL_MATCH = (r"custom-call\(" + _LANES * 3
                    + r"f32\[\d+\]\S* %\S+, " * 2 + r"f32\[\d+\]\S* %\S+\), "
                    + r'custom_call_target="tpu_custom_call"')


def _taps(side: int, k: int, stride: int) -> int:
    """Taps of a ``k``-wide window, summed over the outputs of one axis of
    a ``SAME``-padded convolution, that land inside the input."""
    out = -(-side // stride)
    lo = max((out - 1) * stride + k - side, 0) // 2
    return sum(1 for i in range(out) for t in range(k)
               if 0 <= i * stride + t - lo < side)


def _conv_macs(side: int, k: int, stride: int, cin: int, cout: int) -> int:
    return _taps(side, k, stride) ** 2 * cin * cout


def _layout(n: int, width: int):
    """(in channels, out channels, stride, output side) of every block."""
    out, cin, side = [], width, 32
    for s, c in enumerate((width, 2 * width, 4 * width)):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            side //= stride
            out.append((cin, c, stride, side))
            cin = c
    return out


def resnet_params(n: int, width: int, classes: int = 10) -> int:
    total = 3 * 3 * 3 * width + width
    for cin, c, stride, _ in _layout(n, width):
        total += 9 * cin * c + c + 9 * c * c + c
        if stride != 1 or cin != c:
            total += cin * c
    return total + 4 * width * classes + classes


def resnet_forward_macs(n: int, width: int, classes: int = 10) -> int:
    """Multiply-adds of one sample's forward pass (32x32x3 input)."""
    macs = _conv_macs(32, 3, 1, 3, width)                 # stem
    for cin, c, stride, side in _layout(n, width):
        macs += (_conv_macs(side * stride, 3, stride, cin, c)
                 + _conv_macs(side, 3, 1, c, c))
        if stride != 1 or cin != c:
            macs += _conv_macs(side * stride, 1, stride, cin, c)
    return macs + 4 * width * classes


def resnet_train_flops(n: int, width: int, classes: int = 10) -> float:
    return 3.0 * 2.0 * resnet_forward_macs(n, width, classes)


def momentum_update_bytes(params: int) -> int:
    return 5 * 4 * params
