"""The operation and byte counts kept with the benchmark against the
compiler's count of the same work at a small size."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import shapes  # noqa: E402
from repro.models.resnet import ResNet  # noqa: E402
from repro.train.optimizer import apply_update  # noqa: E402


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    return c[0] if isinstance(c, list) else c


@pytest.mark.parametrize("n, width", [(1, 8), (9, 16), (2, 128)])
def test_param_count_matches_the_model(n, width):
    shapes_ = jax.eval_shape(
        lambda: ResNet(n=n, width=width).init(jax.random.PRNGKey(0)))
    assert shapes.resnet_params(n, width) == sum(
        x.size for x in jax.tree.leaves(shapes_))


@pytest.mark.parametrize("n, width", [(1, 8), (2, 16)])
def test_train_flops_against_the_compiler(n, width):
    """The compiler counts the program's convolutions and head, plus norms
    and activations the shape function leaves out: a few % more, never
    less."""
    batch = 4
    model = ResNet(n=n, width=width)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    fwd = _cost(lambda p, x: model.forward(p, {"images": x}), p, x)["flops"]
    train = _cost(jax.grad(lambda p, x, y: model.loss(
        p, {"images": x, "labels": y})[0]), p, x, y)["flops"]
    mine = shapes.resnet_train_flops(n, width) * batch
    assert 1.0 <= fwd / (mine / 3) <= 1.08
    assert 1.0 <= train / mine <= 1.08


def test_momentum_update_bytes_against_the_compiler():
    """The least the update must move is 5 float32 arrays (reads of p, g
    and m, writes of p and m); XLA:CPU's fusions read the new momentum
    back once more: 6 arrays, never fewer than 5."""
    params = ResNet(n=1, width=8).init(jax.random.PRNGKey(0))
    n = shapes.resnet_params(1, 8)
    hp = {"lr": jnp.float32(0.1), "momentum": jnp.float32(0.9),
          "wd": jnp.float32(1e-4)}
    upd = lambda p, g, m: apply_update("momentum", p, g, {"m": m}, hp,
                                       jnp.int32(0))
    got = _cost(upd, params, params, params)["bytes accessed"]
    need = shapes.momentum_update_bytes(n)
    assert need == 20 * n
    assert need <= got <= need * 6 / 5 + 1024   # + the scalars
