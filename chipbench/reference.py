"""The plain reference: a CIFAR ResNet trained by SGD with momentum, in
straightforward ``jax.numpy``, written without the program's code.

Everything a cell's check needs to recompute a stage lives here:

* the model (He et al. 2016, arXiv:1512.03385, section 4.2, with the
  program's stated departures: channel RMS-norm in place of batch norm
  and 1x1 projection shortcuts where the shape changes), its seeded
  initialisation and its loss;
* the update rule the configuration states: ``m <- mu*m + g`` and
  ``p <- p - lr*(m + wd*p)``, weight decay outside the buffer and the
  learning rate applied at each step;
* the hyper-parameter functions the traffic files name, evaluated at a
  step;
* the data: the CIFAR-shaped synthetic rows made from a seed, and which
  rows a step reads.

The reference computes in float32 under ``highest`` matmul precision.
``dtype=jnp.bfloat16`` gives the control: the same arithmetic with
parameters, momentum, activations and inputs in bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["cifar_rows", "step_rows", "hp_value", "init_params",
           "momentum_update", "Reference"]


# --------------------------------------------------------------------- data
def cifar_rows(n: int, seed: int) -> Dict[str, np.ndarray]:
    """CIFAR-shaped rows (32x32x3, 10 classes) from ``seed``: one Gaussian
    prototype per class in an 8-dimensional space, projected to pixels,
    plus pixel noise."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    protos = rng.normal(0, 1.0, size=(10, 8)).astype(np.float32)
    proj = rng.normal(0, 1.0, size=(8, 32 * 32 * 3)).astype(np.float32) / 8.0
    x = protos[labels] @ proj + rng.normal(0, 0.5, size=(n, 32 * 32 * 3))
    return {"images": x.reshape(n, 32, 32, 3).astype(np.float32),
            "labels": labels}


def step_rows(n: int, batch: int, shuffle_seed: int, step: int) -> np.ndarray:
    """Indices of the rows global step ``step`` trains on: each epoch is a
    fresh permutation drawn from ``(shuffle_seed, epoch)``, walked in
    whole batches, the ragged tail dropped."""
    per_epoch = n // batch
    epoch, pos = divmod(step, per_epoch)
    perm = np.random.default_rng((shuffle_seed, epoch)).permutation(n)
    return perm[pos * batch:(pos + 1) * batch]


# ------------------------------------------------------ hyper-parameters
def hp_value(fn: Dict[str, Any], step: int) -> float:
    """Value at ``step`` of a hyper-parameter function given as a traffic
    file writes it (``{"kind": ..., ...}``)."""
    kind = fn["kind"]
    if kind == "constant":
        return float(fn["value"])
    if kind == "multistep":
        i = sum(1 for m in fn["milestones"] if step >= m)
        return float(fn["values"][i])
    if kind == "warmup":
        d = fn["steps"]
        if step < d:
            return fn["target"] * step / d
        return hp_value(fn["then"], step - d)
    if kind == "exponential":
        return fn["base"] * fn["gamma"] ** step
    if kind == "cosine_restarts":
        t = step % fn["period"]
        return 0.5 * fn["base"] * (1 + math.cos(math.pi * t / fn["period"]))
    if kind == "cyclic":
        up = fn["up"]
        t = step % (2 * up)
        f = t / up if t < up else 1.0 - (t - up) / up
        return fn["low"] + (fn["high"] - fn["low"]) * f
    raise ValueError(f"unknown hyper-parameter function {kind!r}")


# -------------------------------------------------------------------- model
def _block_layout(n: int, width: int):
    """(stage, block, in channels, out channels, stride) of every block."""
    out, cin = [], width
    for s, c in enumerate((width, 2 * width, 4 * width)):
        for b in range(n):
            out.append((s, b, cin, c, 2 if (s > 0 and b == 0) else 1))
            cin = c
    return out


def init_params(seed: int, n: int, width: int, classes: int = 10) -> Dict:
    """He-normal (truncated at 2 sigma) convolutions, unit norm gains, a
    1/sqrt(fan-in) head and a zero head bias, drawn from one key split
    into 6n+2 parts: the stem, one per block in order (split again into
    its two convolutions and projection), then the head."""
    def conv(key, k, cin, cout):
        w = jax.random.truncated_normal(key, -2, 2, (k, k, cin, cout))
        return w * (2.0 / (k * k * cin)) ** 0.5

    keys = jax.random.split(jax.random.PRNGKey(seed), 6 * n + 2)
    p: Dict[str, Any] = {"stem": conv(keys[0], 3, 3, width),
                         "stem_g": jnp.ones((width,))}
    stages: List[List[Dict]] = [[], [], []]
    for i, (s, _, cin, c, stride) in enumerate(_block_layout(n, width)):
        k1, k2, k3 = jax.random.split(keys[1 + i], 3)
        blk = {"c1": conv(k1, 3, cin, c), "g1": jnp.ones((c,)),
               "c2": conv(k2, 3, c, c), "g2": jnp.ones((c,))}
        if stride != 1 or cin != c:
            blk["proj"] = conv(k3, 1, cin, c)
        stages[s].append(blk)
    p["stages"] = stages
    top = 4 * width
    p["head"] = jax.random.truncated_normal(
        keys[1 + 3 * n], -2, 2, (top, classes)) * top ** -0.5
    p["head_b"] = jnp.zeros((classes,))
    return p


def _conv(x, w, stride=1):
    """``SAME``-padded convolution as one matrix product of image patches
    (TPU compilers take minutes over float32 convolutions at ``highest``
    precision, and seconds over the same products)."""
    k, cin = w.shape[0], x.shape[-1]
    _, h, wd, _ = x.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + k - h, 0)
    pw = max((wo - 1) * stride + k - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    cols = [xp[:, i:i + stride * (ho - 1) + 1:stride,
               j:j + stride * (wo - 1) + 1:stride, :]
            for i in range(k) for j in range(k)]
    return jnp.concatenate(cols, -1) @ w.reshape(k * k * cin, -1)


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def _block(x, blk, stride):
    h = jax.nn.relu(_rms(_conv(x, blk["c1"], stride), blk["g1"]))
    h = _rms(_conv(h, blk["c2"]), blk["g2"])
    short = _conv(x, blk["proj"], stride) if "proj" in blk else x
    return jax.nn.relu(short + h)


def forward(p, images):
    """The blocks after the first of each stage share their shapes and run
    as one ``lax.scan``, so the program holds one copy of their body."""
    x = jax.nn.relu(_rms(_conv(images, p["stem"]), p["stem_g"]))
    for s, blocks in enumerate(p["stages"]):
        x = _block(x, blocks[0], 2 if s > 0 else 1)
        if len(blocks) > 1:
            rest = jax.tree.map(lambda *b: jnp.stack(b), *blocks[1:])
            x, _ = jax.lax.scan(lambda h, blk: (_block(h, blk, 1), None),
                                x, rest)
    return jnp.mean(x, axis=(1, 2)) @ p["head"] + p["head_b"]


def loss(p, images, labels):
    logp = jax.nn.log_softmax(forward(p, images).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def momentum_update(p, m, g, lr, mu, wd):
    """The configuration's rule for one leaf: weight decay outside the
    buffer, the learning rate applied at each step."""
    m = mu * m + g
    return p - lr * (m + wd * p), m


# ------------------------------------------------------------------ runner
class Reference:
    """Trains and evaluates from given states, one jitted step at a time.

    ``dtype`` float32 (the reference, ``highest`` precision) or bfloat16
    (the control)."""

    def __init__(self, dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)
        self.precision = ("highest" if self.dtype == jnp.float32
                          else "default")
        self._step = jax.jit(self._step_fn)
        self._eval = jax.jit(self._eval_fn)

    def cast(self, tree):
        return jax.tree.map(lambda x: jnp.asarray(x, self.dtype), tree)

    def _step_fn(self, p, m, images, labels, lr, mu, wd):
        with jax.default_matmul_precision(self.precision):
            g = jax.grad(loss)(p, images.astype(self.dtype), labels)
        out = jax.tree.map(
            lambda p_, m_, g_: momentum_update(p_, m_, g_, lr.astype(
                self.dtype), mu.astype(self.dtype), wd.astype(self.dtype)),
            p, m, g)
        new_p = jax.tree.map(lambda _, o: o[0], p, out)
        new_m = jax.tree.map(lambda _, o: o[1], p, out)
        return new_p, new_m, g

    def _eval_fn(self, p, images, labels):
        with jax.default_matmul_precision(self.precision):
            return loss(p, images.astype(self.dtype), labels)

    def train(self, params, mom, batches: Sequence, hps: Sequence,
              first_grad: bool = False):
        """``batches[i]`` = (images, labels); ``hps[i]`` = (lr, mu, wd).
        Returns the float32 (params, momentum) after the steps, and with
        ``first_grad`` the gradient of the first step."""
        p, m = self.cast(params), self.cast(mom)
        g0 = None
        for (images, labels), (lr, mu, wd) in zip(batches, hps):
            p, m, g = self._step(p, m, images, labels, jnp.float32(lr),
                                 jnp.float32(mu), jnp.float32(wd))
            g0 = g if g0 is None else g0
        to32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        if first_grad:
            return to32(p), to32(m), to32(g0)
        return to32(p), to32(m)

    def evaluate(self, params, images, labels) -> float:
        return float(self._eval(self.cast(params), images, labels))
