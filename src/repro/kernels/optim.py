"""Fused trial-stacked optimizer-update Pallas kernel.

On the batched-sibling path the data plane vmaps the whole chunk body
over the member axis, so the optimizer update becomes ~4 XLA ops ×
parameter leaves × members.  This kernel fuses one leaf's update across
every member into a single launch: the member-stacked leaf is viewed as
``(M, R, 128)`` lanes, the grid is ``(M, R/BR)``, and the divergent
per-member hyper-parameters (lr, wd, momentum, b1/b2/eps) ride in as
whole ``(M,)`` SMEM operands indexed by the member grid axis — exactly the
"divergent hp values, one compile per group" contract the data plane
already guarantees for the loss.

:func:`fused_apply_update` is a drop-in for
:func:`repro.train.optimizer.apply_update` (same signature, same
update formulas — sgd / momentum / adam / adamw with the same wd
coupling and bias correction, computed in f32 and cast back to the leaf
dtype).  Each per-leaf op carries a ``custom_vmap`` rule that folds the
vmapped member axis into the kernel's member grid axis, so the solo path
(M = 1) and the vmapped sibling-group path share one kernel.  Like the
attention/SSD wrappers in :mod:`repro.kernels.ops`, calls and fallbacks
are counted in ``KERNEL_STATS`` (reason-tagged, warn-once).

No custom_vjp is needed: the optimizer update sits outside
``value_and_grad`` in every chunk body.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kops
from repro.train.optimizer import apply_update

__all__ = ["fused_apply_update"]

_LANE = 128      # f32 lane width: leaves are padded to lane multiples
_MAX_ROWS = 64   # block rows → ≤ 64·128 elements per grid step


def _sgd_kernel(p_ref, g_ref, lr_ref, wd_ref, o_ref):
    i = pl.program_id(0)
    p = p_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    lr = lr_ref[i]
    wd = wd_ref[i]
    o_ref[0] = (p - lr * (g + wd * p)).astype(o_ref.dtype)


def _momentum_kernel(p_ref, g_ref, m_ref, lr_ref, wd_ref, mom_ref,
                     op_ref, om_ref):
    i = pl.program_id(0)
    p = p_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    m = m_ref[0].astype(jnp.float32)
    lr = lr_ref[i]
    wd = wd_ref[i]
    mom = mom_ref[i]
    m2 = mom * m + g
    om_ref[0] = m2.astype(om_ref.dtype)
    op_ref[0] = (p - lr * (m2 + wd * p)).astype(op_ref.dtype)


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, wd_ref, b1_ref,
                 b2_ref, eps_ref, bc1_ref, bc2_ref, op_ref, om_ref, ov_ref,
                 *, decoupled: bool):
    i = pl.program_id(0)
    p = p_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    m = m_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lr = lr_ref[i]
    wd = wd_ref[i]
    b1 = b1_ref[i]
    b2 = b2_ref[i]
    eps = eps_ref[i]
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    om_ref[0] = m2.astype(om_ref.dtype)
    ov_ref[0] = v2.astype(ov_ref.dtype)
    mh = m2 / bc1_ref[i]
    vh = v2 / bc2_ref[i]
    if decoupled:   # adamw
        upd = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    else:           # adam: wd folded into the gradient (L2)
        upd = p - lr * mh / (jnp.sqrt(vh) + eps) - lr * wd * p
    op_ref[0] = upd.astype(op_ref.dtype)


# per optimizer: (kernel, #array operands, #scalar operands, #outputs)
_SPEC = {
    "sgd": (_sgd_kernel, 2, 2, 1),
    "momentum": (_momentum_kernel, 3, 3, 2),
    "adam": (functools.partial(_adam_kernel, decoupled=False), 4, 7, 3),
    "adamw": (functools.partial(_adam_kernel, decoupled=True), 4, 7, 3),
}


def _stacked_leaf_update(name: str, *args, interpret: Optional[bool] = None):
    """One member-stacked leaf update: ``args`` are ``narr`` arrays of
    shape (M, *leaf) followed by ``nscal`` per-member (M,) f32 scalars."""
    kernel, narr, nscal, nout = _SPEC[name]
    arrs, scals = args[:narr], args[narr:]
    assert len(scals) == nscal, (name, len(scals))
    M = arrs[0].shape[0]
    shape = arrs[0].shape[1:]
    L = int(np.prod(shape, dtype=np.int64)) if shape else 1

    R = -(-L // _LANE)
    br = min(R, _MAX_ROWS)
    Rp = -(-R // br) * br
    pad = Rp * _LANE - L

    def lanes(a):
        flat = a.reshape(M, L)
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat.reshape(M, Rp, _LANE)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    blk = pl.BlockSpec((1, br, _LANE), lambda i, j: (i, j, 0))
    # per-member scalars stay whole in SMEM, read at the member grid index:
    # a (1, 1) VMEM block of an (M, 1) array is no legal TPU tile for M > 1
    sblk = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((M, Rp, _LANE), arrs[i].dtype)
                 for i in range(nout)]
    outs = pl.pallas_call(
        kernel,
        grid=(M, Rp // br),
        in_specs=[blk] * narr + [sblk] * nscal,
        out_specs=[blk] * nout if nout > 1 else blk,
        out_shape=out_shape if nout > 1 else out_shape[0],
        interpret=interpret,
    )(*[lanes(a) for a in arrs],
      *[s.reshape(M).astype(jnp.float32) for s in scals])

    def unlanes(o):
        flat = o.reshape(M, Rp * _LANE)
        if pad:
            flat = flat[:, :L]
        return flat.reshape((M,) + shape)

    if nout == 1:
        return unlanes(outs)
    return tuple(unlanes(o) for o in outs)


@functools.lru_cache(maxsize=None)
def _leaf_op(name: str):
    """Single-member leaf op with a member-folding batching rule: vmap
    over sibling-group members maps onto the kernel's member grid axis."""
    def run(args):
        if name in ("adam", "adamw"):
            # precompute the bias corrections on (M,) vectors in XLA —
            # args: p, g, m, v, lr, wd, b1, b2, eps, t
            *rest, b1, b2, eps, t = args
            tt = t.astype(jnp.float32) + 1.0
            bc1 = 1.0 - b1 ** tt
            bc2 = 1.0 - b2 ** tt
            args = (*rest, b1, b2, eps, bc1, bc2)
        return _stacked_leaf_update(name, *args)

    @custom_vmap
    def op(*args):
        outs = run(tuple(jnp.asarray(a)[None] for a in args))
        if isinstance(outs, tuple):
            return tuple(o[0] for o in outs)
        return outs[0]

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = tuple(
            a if b else jnp.broadcast_to(jnp.asarray(a),
                                         (axis_size,) + jnp.shape(a))
            for a, b in zip(args, in_batched))
        outs = run(args)
        if isinstance(outs, tuple):
            return outs, tuple(True for _ in outs)
        return outs, True

    return op


def _pick(tree, i: int):
    return jax.tree.map(lambda t: t[i], tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def fused_apply_update(name: str, params: Any, grads: Any,
                       state: Dict[str, Any], hp: Dict[str, jnp.ndarray],
                       step: jnp.ndarray) -> Tuple[Any, Dict[str, Any]]:
    """Drop-in for :func:`repro.train.optimizer.apply_update` running each
    leaf's update as one fused Pallas launch (member-stacked under vmap)."""
    reason = kops.unsupported_reason()
    if reason:
        kops.note_fallback("opt_update", reason)
        return apply_update(name, params, grads, state, hp, step)
    kops.note_call("opt_update")

    f32 = lambda x: jnp.asarray(x, jnp.float32)
    lr = f32(hp["lr"])
    wd = f32(hp.get("wd", 0.0))

    if name == "sgd":
        op = _leaf_op("sgd")
        new = jax.tree.map(lambda p, g: op(p, g, lr, wd), params, grads)
        return new, state

    if name == "momentum":
        mom = f32(hp.get("momentum", 0.9))
        op = _leaf_op("momentum")
        pairs = jax.tree.map(lambda p, g, m: op(p, g, m, lr, wd, mom),
                             params, grads, state["m"])
        return _pick(pairs, 0), {"m": _pick(pairs, 1)}

    if name in ("adam", "adamw"):
        b1 = f32(hp.get("b1", 0.9))
        b2 = f32(hp.get("b2", 0.999))
        eps = f32(hp.get("eps", 1e-8))
        t = f32(step)
        op = _leaf_op(name)
        trips = jax.tree.map(
            lambda p, g, m, v: op(p, g, m, v, lr, wd, b1, b2, eps, t),
            params, grads, state["m"], state["v"])
        return _pick(trips, 0), {"m": _pick(trips, 1), "v": _pick(trips, 2)}

    raise ValueError(name)
