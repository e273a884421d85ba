"""Pallas TPU kernel for the SSD intra-chunk term (Mamba2 hot spot).

Within a chunk of ``Q`` steps the SSD output is an attention-like product::

    att[i, j] = (C_i · B_j) · exp(cum_i − cum_j) · dt_j     (j ≤ i)
    y[i]      = Σ_j att[i, j] · x_j

— two Q×N and one Q×Q matmul per (batch, chunk, head): exactly the MXU
shape the TPU wants when Q = N = 128 (mamba2-2.7b's configuration).  The
kernel computes one (batch, chunk, head) cell per grid step with all
operands resident in VMEM:

  VMEM working set = Q·N (C) + Q·N (B) + Q (cum) + Q (dt) + Q·P (x)
                   + Q·Q (att) + Q·P (y) ≈ 0.3 MB at Q=N=P=128 — far under
  the ~16 MB budget, leaving headroom for double-buffered pipelining.

The inter-chunk state hand-off stays in XLA (a ``lax.scan`` of rank-1
updates — bandwidth-bound, nothing for the MXU), mirroring how the paper's
CUDA SSD kernel splits intra/inter work.  Oracle: ``ref.ssd_intra_ref``.

The **backward** kernel (:func:`ssd_intra_bwd_pallas`) walks the same
(B·nc, H) grid.  Per cell it recomputes the forward tile (cb, decay, att)
and derives all five input cotangents; the B/C projections are shared
across heads, so their gradient contribution ``dcb = Σ_h datt_h · decay_h
· dt_h`` accumulates in a (Q, Q) VMEM scratch across the sequential
innermost head axis, and ``dB = dcbᵀC`` / ``dC = dcb·B`` are emitted once
at the last head step (the output block's index_map is constant in ``h``,
the legal TPU revisiting pattern).  The ``dcum → dltT`` suffix-sum (the
cumsum transpose) is O(Q) elementwise and stays in XLA, like the
inter-chunk scan.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_intra_pallas", "ssd_intra_bwd_pallas"]


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, o_ref, *, q: int):
    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)         # (Q,)
    cum = cum_ref[0, 0, 0].astype(jnp.float32)       # (Q,)
    Bm = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)             # (Q, N)

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (Q, Q)
    seg = cum[:, None] - cum[None, :]                            # cum_i - cum_j
    i_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = j_pos <= i_pos
    decay = jnp.where(tril, jnp.exp(seg), 0.0)
    att = cb * decay * dt[None, :]
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())))    # (Q, P)
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_intra_pallas(xr: jnp.ndarray, dtr: jnp.ndarray, ltT: jnp.ndarray,
                     Br: jnp.ndarray, Cr: jnp.ndarray,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Intra-chunk SSD term.

    xr  (B, nc, Q, H, P)  chunked head inputs
    dtr (B, nc, Q, H)     per-step dt
    ltT (B, nc, H, Q)     per-step log-decay (dt·A), head-major
    Br/Cr (B, nc, Q, N)   state in/out projections (shared across heads)
    → y (B, nc, Q, H, P)
    """
    B, nc, Q, H, P = xr.shape
    N = Br.shape[-1]
    cum = jnp.cumsum(ltT, axis=-1)                   # (B, nc, H, Q)

    # head-major layouts so each grid cell reads contiguous blocks
    x_hm = jnp.moveaxis(xr, 3, 2)                    # (B, nc, H, Q, P)
    dt_hm = jnp.moveaxis(dtr, 3, 2)                  # (B, nc, H, Q)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    grid = (B * nc, H)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bc, h: (bc, 0, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bc, h: (bc, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda bc, h: (bc, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nc, H, Q, P), xr.dtype),
        interpret=interpret,
    )(
        x_hm.reshape(B * nc, H, Q, P),
        dt_hm.reshape(B * nc, H, 1, Q),
        cum.reshape(B * nc, H, 1, Q),
        Br.reshape(B * nc, 1, Q, N),
        Cr.reshape(B * nc, 1, Q, N),
    )
    y = out.reshape(B, nc, H, Q, P)
    return jnp.moveaxis(y, 2, 3)                     # (B, nc, Q, H, P)


def _ssd_bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, g_ref,
                    dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                    dcb_scr, *, q: int):
    h = pl.program_id(1)
    nh = pl.num_programs(1)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)         # (Q,)
    cum = cum_ref[0, 0, 0].astype(jnp.float32)       # (Q,)
    Bm = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)             # (Q, N)
    g = g_ref[0, 0].astype(jnp.float32)              # (Q, P)

    # recompute the forward tile
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (Q, Q)
    seg = cum[:, None] - cum[None, :]
    i_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = j_pos <= i_pos
    decay = jnp.where(tril, jnp.exp(seg), 0.0)
    att = cb * decay * dt[None, :]

    # y = att @ x  ⇒  datt = g xᵀ, dx = attᵀ g
    datt = jax.lax.dot_general(g, x, (((1,), (1,)), ((), ())))   # (Q, Q)
    dx = jax.lax.dot_general(att, g, (((0,), (0,)), ((), ())))   # (Q, P)

    # att = cb · decay · dt[None, :]: product-rule splits, all masked by
    # decay (zero above the diagonal, so no tril re-mask needed)
    dad = datt * decay                                           # (Q, Q)
    ddt = jnp.sum(dad * cb, axis=0)                              # (Q,)
    dseg = dad * cb * dt[None, :]                                # through exp
    dcum = jnp.sum(dseg, axis=1) - jnp.sum(dseg, axis=0)         # (Q,)

    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    ddt_ref[0, 0, 0] = ddt.astype(ddt_ref.dtype)
    dcum_ref[0, 0, 0] = dcum.astype(dcum_ref.dtype)

    # B/C are shared across heads: accumulate dcb over the sequential
    # innermost h axis, emit dB/dC once at the last head step
    dcb_h = dad * dt[None, :]

    @pl.when(h == 0)
    def _init():
        dcb_scr[...] = jnp.zeros_like(dcb_scr)

    dcb_scr[...] += dcb_h

    @pl.when(h == nh - 1)
    def _finish():
        dcb = dcb_scr[...]
        db_ref[0, 0] = jax.lax.dot_general(
            dcb, Cm, (((0,), (0,)), ((), ()))).astype(db_ref.dtype)
        dc_ref[0, 0] = jax.lax.dot_general(
            dcb, Bm, (((1,), (0,)), ((), ()))).astype(dc_ref.dtype)


def ssd_intra_bwd_pallas(xr: jnp.ndarray, dtr: jnp.ndarray, ltT: jnp.ndarray,
                         Br: jnp.ndarray, Cr: jnp.ndarray, g: jnp.ndarray,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                    jnp.ndarray, jnp.ndarray]:
    """Backward of :func:`ssd_intra_pallas` for cotangent ``g`` (the shape
    of ``y``).  Returns (dxr, ddtr, dltT, dBr, dCr) in input layouts."""
    B, nc, Q, H, P = xr.shape
    N = Br.shape[-1]
    cum = jnp.cumsum(ltT, axis=-1)                   # (B, nc, H, Q)

    x_hm = jnp.moveaxis(xr, 3, 2)                    # (B, nc, H, Q, P)
    dt_hm = jnp.moveaxis(dtr, 3, 2)                  # (B, nc, H, Q)
    g_hm = jnp.moveaxis(g, 3, 2)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    grid = (B * nc, H)
    x_spec = pl.BlockSpec((1, 1, Q, P), lambda bc, h: (bc, h, 0, 0))
    row_spec = pl.BlockSpec((1, 1, 1, Q), lambda bc, h: (bc, h, 0, 0))
    bc_spec = pl.BlockSpec((1, 1, Q, N), lambda bc, h: (bc, 0, 0, 0))
    dx, ddt, dcum, db, dc = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, q=Q),
        grid=grid,
        in_specs=[x_spec, row_spec, row_spec, bc_spec, bc_spec, x_spec],
        out_specs=[x_spec, row_spec, row_spec, bc_spec, bc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, H, Q, P), xr.dtype),
            jax.ShapeDtypeStruct((B * nc, H, 1, Q), dtr.dtype),
            jax.ShapeDtypeStruct((B * nc, H, 1, Q), jnp.float32),
            jax.ShapeDtypeStruct((B * nc, 1, Q, N), Br.dtype),
            jax.ShapeDtypeStruct((B * nc, 1, Q, N), Cr.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((Q, Q), jnp.float32)],
        interpret=interpret,
    )(
        x_hm.reshape(B * nc, H, Q, P),
        dt_hm.reshape(B * nc, H, 1, Q),
        cum.reshape(B * nc, H, 1, Q),
        Br.reshape(B * nc, 1, Q, N),
        Cr.reshape(B * nc, 1, Q, N),
        g_hm.reshape(B * nc, H, Q, P),
    )

    dxr = jnp.moveaxis(dx.reshape(B, nc, H, Q, P), 2, 3)
    ddtr = jnp.moveaxis(ddt.reshape(B, nc, H, Q), 2, 3)
    # cum = cumsum(ltT) ⇒ dltT is the suffix sum (reversed cumsum) of dcum
    dcum = dcum.reshape(B, nc, H, Q)
    dltT = jnp.cumsum(dcum[..., ::-1], axis=-1)[..., ::-1].astype(ltT.dtype)
    dBr = db.reshape(B, nc, Q, N)
    dCr = dc.reshape(B, nc, Q, N)
    return dxr, ddtr, dltT, dBr, dCr
