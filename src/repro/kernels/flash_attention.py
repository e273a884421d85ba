"""Pallas TPU flash attention (fwd + bwd) with GQA, causal and window masks.

Blockwise online-softmax attention à la Flash-Attention-2, tiled for the
TPU memory hierarchy:

* grid = (batch, q_heads, Sq/BQ, Sk/BK); the KV dimension is the innermost
  (sequential on TPU), so the running (m, l, acc) statistics live in VMEM
  scratch across KV steps;
* ``BlockSpec`` tiles: Q block (BQ, hd), K/V blocks (BK, hd) — BQ = BK =
  128 by default, MXU-aligned; the working set per step is
  ``(BQ + 2·BK)·hd·4`` bytes ≪ 16 MB VMEM;
* GQA without materializing repeated KV heads: the K/V index_map sends
  query-head ``h`` to KV head ``h // group``;
* causal/sliding-window masking is applied per-tile from absolute
  positions; fully-masked (Q, KV) tiles are *skipped* with ``pl.when``
  (the init/finish epilogues stay outside the predicate), cutting the
  causal forward to ~half the tiles and the windowed forward to
  O(window/BK) tiles per Q row.  ``count_tiles=True`` adds a scalar
  output with the number of executed tiles for the skip-accounting test;
  :func:`fa_tile_counts` is the analytic oracle (also used by the
  roofline model in ``benchmarks/bench_kernels``).

The backward pass is the FA2 recompute-tile scheme: the forward also
emits per-row LSE statistics (``lse = m + log l``), the launcher
precomputes ``delta = rowsum(dO · O)``, and two kernels recompute
``p = exp(s − lse)`` tile-by-tile:

* **dq**: grid (B, Hq, Sq/BQ, Sk/BK), KV innermost, dq accumulated in
  VMEM scratch across KV steps;
* **dk/dv**: grid (B, Hq, Sk/BK, Sq/BQ), Q innermost, dk/dv accumulated
  in scratch; GQA group reduction (summing query heads onto their shared
  KV head) happens outside the kernel as one XLA reshape-sum.

Both backward kernels reuse the forward's tile-skip predicate, so the
skipped work is symmetric.  Validated against :mod:`repro.kernels.ref`
in ``interpret=True`` mode (kernel body executed step-by-step on CPU);
on real TPUs the same code compiles to Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd", "flash_attention_bwd", "fa_tile_counts"]

NEG_INF = -1e30
# LSE filler for rows that saw no valid key (and for padded Q rows in the
# backward): exp(s - BIG) == 0 for any finite tile score s.
LSE_EMPTY = 1e30
# Per-row statistics (LSE, delta) cross the kernel boundary broadcast over
# one lane tile, (…, Sq, 128): a TPU block's last two dims must be
# (8k, 128m) or the whole array, which a (…, bq) row block is not.
_LANES = 128


def _tile_live(qi, ki, *, causal: bool, window: int, bq: int, bk: int,
               seq_k: int):
    """Traced predicate: does tile (qi, ki) contain any unmasked entry?

    Mirrors the in-tile mask exactly: a tile is dead when every (q_pos,
    k_pos) pair fails ``k_pos < seq_k`` / causal / window.  Python-static
    structure (causal/window are compile-time), traced program ids.
    """
    first_q = qi * bq
    last_q = first_q + bq - 1
    first_k = ki * bk
    last_k = first_k + bk - 1
    dead = first_k >= seq_k                       # whole KV tile is padding
    if causal:
        dead |= first_k > last_q                  # strictly above diagonal
    if window > 0:
        dead |= last_k <= first_q - window        # fell out of the window
    return jnp.logical_not(dead)


def fa_tile_counts(Sq: int, Sk: int, bq: int, bk: int, causal: bool,
                   window: int) -> Tuple[int, int]:
    """Analytic (executed, skipped) tile counts per (batch, head) for the
    skip predicate above — the oracle for the unit test and the tile term
    of the roofline FLOP model."""
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    executed = 0
    for qi in range(nq):
        for ki in range(nk):
            first_q, last_q = qi * bq, qi * bq + bq - 1
            first_k, last_k = ki * bk, ki * bk + bk - 1
            dead = first_k >= Sk
            if causal:
                dead = dead or first_k > last_q
            if window > 0:
                dead = dead or last_k <= first_q - window
            executed += 0 if dead else 1
    return executed, nq * nk - executed


# --------------------------------------------------------------- forward
def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, tiles_ref,
               m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int, bq: int, bk: int,
               seq_k: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((b == 0) & (h == 0) & (qi == 0) & (ki == 0))
    def _zero_counter():
        tiles_ref[0, 0] = 0

    live = _tile_live(qi, ki, causal=causal, window=window, bq=bq, bk=bk,
                      seq_k=seq_k)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                      # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)                      # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)                      # (BK, hd)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        s = s * scale

        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                      # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                   # (BQ, BK)

        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new
        tiles_ref[0, 0] += 1

    @pl.when(ki == nk - 1)
    def _finish():
        # rows that saw no valid key (padding) get l = 0 → emit zeros
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, LSE_EMPTY, m_scr[...] + jnp.log(safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, (bq, _LANES))


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        return_lse: bool = False, count_tiles: bool = False,
                        interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd) → (B, Sq, Hq, hd).

    Hq must be a multiple of Hkv (GQA).  Sequences are padded to the block
    size internally; padded keys are masked out, padded queries dropped.
    With ``return_lse`` also returns the per-row log-sum-exp statistics,
    shape (B, Hq, Sq) — the FA2 backward residual.  With ``count_tiles``
    additionally returns the number of executed (non-skipped) tiles.
    """
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = hd ** -0.5

    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    Sqp, Skp = Sq + pq, Sk + pk

    # layout: (B, H, S, hd) for clean 2D blocks
    qt = qp.transpose(0, 2, 1, 3)
    kt = kp.transpose(0, 2, 1, 3)
    vt = vp.transpose(0, 2, 1, 3)

    grid = (B, Hq, Sqp // bq, Skp // bk)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    out, lse, tiles = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar tile counter
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sqp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sqp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)

    out = out.transpose(0, 2, 1, 3)
    lse = lse[..., 0]
    if pq:
        out = out[:, :Sq]
        lse = lse[:, :, :Sq]
    res = (out,)
    if return_lse:
        res += (lse,)
    if count_tiles:
        res += (tiles[0, 0],)
    return res if len(res) > 1 else out


# -------------------------------------------------------------- backward
def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *,
                      scale: float, causal: bool, window: int, bq: int,
                      bk: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = _tile_live(qi, ki, causal=causal, window=window, bq=bq, bk=bk,
                      seq_k=seq_k)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                      # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)                      # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)                      # (BK, hd)
        do = do_ref[0, 0].astype(jnp.float32)                    # (BQ, hd)
        lse = lse_ref[0, 0][:, :1]                               # (BQ, 1)
        delta = delta_ref[0, 0][:, :1]                           # (BQ, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        p = jnp.exp(s - lse)                                     # (BQ, BK)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(ds, k,
                                           (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *,
                       scale: float, causal: bool, window: int, bq: int,
                       bk: int, seq_k: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = _tile_live(qi, ki, causal=causal, window=window, bq=bq, bk=bk,
                      seq_k=seq_k)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                      # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)                      # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)                      # (BK, hd)
        do = do_ref[0, 0].astype(jnp.float32)                    # (BQ, hd)
        lse = lse_ref[0, 0][:, :1]                               # (BQ, 1)
        delta = delta_ref[0, 0][:, :1]                           # (BQ, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        # padded Q rows carry lse = LSE_EMPTY → p == 0: no contribution
        p = jnp.exp(s - lse)                                     # (BQ, BK)
        dv_scr[...] += jax.lax.dot_general(p, do,
                                           (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(ds, q,
                                           (((0,), (0,)), ((), ())))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        out: jnp.ndarray, lse: jnp.ndarray,
                        do: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """FA2 recompute-tile backward.  Residuals: ``out`` (B, Sq, Hq, hd)
    and ``lse`` (B, Hq, Sq) from the forward.  Returns (dq, dk, dv) in
    the input layouts/dtypes."""
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = hd ** -0.5

    # delta_i = rowsum(dO_i · O_i) — cheap elementwise+reduce, precomputed
    # in XLA exactly like FA2 does in its preamble kernel
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                     # (B, Sq, Hq)

    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    dop = jnp.pad(do, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else do
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    # padded Q rows: lse = LSE_EMPTY kills p; delta = 0 for symmetry
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pq)),
                   constant_values=LSE_EMPTY) if pq else lse
    deltap = jnp.pad(delta, ((0, 0), (0, pq), (0, 0))) if pq else delta
    Sqp, Skp = Sq + pq, Sk + pk

    qt = qp.transpose(0, 2, 1, 3)                                # (B,Hq,Sqp,hd)
    dot = dop.transpose(0, 2, 1, 3)
    kt = kp.transpose(0, 2, 1, 3)                                # (B,Hkv,Skp,hd)
    vt = vp.transpose(0, 2, 1, 3)
    deltat = deltap.transpose(0, 2, 1)                           # (B,Hq,Sqp)
    lanes = lambda r: jnp.broadcast_to(r.astype(jnp.float32)[..., None],
                                       r.shape + (_LANES,))
    lsep, deltat = lanes(lsep), lanes(deltat)                    # (…,Sqp,128)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec_q = pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, i, j: (b, h // group, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=Sk),
        grid=(B, Hq, Sqp // bq, Skp // bk),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lsep, deltat)

    # dk/dv: grid transposed (KV outer, Q innermost sequential); outputs
    # are per *query* head — the GQA group reduction onto the shared KV
    # head is one XLA reshape-sum below.
    q_spec_t = pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0))
    kv_spec_t = pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, j, i: (b, h // group, j, 0))
    kv_out_t = pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0))
    row_spec_t = pl.BlockSpec((1, 1, bq, _LANES),
                              lambda b, h, j, i: (b, h, i, 0))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=Sk),
        grid=(B, Hq, Skp // bk, Sqp // bq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_out_t, kv_out_t],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Skp, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Skp, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lsep, deltat)

    dq = dq.transpose(0, 2, 1, 3)
    if pq:
        dq = dq[:, :Sq]
    dk = dk_h.reshape(B, Hkv, group, Skp, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, Skp, hd).sum(axis=2).astype(v.dtype)
    dk = dk.transpose(0, 2, 1, 3)
    dv = dv.transpose(0, 2, 1, 3)
    if pk:
        dk = dk[:, :Sk]
        dv = dv[:, :Sk]
    return dq, dk, dv
