"""Fused row-wise optimizer updates: the update arithmetic on touched rows only.

The dense training path materializes a (R, D) gradient for every pooled
embedding store and lets the optimizer touch all ~R rows per step, even
though a batch looks up a tiny skewed subset — the FBGEMM fused-sparse-
adagrad observation. This module is the update half of the fused sparse
backward: it consumes the deduped COO row gradients produced by
``fused_embedding.sparse_row_grads`` (``rows`` (N,) store rows with an
out-of-bounds sentinel tail, ``vals`` (N, D) f32 summed cotangents) and
applies the row-wise adagrad/adam update to exactly those rows of the
parameter pool and its moment pools.

Two implementations share one arithmetic contract:

XLA fallback
    One gather per state array, the row-wise update expression, one scatter
    back. Sentinel rows read a clamped row (harmless) and their writes are
    dropped by JAX's out-of-bounds scatter semantics — padding rows of a
    ``PaddedLayout`` store are never named by ``rows`` at all, so they are
    untouched by construction.

Pallas kernel
    Grid over row blocks; each step receives its ``(1, block)`` row-id row
    in SMEM and its value rows in VMEM, while the parameter and moment
    pools stay off-chip (``memory_space=ANY``), lane-packed
    (``common.lane_pack``: Mosaic moves HBM data in 128-lane lines, and a
    ``(R, 16)`` or ``(R, 1)`` row is narrower), and aliased input→output
    (``input_output_aliases``). The state holds the pools as ``(R, D)``,
    so each call copies every pool into its lines and back afterwards: the
    aliased buffer is that temporary, and the call reads and writes all
    ``R`` rows of each pool even though the kernel touches a few. Per row,
    the kernel DMAs the parameter/moment lines holding the row into ``(1, W)``
    VMEM staging, rotates the value row onto the row's lanes, applies the
    *same* f32 expressions as the XLA fallback on those lanes only, and
    DMAs the lines back — guarded by ``pl.when(row < R)`` so the sentinel
    tail never issues a DMA. Rows are updated one after another, so two
    rows sharing a line never race. Identical expressions keep interpret
    mode within a ULP or two of the fallback (XLA may contract the
    multiply-adds into FMAs differently between the two lowerings).

Row-wise vs dense semantics: adagrad's dense update is an exact no-op on
rows with zero gradient, so the row-wise form is bit-identical to the dense
path. Adam is *lazy*: moments of untouched rows are not decayed (standard
sparse-adam semantics); its reference oracle is the dense gradient with the
row-wise expression applied to the touched rows.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import lane_pack, lane_unpack, rows_per_line


def _pad_to_block(rows, vals, num_rows: int, block: int):
    """Pad the COO pair to a whole number of row blocks (sentinel/zero)."""
    n = rows.shape[0]
    n_pad = pl.cdiv(n, block) * block - n
    if n_pad:
        rows = jnp.concatenate(
            [rows, jnp.full((n_pad,), num_rows, rows.dtype)])
        vals = jnp.pad(vals, ((0, n_pad), (0, 0)))
    return rows, vals


def _kernel_operands(pools, rows, vals, block: int):
    """Lane-pack the pools and lay out the COO pair for the row kernels.

    Returns ``(lines, rows, vals, P)``: each pool as ``lane_pack`` lines,
    the row ids as ``(n_blocks, 1, block)`` SMEM rows, and the values
    zero-padded to the line width (row values at lanes ``[0, D)``).
    """
    R, D = pools[0].shape
    rows, vals = _pad_to_block(rows, vals, R, block)
    lines = [lane_pack(p) for p in pools]
    W = lines[0].shape[1]
    return (lines, rows.reshape(-1, 1, block),
            jnp.pad(vals, ((0, 0), (0, W - D))), rows_per_line(D))


def _row_view(row, g, *, P: int, D: int):
    """Where row ``row`` sits in its lane line: the line index, the value row
    ``g`` rotated onto the row's lanes, and the mask of those lanes."""
    if P == 1:
        return row, g, None
    W = g.shape[-1]
    lane = jax.lax.rem(row, P) * D
    iota = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    mask = (iota >= lane) & (iota < lane + D)
    return row // P, pltpu.roll(g, lane, 1), mask


# ---------------------------------------------------------------------------
# adagrad
# ---------------------------------------------------------------------------
def _adagrad_xla(params, acc, rows, vals, *, lr: float, eps: float):
    g = vals
    acc_rows = acc[rows] + jnp.square(g)
    upd = (-lr * g / (jnp.sqrt(acc_rows) + eps)).astype(params.dtype)
    return params.at[rows].add(upd), acc.at[rows].set(acc_rows)


def _adagrad_kernel(rows_ref, vals_ref, p_hbm, a_hbm, p_out, a_out,
                    p_stage, a_stage, sem, *, R: int, P: int, D: int,
                    block: int, lr: float, eps: float):
    del p_hbm, a_hbm   # aliased with p_out/a_out; all access goes via out refs
    for r in range(block):
        row = rows_ref[0, r]

        @pl.when(row < R)
        def update_row(row=row, r=r):
            line, g, mask = _row_view(row, vals_ref[pl.ds(r, 1), :], P=P, D=D)
            fetch_p = pltpu.make_async_copy(
                p_out.at[pl.ds(line, 1), :], p_stage, sem.at[0])
            fetch_a = pltpu.make_async_copy(
                a_out.at[pl.ds(line, 1), :], a_stage, sem.at[1])
            fetch_p.start()
            fetch_a.start()
            fetch_p.wait()
            fetch_a.wait()
            p_old, a_old = p_stage[...], a_stage[...]
            acc_row = a_old + jnp.square(g)
            upd = (-lr * g / (jnp.sqrt(acc_row) + eps)).astype(p_stage.dtype)
            p_new = p_old + upd
            if mask is not None:        # the line's other rows stay as read
                p_new = jnp.where(mask, p_new, p_old)
                acc_row = jnp.where(mask, acc_row, a_old)
            p_stage[...] = p_new
            a_stage[...] = acc_row
            store_p = pltpu.make_async_copy(
                p_stage, p_out.at[pl.ds(line, 1), :], sem.at[0])
            store_a = pltpu.make_async_copy(
                a_stage, a_out.at[pl.ds(line, 1), :], sem.at[1])
            store_p.start()
            store_a.start()
            store_p.wait()
            store_a.wait()


def _adagrad_pallas(params, acc, rows, vals, *, lr, eps, block, interpret):
    R, D = params.shape
    (p_lines, a_lines), rows, vals, P = _kernel_operands(
        (params, acc), rows, vals, block)
    W = p_lines.shape[1]
    kernel = functools.partial(
        _adagrad_kernel, R=R, P=P, D=D, block=block, lr=lr, eps=eps)
    p_lines, a_lines = pl.pallas_call(
        kernel,
        grid=(rows.shape[0],),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, block), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block, W), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),    # params (aliased out 0)
            pl.BlockSpec(memory_space=pl.ANY),    # acc    (aliased out 1)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p_lines.shape, p_lines.dtype),
            jax.ShapeDtypeStruct(a_lines.shape, a_lines.dtype),
        ],
        input_output_aliases={2: 0, 3: 1},
        scratch_shapes=[
            pltpu.VMEM((1, W), params.dtype),
            pltpu.VMEM((1, W), acc.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(rows, vals, p_lines, a_lines)
    return lane_unpack(p_lines, R, D), lane_unpack(a_lines, R, D)


def adagrad_row_update(params: jnp.ndarray, acc: jnp.ndarray,
                       rows: jnp.ndarray, vals: jnp.ndarray, *,
                       lr: float, eps: float = 1e-10, method: str = "xla",
                       block: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise adagrad on deduped COO row grads. -> (params, acc).

    Args:
      params: (R, D) parameter pool (flat or flattened padded store).
      acc:    (R, D) f32 second-moment accumulator pool (same row space).
      rows:   (N,) deduplicated store rows; entries ``>= R`` are padding.
      vals:   (N, D) summed row gradients (zero on padding entries).
      lr/eps: adagrad hyperparameters (``train.optim.adagrad`` defaults).
      method: "xla" (gather/scatter fallback), "pallas", or "interpret".
      block:  rows per Pallas grid step.

    Matches the dense adagrad update fed the dense gradient that
    ``SparseRowGrad.to_dense`` reconstructs, up to FMA-contraction ULPs
    (zero-grad rows are exact no-ops either way).
    """
    rows = rows.astype(jnp.int32)
    vals = vals.astype(jnp.float32)
    if method in ("pallas", "interpret"):
        return _adagrad_pallas(params, acc, rows, vals, lr=lr, eps=eps,
                               block=max(1, block),
                               interpret=(method == "interpret"))
    return _adagrad_xla(params, acc, rows, vals, lr=lr, eps=eps)


# ---------------------------------------------------------------------------
# adam (lazy row-wise)
# ---------------------------------------------------------------------------
def _adam_xla(params, m, v, rows, vals, bias, *, lr, b1, b2, eps, wd):
    g = vals
    m_rows = b1 * m[rows] + (1 - b1) * g
    v_rows = b2 * v[rows] + (1 - b2) * jnp.square(g)
    mh = m_rows / bias[0]
    vh = v_rows / bias[1]
    p32 = params[rows].astype(jnp.float32)
    upd = (-lr * (mh / (jnp.sqrt(vh) + eps) + wd * p32)).astype(params.dtype)
    return (params.at[rows].add(upd), m.at[rows].set(m_rows),
            v.at[rows].set(v_rows))


def _adam_kernel(rows_ref, vals_ref, bias_ref, p_hbm, m_hbm, v_hbm,
                 p_out, m_out, v_out, p_stage, m_stage, v_stage, sem, *,
                 R: int, P: int, D: int, block: int, lr: float, b1: float,
                 b2: float, eps: float, wd: float):
    del p_hbm, m_hbm, v_hbm   # aliased with the out refs
    for r in range(block):
        row = rows_ref[0, r]

        @pl.when(row < R)
        def update_row(row=row, r=r):
            line, g, mask = _row_view(row, vals_ref[pl.ds(r, 1), :], P=P, D=D)
            fetch_p = pltpu.make_async_copy(
                p_out.at[pl.ds(line, 1), :], p_stage, sem.at[0])
            fetch_m = pltpu.make_async_copy(
                m_out.at[pl.ds(line, 1), :], m_stage, sem.at[1])
            fetch_v = pltpu.make_async_copy(
                v_out.at[pl.ds(line, 1), :], v_stage, sem.at[2])
            fetch_p.start()
            fetch_m.start()
            fetch_v.start()
            fetch_p.wait()
            fetch_m.wait()
            fetch_v.wait()
            p_old, m_old, v_old = p_stage[...], m_stage[...], v_stage[...]
            m_row = b1 * m_old + (1 - b1) * g
            v_row = b2 * v_old + (1 - b2) * jnp.square(g)
            mh = m_row / bias_ref[0]
            vh = v_row / bias_ref[1]
            p32 = p_old.astype(jnp.float32)
            upd = (-lr * (mh / (jnp.sqrt(vh) + eps)
                          + wd * p32)).astype(p_stage.dtype)
            p_new = p_old + upd
            if mask is not None:        # the line's other rows stay as read
                p_new = jnp.where(mask, p_new, p_old)
                m_row = jnp.where(mask, m_row, m_old)
                v_row = jnp.where(mask, v_row, v_old)
            p_stage[...] = p_new
            m_stage[...] = m_row
            v_stage[...] = v_row
            store_p = pltpu.make_async_copy(
                p_stage, p_out.at[pl.ds(line, 1), :], sem.at[0])
            store_m = pltpu.make_async_copy(
                m_stage, m_out.at[pl.ds(line, 1), :], sem.at[1])
            store_v = pltpu.make_async_copy(
                v_stage, v_out.at[pl.ds(line, 1), :], sem.at[2])
            store_p.start()
            store_m.start()
            store_v.start()
            store_p.wait()
            store_m.wait()
            store_v.wait()


def _adam_pallas(params, m, v, rows, vals, bias, *, lr, b1, b2, eps, wd,
                 block, interpret):
    R, D = params.shape
    (p_lines, m_lines, v_lines), rows, vals, P = _kernel_operands(
        (params, m, v), rows, vals, block)
    W = p_lines.shape[1]
    kernel = functools.partial(
        _adam_kernel, R=R, P=P, D=D, block=block, lr=lr, b1=b1, b2=b2,
        eps=eps, wd=wd)
    p_lines, m_lines, v_lines = pl.pallas_call(
        kernel,
        grid=(rows.shape[0],),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, block), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block, W), lambda i: (i, 0)),
            # bias-correction denominators: tiny, grid-constant, scalar mem
            pl.BlockSpec((2,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),    # params (aliased out 0)
            pl.BlockSpec(memory_space=pl.ANY),    # m      (aliased out 1)
            pl.BlockSpec(memory_space=pl.ANY),    # v      (aliased out 2)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p_lines.shape, p_lines.dtype),
            jax.ShapeDtypeStruct(m_lines.shape, m_lines.dtype),
            jax.ShapeDtypeStruct(v_lines.shape, v_lines.dtype),
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        scratch_shapes=[
            pltpu.VMEM((1, W), params.dtype),
            pltpu.VMEM((1, W), m.dtype),
            pltpu.VMEM((1, W), v.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(rows, vals, bias, p_lines, m_lines, v_lines)
    return (lane_unpack(p_lines, R, D), lane_unpack(m_lines, R, D),
            lane_unpack(v_lines, R, D))


def adam_row_update(params: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
                    rows: jnp.ndarray, vals: jnp.ndarray, *, lr: float,
                    count, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, weight_decay: float = 0.0,
                    method: str = "xla", block: int = 8):
    """Lazy row-wise adam on deduped COO row grads. -> (params, m, v).

    Args:
      params:  (R, D) parameter pool.
      m, v:    (R, D) f32 first/second-moment pools (same row space).
      rows:    (N,) deduplicated store rows; entries ``>= R`` are padding.
      vals:    (N, D) summed row gradients.
      lr/b1/b2/eps/weight_decay: adam hyperparameters.
      count:   the step count *after* this step (the dense-side update's
               incremented counter) — bias correction must agree with it.
      method:  "xla", "pallas", or "interpret".
      block:   rows per Pallas grid step.

    Lazy semantics: untouched rows' moments are not decayed (sparse-adam
    convention); weight decay likewise only reaches touched rows.
    """
    rows = rows.astype(jnp.int32)
    vals = vals.astype(jnp.float32)
    tc = jnp.asarray(count, jnp.float32)
    # one shared bias-correction computation feeds both impls bit-identically
    bias = jnp.stack([1 - b1 ** tc, 1 - b2 ** tc])
    kw = dict(lr=lr, b1=b1, b2=b2, eps=eps, wd=weight_decay)
    if method in ("pallas", "interpret"):
        return _adam_pallas(params, m, v, rows, vals, bias,
                            block=max(1, block),
                            interpret=(method == "interpret"), **kw)
    return _adam_xla(params, m, v, rows, vals, bias, **kw)
