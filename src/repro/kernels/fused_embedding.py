"""Fused multi-table embedding engine: pipelined DMA + hot-row cache + sparse VJP.

The paper's #1 hot spot is embedding lookups (30–48 % of DLRM iteration time,
§1 Fig 1a). The naive formulation issues one gather/pool per table — for a
Criteo-style model that is 26 kernel launches per step, each with its own grid
setup, and 26 scatter-adds in the backward pass. This module fuses *all*
tables into a single call and pipelines the memory traffic:

Pooled-table layout
    Every table shares the embedding width ``D``, so the ``T`` tables are
    concatenated row-wise into one pool ``(sum(rows_t), D)``. Per-table row
    ranges are addressed by static ``offsets`` (exclusive cumulative sums of
    the per-table row counts). A batch of per-table-local indices
    ``(B, T, H)`` becomes global pool rows by adding ``offsets[t]`` — after
    which the table dimension is just another axis of one big gather.

Padded physical layout (unequal PS shards)
    With ``layout`` (a ``repro.sharding.policy.PaddedLayout``) the engine
    addresses the *padded* pool ``(n_ps * max_range, D)`` — the flattened
    form of the ``(n_ps, max_range, D)`` store whose leading axis GSPMD
    splits equally, placing exactly the balanced range plan on the mesh.
    Lookups keep flowing in as **flat** pooled rows (the canonical id space
    every planner and the hot-row contract speak); the engine translates
    them to padded rows — ``shard * max_range + (row - shard_start)`` — on
    both forward paths and in the backward ``segment_sum``. Padding slots
    are never addressed, so they contribute zero to pooling and receive
    zero gradient, and numerics are bit-identical to the flat layout (same
    rows, same reduce order). See ``docs/EMBEDDING_LAYOUT.md``.

Hot-row cache (skew-aware placement contract)
    Real sparse-feature traffic is power-law skewed: a tiny fraction of rows
    serves most lookups (RecShard / MTrainS). Under frequency-aware placement
    the hot rows of table ``t`` are *packed* into its leading local ids
    ``[0, table_hot[t])`` (see ``repro.sharding.policy.pack_hot_ranges``).
    The engine mirrors those prefixes into a VMEM-resident cache
    ``(sum(table_hot), D)`` and consults it before issuing any HBM DMA: hot
    lookups become direct VMEM loads, only the cold tail pays an HBM round
    trip. On the XLA path the packed prefix *is* the cache — it stays
    hardware-cache-resident by construction, so no extra gather is issued.
    The custom-VJP backward is unchanged either way because global row ids
    are preserved (the cache only re-routes forward reads). The plan is not
    frozen for the job's lifetime: when access skew drifts, the live
    re-planner (``repro.train.replan``) re-packs the pool and recompiles
    with a fresh ``table_hot`` — any plan computes identical numerics.

Forward (Pallas path, double-buffered)
    The grid is ``(T, ceil(B/block_b))`` and the kernel works table-major:
    the batch is padded to a whole number of blocks, the encoded index
    tensor is laid out ``(T, B_pad, H)`` and flattened, and the output is
    ``(T, B_pad, W)`` (sliced and transposed back to ``(B, T, D)`` by XLA).
    The pool is read as lane-packed lines of ``W = max(D, 128)`` words
    (``common.lane_pack``), because Mosaic, the TPU kernel compiler, moves
    HBM data in whole 128-lane lines; a lane rotate brings each staged row
    to lane 0. So every block's two minor dims are ``(block_b, W)`` or a
    full index row, as Mosaic tiles arrays. Each step receives its
    ``block_b * H`` slice of the *encoded* indices as a 1-D SMEM block (hot
    lookups are encoded as ``-(cache_slot+1)``, cold ones as the global pool
    row). Row staging is double-buffered across grid steps — two
    ``(H, block_b, W)`` VMEM staging buffers and two DMA semaphores: while
    step ``i`` drains its buffer and reduces it into its ``(block_b, W)``
    output tile, step ``i``'s body has already issued the copies for step
    ``i+1`` into the other buffer (the next step's index slice is delivered
    through a second, look-ahead SMEM block), so HBM copy latency overlaps
    the reduction instead of serializing with it.

Forward (XLA fallback)
    One ``jnp.take`` over the pool + one reduction over the hot axis — no
    Python per-table loop, so CPU/dry-run paths get one fused HLO gather
    instead of ``T`` of them.

Backward (custom VJP — the paper's sparse-gradient aggregation)
    Differentiating through the gather loop would replay ``T`` scatter-adds
    (and is impossible through the Pallas kernel). Instead ``jax.custom_vjp``
    computes per-lookup row gradients analytically (sum/mean broadcast,
    max via a tie-normalized argmax mask matching ``jax.grad``-of-``jnp.max``
    semantics) and aggregates duplicate rows with a single
    ``jax.ops.segment_sum`` over the flattened global indices — deduplication
    and scatter-add in one fused op, shared by every impl. Cached rows need
    no special casing: their cotangents land on the same global ids.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.common import lane_pack, rows_per_line
from repro.spans import scope

COMBINERS = ("sum", "mean", "max")


def table_offsets(table_rows: Sequence[int]) -> Tuple[int, ...]:
    """Exclusive cumulative row offsets for a pooled-table layout.

    Args:
      table_rows: per-table row counts.

    Returns one flat-pool start row per table; table ``t``'s local id ``i``
    is flat pooled row ``offsets[t] + i``.
    """
    offs, acc = [], 0
    for r in table_rows:
        offs.append(acc)
        acc += int(r)
    return tuple(offs)


def cache_slot_offsets(table_hot: Sequence[int]) -> Tuple[int, ...]:
    """Exclusive cumulative cache-slot offsets of the per-table hot prefixes.

    Args:
      table_hot: per-table hot-prefix sizes (``pack_hot_ranges`` output).

    Returns the cache slot where each table's hot rows begin: table ``t``'s
    hot local id ``i < table_hot[t]`` occupies slot ``offsets[t] + i`` of the
    ``(sum(table_hot), D)`` VMEM cache.
    """
    return table_offsets(table_hot)


def hot_row_ids(offsets: Sequence[int], table_hot: Sequence[int]) -> np.ndarray:
    """Flat pool row ids mirrored by the cache (per-table leading ranges).

    Args:
      offsets:   per-table flat-pool start rows (``table_offsets``).
      table_hot: per-table hot-prefix sizes.

    Returns the ``(sum(table_hot),)`` int64 ids in cache-slot order — the
    rows to gather when materializing the cache, under any physical layout.
    """
    parts = [np.arange(o, o + k, dtype=np.int64)
             for o, k in zip(offsets, table_hot) if k > 0]
    if not parts:
        return np.zeros((0,), np.int64)
    return np.concatenate(parts)


# ---------------------------------------------------------------------------
# flat → padded row translation (physically-unequal PS shards)
# ---------------------------------------------------------------------------
def translate_rows(rows: jnp.ndarray, layout) -> jnp.ndarray:
    """Flat pooled rows → rows of the flattened padded pool (traced).

    The jit-side twin of ``PaddedLayout.flat_to_padded``: finds each row's
    shard with a ``searchsorted`` over the static shard starts (rightmost
    match, so empty shards are never selected) and rebases it to
    ``shard * max_range + slot``.

    Args:
      rows:   int array of flat pooled row ids (any shape).
      layout: a ``repro.sharding.policy.PaddedLayout`` (duck-typed: only
              ``shard_starts``, ``max_range`` and ``n_ps`` are read, keeping
              this module free of cross-package imports).

    Returns padded row ids, same shape/dtype as ``rows``.
    """
    starts = jnp.asarray(layout.shard_starts, rows.dtype)
    shard = jnp.clip(jnp.searchsorted(starts, rows, side="right") - 1,
                     0, layout.n_ps - 1)
    return shard * layout.max_range + rows - starts[shard]


def translate_rows_np(rows: np.ndarray, layout) -> np.ndarray:
    """Host-side ``translate_rows`` for static plans (cache row gathers).

    Delegates to ``layout.flat_to_padded`` — one implementation of the
    subtle rightmost-match/empty-shard logic, shared with the traced twin's
    tests, instead of a drifting copy.
    """
    return layout.flat_to_padded(np.asarray(rows, np.int64))


def encode_hot_indices(idx, offsets: Sequence[int],
                       table_hot: Sequence[int]):
    """Route each lookup: hot rows -> ``-(cache_slot+1)``, cold -> flat row.

    Hot rows of table ``t`` are its leading local ids ``[0, table_hot[t])``
    (the frequency-packed placement contract); their cache slots are laid
    out contiguously per table. Encoding always happens in the FLAT id space
    — under a padded physical layout the cold entries are rebased into the
    padded space *after* this (hot detection would be meaningless on padded
    ids, whose shard-local arithmetic destroys table locality).

    Args:
      idx:       (B, T, H) *flat* global index tensor (offsets applied).
      offsets:   per-table flat-pool start rows (``table_offsets``).
      table_hot: per-table hot-prefix sizes.

    Returns ``(encoded, hit)``: ``encoded`` is ``idx`` with hot lookups
    replaced by ``-(cache_slot + 1)``, ``hit`` the boolean hot mask.
    """
    off = jnp.asarray(offsets, jnp.int32)[None, :, None]
    k = jnp.asarray(table_hot, jnp.int32)[None, :, None]
    coff = jnp.asarray(cache_slot_offsets(table_hot), jnp.int32)[None, :, None]
    local = idx - off
    hit = local < k
    slot = coff + local
    return jnp.where(hit, -slot - 1, idx), hit


# ---------------------------------------------------------------------------
# Pallas kernel: (T, ceil(B/block_b)) grid, double-buffered row staging
# ---------------------------------------------------------------------------
def _row_copy(lines_ref, stage_ref, sem, v, r: int, j: int, *, L: int, P: int):
    """The DMA of the lane line holding pool row ``v`` into slot ``(j, r)``."""
    return pltpu.make_async_copy(
        lines_ref.at[pl.ds(jnp.clip(v // P, 0, L - 1), 1), :],
        stage_ref.at[j].at[pl.ds(r, 1), :],
        sem,
    )


def _fill_stage(stage_ref, sem, blk_ref, lines_ref, cache_ref, *,
                L: int, P: int, K: int, H: int, block_b: int):
    """Stage one block's rows: hot slots from VMEM cache, cold lines via DMA."""
    for r in range(block_b):
        for j in range(H):
            v = blk_ref[0, r * H + j]
            if cache_ref is None:
                _row_copy(lines_ref, stage_ref, sem, v, r, j, L=L, P=P).start()
            else:
                @pl.when(v >= 0)
                def start_cold(v=v, r=r, j=j):
                    _row_copy(lines_ref, stage_ref, sem, v, r, j,
                              L=L, P=P).start()

                @pl.when(v < 0)
                def copy_hot(v=v, r=r, j=j):
                    slot = jnp.clip(-v - 1, 0, K - 1)
                    stage_ref[j, pl.ds(r, 1), :] = cache_ref[pl.ds(slot, 1), :]


def _drain_stage(stage_ref, sem, blk_ref, lines_ref, cached: bool, *,
                 L: int, P: int, D: int, H: int, block_b: int):
    """Wait for exactly the DMAs `_fill_stage` issued for this block, then
    rotate every staged line so its row starts at lane 0 (cold row ``v``
    sits at lane ``(v % P) * D`` of its line; cached rows already at 0)."""
    for r in range(block_b):
        for j in range(H):
            v = blk_ref[0, r * H + j]
            cp = _row_copy(lines_ref, stage_ref, sem, v, r, j, L=L, P=P)
            if cached:
                @pl.when(v >= 0)
                def wait_cold(cp=cp):
                    cp.wait()
            else:
                cp.wait()
            if P > 1:
                width = stage_ref.shape[-1]
                lane = jnp.where(v >= 0, jax.lax.rem(v, P) * D, 0)
                stage_ref[j, pl.ds(r, 1), :] = pltpu.roll(
                    stage_ref[j, pl.ds(r, 1), :],
                    jax.lax.rem(width - lane, width), 1)


def _fused_kernel(idx_ref, nxt_ref, lines_ref, *refs,
                  L: int, P: int, D: int, K: int, H: int, block_b: int,
                  combiner: str, weighted: bool, cached: bool):
    # refs = (cache_ref?, w_ref?, out_ref, stage_a, stage_b, sem)
    i = 0
    cache_ref = refs[i] if cached else None
    i += int(cached)
    w_ref = refs[i] if weighted else None
    i += int(weighted)
    out_ref, stage_a, stage_b, sem = refs[i], refs[i + 1], refs[i + 2], refs[i + 3]

    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    nsteps = pl.num_programs(0) * pl.num_programs(1)
    parity = jax.lax.rem(step, 2)
    fill_kw = dict(L=L, P=P, K=K, H=H, block_b=block_b)
    drain_kw = dict(L=L, P=P, D=D, H=H, block_b=block_b)

    # warm-up: the very first step stages its own rows
    @pl.when(step == 0)
    def warmup():
        _fill_stage(stage_a, sem.at[0], idx_ref, lines_ref, cache_ref,
                    **fill_kw)

    # prefetch step i+1's rows into the other buffer while this step reduces
    @pl.when((step + 1 < nsteps) & (parity == 0))
    def prefetch_into_b():
        _fill_stage(stage_b, sem.at[1], nxt_ref, lines_ref, cache_ref,
                    **fill_kw)

    @pl.when((step + 1 < nsteps) & (parity == 1))
    def prefetch_into_a():
        _fill_stage(stage_a, sem.at[0], nxt_ref, lines_ref, cache_ref,
                    **fill_kw)

    def reduce_from(stage_ref):
        # lanes [0, D) hold the looked-up rows; the rest are neighbours from
        # the same line, reduced alongside and sliced off by the caller
        rows = stage_ref[...].astype(jnp.float32)       # (H, block_b, W)
        if weighted:
            # a zero weight contributes exactly 0; the select also keeps
            # each product rounded on its own, as the XLA path's reduce sees
            # it (no multiply-add contraction across the sum)
            w = jnp.broadcast_to(w_ref[...], rows.shape)  # from (H, block_b, 1)
            rows = jnp.where(w == 0, 0.0, rows * w)
        if combiner == "max":
            res = jnp.max(rows, axis=0)
        else:
            res = jnp.sum(rows, axis=0)
            if combiner == "mean":
                res = res / H
        out_ref[...] = res.astype(out_ref.dtype)

    @pl.when(parity == 0)
    def consume_a():
        _drain_stage(stage_a, sem.at[0], idx_ref, lines_ref, cached,
                     **drain_kw)
        reduce_from(stage_a)

    @pl.when(parity == 1)
    def consume_b():
        _drain_stage(stage_b, sem.at[1], idx_ref, lines_ref, cached,
                     **drain_kw)
        reduce_from(stage_b)


def _pallas_forward(pool, enc_idx, weights, cache, *, T, H, combiner,
                    block_b, interpret):
    R, D = pool.shape
    B = enc_idx.shape[0]              # this device's batch shard on a mesh
    block_b = min(block_b, B)
    P = rows_per_line(D)
    lines = lane_pack(pool)                                     # (L, W)
    L, W = lines.shape
    K = 0 if cache is None else cache.shape[0]
    if K > 0:
        # cached rows sit at lanes [0, D) of their own line
        cache = jnp.pad(cache, ((0, 0), (0, W - D)))
    nb = pl.cdiv(B, block_b)
    nsteps = T * nb
    # pad the batch to whole blocks: encoded index 0 is a harmless cold DMA
    # of pool row 0, so no grid step ever sees unspecified block padding
    B_pad = nb * block_b
    # table-major layout: grid step (t, bb) owns one contiguous index row
    # and the (block_b, W) output tile of table t, batch block bb — blocks
    # whose two minor dims are (8k or full, full), as Mosaic tiles them
    enc_idx = jnp.pad(enc_idx.reshape(B, T, H), ((0, B_pad - B), (0, 0), (0, 0)))
    enc_idx = enc_idx.transpose(1, 0, 2).reshape(nsteps, 1, block_b * H)
    if weights is not None:
        # (T, H, B_pad, 1): each lookup weight broadcasts along lanes
        weights = jnp.pad(weights.reshape(B, T, H),
                          ((0, B_pad - B), (0, 0), (0, 0)))
        weights = weights.transpose(1, 2, 0)[..., None]

    def nxt_map(t, bb):
        # look-ahead SMEM block: step (t, bb) receives the next step's index
        # row so it can prefetch into the idle staging buffer
        return (jnp.minimum(t * nb + bb + 1, nsteps - 1), 0, 0)

    kernel = functools.partial(
        _fused_kernel, L=L, P=P, D=D, K=max(K, 1), H=H, block_b=block_b,
        combiner=combiner, weighted=weights is not None, cached=K > 0)
    in_specs = [
        # per-step (1, block_b*H) encoded-index rows staged to SMEM — the
        # full index tensor never has to fit on-chip
        pl.BlockSpec((pl.Squeezed(), 1, block_b * H),
                     lambda t, bb: (t * nb + bb, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((pl.Squeezed(), 1, block_b * H), nxt_map,
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),           # pool lines (manual DMA)
    ]
    args = [enc_idx, enc_idx, lines]
    if K > 0:
        # constant index map -> fetched once, VMEM-resident across the grid
        in_specs.append(pl.BlockSpec((K, W), lambda t, bb: (0, 0)))
        args.append(cache)
    if weights is not None:
        in_specs.append(pl.BlockSpec((pl.Squeezed(), H, block_b, 1),
                                     lambda t, bb: (t, 0, bb, 0)))
        args.append(weights)
    out = pl.pallas_call(
        kernel,
        grid=(T, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((pl.Squeezed(), block_b, W),
                               lambda t, bb: (t, bb, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, block_b, W), pool.dtype),
            pltpu.VMEM((H, block_b, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct((T, B_pad, W), pool.dtype),
        # the staging buffers carry state from one grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="emb_fwd",
    )(*args)
    return out[:, :B, :D].transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# XLA fallback: one take + one reduction (no per-table Python loop)
# ---------------------------------------------------------------------------
def _xla_forward(pool, flat_idx, weights, *, B, T, H, combiner):
    D = pool.shape[1]
    rows = jnp.take(pool, flat_idx, axis=0).reshape(B, T, H, D)
    if weights is not None:
        rows = rows * weights.reshape(B, T, H)[..., None]
    if combiner == "sum":
        out = jnp.sum(rows, axis=2)
    elif combiner == "mean":
        out = jnp.mean(rows, axis=2)
    else:
        out = jnp.max(rows, axis=2)
    return out.astype(pool.dtype)   # weights are f32; match the Pallas path


# ---------------------------------------------------------------------------
# sparse-gradient aggregation: the dedupe+segment step both backward paths share
# ---------------------------------------------------------------------------
def dedupe_rows(store_idx: jnp.ndarray, g_rows: jnp.ndarray,
                num_rows: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deduplicate row cotangents: (N,) rows + (N, D) grads → COO row grads.

    Duplicate store rows (the same id looked up twice in a batch — or twice
    inside one bag) are segment-reduced into a single entry, accumulating in
    a deterministic order (stable sort preserves the original order of equal
    rows). Output keeps the static input length: entry ``j`` of the result
    is the ``j``-th *distinct* row with its summed gradient; the tail is
    padded with the sentinel row ``num_rows`` and zero values. The sentinel
    is out of bounds on purpose — JAX scatter drops out-of-bounds updates,
    so the tail is inert for both the dense scatter-add and the fused
    row-wise optimizer update.

    Args:
      store_idx: (N,) int store rows (flat or padded space — whichever space
                 the pool being updated lives in).
      g_rows:    (N, D) per-lookup row cotangents.
      num_rows:  static row count of the store (the sentinel value).

    Returns ``(rows, vals)``: (N,) int rows (deduped + sentinel tail),
    (N, D) summed values (zero tail).
    """
    n = store_idx.shape[0]
    order = jnp.argsort(store_idx, stable=True)
    sorted_rows = store_idx[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_rows[1:] != sorted_rows[:-1]])
    seg = jnp.cumsum(first) - 1                    # dense segment id per entry
    vals = jax.ops.segment_sum(g_rows[order], seg, num_segments=n)
    rows = jnp.full((n,), num_rows, sorted_rows.dtype).at[seg].set(sorted_rows)
    return rows, vals


def _row_cotangents(pool, store_idx, w, g, *, combiner: str, B: int, T: int,
                    H: int):
    """Per-lookup row cotangents for one pooled bag output cotangent ``g``.

    Args:
      pool:      (R, D) store (only read for max ties and weighted ``dw``).
      store_idx: (B*T*H,) store rows of every lookup.
      w:         optional (B, T, H) f32 per-lookup weights.
      g:         (B, T, D) f32 output cotangent.

    Returns ``(g_rows, dw)``: (B, T, H, D) f32 cotangent per looked-up row,
    and the (B, T, H) weight cotangent (None when unweighted).
    """
    D = pool.shape[1]
    if combiner == "max":
        rows = jnp.take(pool, store_idx, axis=0).reshape(B, T, H, D)
        rows = rows.astype(jnp.float32)
        v = rows if w is None else rows * w[..., None]
        m = jnp.max(v, axis=2)                             # (B, T, D)
        # jax.grad(jnp.max) splits the cotangent evenly among tied argmaxes;
        # the normalized indicator reproduces that exactly (duplicate indices
        # inside one bag are the common tie source).
        tie = (v == m[:, :, None, :]).astype(jnp.float32)
        tie = tie / jnp.sum(tie, axis=2, keepdims=True)
        g_v = g[:, :, None, :] * tie                       # d loss / d v
        dw = None if w is None else jnp.sum(g_v * rows, axis=-1)
        g_rows = g_v if w is None else g_v * w[..., None]
        return g_rows, dw
    g_v = jnp.broadcast_to(g[:, :, None, :], (B, T, H, D))
    if combiner == "mean":
        g_v = g_v / H
    if w is None:
        return g_v, None
    rows = jnp.take(pool, store_idx, axis=0).reshape(B, T, H, D)
    dw = jnp.sum(g_v * rows.astype(jnp.float32), axis=-1)
    return g_v * w[..., None], dw


def sparse_row_grads(pool: jnp.ndarray, indices: jnp.ndarray, g: jnp.ndarray,
                     weights: Optional[jnp.ndarray] = None, *, plan):
    """Fused sparse backward: bag cotangents → deduped COO row gradients.

    The sparse twin of the custom VJP's pool gradient: instead of
    materializing the dense (R, D) scatter, it stops at the deduped
    (rows, vals) pair — exactly what ``Optimizer.update_rows`` (the fused
    row-wise optimizer update) consumes. Scattering ``vals`` at ``rows``
    into zeros reproduces the dense gradient bit for bit (same dedupe, same
    accumulation order).

    Args:
      pool:    (R, D) store (flat, or the flattened padded pool under
               ``plan.layout``).
      indices: (B, T, H) per-table-local (or global flat) lookup rows.
      g:       (B, T, D) cotangent of the fused bag output.
      weights: optional (B, T, H) per-lookup scalars.
      plan:    the ``EmbeddingPlan`` the forward ran under (duck-typed:
               ``offsets``, ``combiner``, ``layout`` are read).

    Returns ``(rows, vals, dweights)``: (B*T*H,) deduped store rows with
    sentinel tail, (B*T*H, D) f32 summed row grads, and the weights
    cotangent (None when unweighted).
    """
    with scope("emb_bwd"):
        B, T, H = indices.shape
        R = pool.shape[0]
        idx = indices.astype(jnp.int32)
        if plan.offsets is not None:
            idx = idx + jnp.asarray(plan.offsets, jnp.int32)[None, :, None]
        flat_idx = idx.reshape(-1)
        store_idx = flat_idx if plan.layout is None else \
            translate_rows(flat_idx, plan.layout)
        w = None if weights is None else \
            weights.astype(jnp.float32).reshape(B, T, H)
        g_rows, dw = _row_cotangents(pool, store_idx, w, g.astype(jnp.float32),
                                     combiner=plan.combiner, B=B, T=T, H=H)
        rows, vals = dedupe_rows(store_idx, g_rows.reshape(B * T * H, -1), R)
        dweights = None if dw is None else dw.reshape(weights.shape).astype(
            weights.dtype)
        return rows, vals, dweights


# ---------------------------------------------------------------------------
# custom VJP: forward dispatches impls, backward is dedupe + one scatter-add
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused(pool, flat_idx, weights, meta):
    with scope("emb_fwd"):
        combiner, B, T, H, method, block_b, hot, layout, mesh = meta
        if method in ("pallas", "interpret"):
            if hot is not None:
                offsets, table_hot = hot
                # the cache is gathered from `pool` *inside* the VJP-wrapped
                # forward, so gradients through cached rows flow to the pool
                # exactly like uncached ones (row ids are preserved). Flat
                # layout: the hot prefixes are contiguous, one slice per table.
                # Padded layout: a table's prefix may straddle a shard boundary,
                # so gather the statically-translated row ids instead.
                if layout is None:
                    cache = jnp.concatenate([
                        jax.lax.slice_in_dim(pool, o, o + k)
                        for o, k in zip(offsets, table_hot) if k > 0])
                else:
                    ids = translate_rows_np(hot_row_ids(offsets, table_hot),
                                            layout)
                    cache = jnp.take(pool, jnp.asarray(ids), axis=0)
                # hot detection speaks FLAT local ids (the placement contract);
                # encode first, then rebase only the cold (non-negative) entries
                # into the padded space
                enc, _ = encode_hot_indices(flat_idx.reshape(B, T, H),
                                            offsets, table_hot)
                if layout is not None:
                    enc = jnp.where(enc < 0, enc,
                                    translate_rows(jnp.maximum(enc, 0), layout))
            else:
                cache = None
                enc = flat_idx.reshape(B, T, H)
                if layout is not None:
                    enc = translate_rows(enc, layout)
            fwd = functools.partial(_pallas_forward, T=T, H=H,
                                    combiner=combiner, block_b=block_b,
                                    interpret=(method == "interpret"))
            if mesh is not None:
                # GSPMD cannot partition a Mosaic kernel: each device runs it on
                # its own batch shard against the whole pool, which is
                # all-gathered (replicated) around it
                mesh, batch_axes = mesh
                rows = P(batch_axes or None)
                fwd = jax.shard_map(fwd, mesh=mesh,
                                    in_specs=(P(), rows, rows, P()),
                                    out_specs=rows, check_vma=False)
            return fwd(pool, enc, weights, cache)
        # XLA path: under frequency-packed placement the hot prefixes are already
        # contiguous in the pool and stay hardware-cache-resident; a separate
        # cache gather would only add traffic, so the plain fused take IS the
        # cached path here (bit-identical by construction).
        idx = flat_idx if layout is None else translate_rows(flat_idx, layout)
        return _xla_forward(pool, idx, weights, B=B, T=T, H=H,
                            combiner=combiner)


def _fused_fwd(pool, flat_idx, weights, meta):
    return _fused(pool, flat_idx, weights, meta), (pool, flat_idx, weights)


def _fused_bwd(meta, res, g):
    with scope("emb_bwd"):
        combiner, B, T, H, method, block_b, hot, layout, _ = meta
        pool, flat_idx, weights = res
        R, D = pool.shape
        # gradients deposit into the physical store's row space: flat rows when
        # the pool is unpadded, padded rows under a layout (whose padding slots
        # are never addressed, so they receive exactly zero)
        store_idx = flat_idx if layout is None else translate_rows(flat_idx, layout)
        w = None if weights is None else weights.reshape(B, T, H)
        g_rows, dw = _row_cotangents(pool, store_idx, w, g.astype(jnp.float32),
                                     combiner=combiner, B=B, T=T, H=H)

        # Sparse-gradient aggregation through the explicit dedupe+segment step
        # shared with ``sparse_row_grads``: one scatter of the deduped values
        # reproduces the old per-index segment_sum (and makes the dense path the
        # bit-exact oracle for the fused row-wise update, which consumes the
        # same (rows, vals) pair).
        rows, vals = dedupe_rows(store_idx, g_rows.reshape(B * T * H, D), R)
        dpool = jnp.zeros((R, D), jnp.float32).at[rows].add(vals)
        dweights = None if dw is None else dw.reshape(weights.shape).astype(
            weights.dtype)
        return dpool.astype(pool.dtype), None, dweights


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def fused_embedding_bag(pool: jnp.ndarray, indices: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None, *,
                        offsets: Optional[Sequence[int]] = None,
                        combiner: str = "sum", method: str = "xla",
                        block_b: int = 8,
                        table_hot: Optional[Sequence[int]] = None,
                        layout=None, plan=None, mesh=None,
                        batch_axes: Sequence[str] = ()) -> jnp.ndarray:
    """Pool per-table embedding bags for all tables in one fused call.

    Args:
      pool:      row store for every table. Flat layout (``layout=None``):
                 the (R, D) row-concatenation of all tables, R =
                 ``sum(table_rows)``. Padded layout: the
                 (n_ps * max_range, D) flattening of the physically-sharded
                 ``(n_ps, max_range, D)`` store (padding rows zero).
      indices:   (B, T, H) per-table-local (or, with ``offsets=None``, global
                 flat-pool) int rows; T tables, H lookups ("hot" axis) per
                 bag. Always expressed in the FLAT id space — the engine
                 translates into the padded space itself.
      weights:   optional (B, T, H) per-lookup scalars, applied before the
                 combiner (so weighted mean/max match the unfused oracle).
      offsets:   static per-table flat-pool row offsets; ``None`` means
                 indices are already global flat-pool rows.
      combiner:  "sum" | "mean" | "max".
      method:    "xla" (one take + reduce), "pallas", or "interpret".
      block_b:   batch rows per Pallas grid step.
      table_hot: optional per-table counts of frequency-packed hot rows — the
                 leading ``table_hot[t]`` local rows of table ``t`` are served
                 from the VMEM-resident hot-row cache on the Pallas path
                 instead of an HBM DMA. Requires ``offsets`` when ``T > 1``.
                 Numerics are identical with or without it.
      layout:    optional ``repro.sharding.policy.PaddedLayout`` describing
                 the padded physical placement of ``pool``. Hashable and
                 jit-static (rides in the custom-VJP meta): changing the
                 physical layout recompiles, as a live re-plan requires.
                 Numerics are bit-identical to the flat layout.
      plan:      optional ``repro.sharding.policy.EmbeddingPlan`` supplying
                 ``offsets``/``combiner``/``block_b``/``table_hot``/``layout``
                 in one hashable value (overrides the loose kwargs; the
                 preferred form — see ``kernels/ops.py``).
      mesh:      the device mesh the calling step is partitioned over, if
                 any; the Pallas kernel then runs on each device's shard of
                 the batch with the pool replicated (a Mosaic kernel is not
                 partitioned by GSPMD).
      batch_axes: the mesh axes the batch dim is split over (with ``mesh``).

    Returns (B, T, D); gradients flow to ``pool`` (sparse scatter-add of
    the deduped row cotangents, into padded rows under ``layout``) and
    ``weights``.
    """
    if plan is not None:
        offsets, combiner, block_b = plan.offsets, plan.combiner, plan.block_b
        table_hot, layout = plan.table_hot, plan.layout
    assert combiner in COMBINERS, combiner
    assert indices.ndim == 3, f"indices must be (B, T, H), got {indices.shape}"
    B, T, H = indices.shape
    if layout is not None:
        assert pool.shape[0] == layout.padded_rows, \
            (pool.shape, layout.padded_rows)
    idx = indices.astype(jnp.int32)
    if offsets is not None:
        off = jnp.asarray(offsets, jnp.int32)
        assert off.shape == (T,), (off.shape, T)
        idx = idx + off[None, :, None]
    hot = None
    if table_hot is not None:
        table_hot = tuple(int(k) for k in table_hot)
        assert len(table_hot) == T, (len(table_hot), T)
        if sum(table_hot) > 0:
            offs = tuple(int(o) for o in offsets) if offsets is not None \
                else (0,) * T
            assert offsets is not None or T == 1, \
                "table_hot with T > 1 requires offsets"
            hot = (offs, table_hot)
    flat_idx = idx.reshape(-1)
    w = None if weights is None else weights.astype(jnp.float32)
    spmd = None if mesh is None else (mesh, tuple(batch_axes))
    meta = (combiner, B, T, H, method, max(1, min(block_b, B)), hot, layout,
            spmd)
    return _fused(pool, flat_idx, w, meta)
