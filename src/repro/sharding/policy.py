"""Logical-axis sharding rules → concrete NamedShardings.

The paper's PS architecture maps onto a 2-D/3-D device mesh:

* ``"data"`` (and ``"pod"``) — the *worker* axis: batch/data parallel, FSDP
  parameter sharding (the paper's ``w`` and, across pods, elastic scale-out).
* ``"model"`` — the *parameter-server* axis: embedding rows (vocab), attention
  heads, FFN hidden, experts (the paper's ``p``; embedding tables distributed
  across PSes, §2.1/§4.1). For skewed DLRM traffic the vocab axis carries an
  optional *balanced range plan* (``ShardingPolicy.vocab_ranges``): contiguous
  pooled-row ranges with ~equal access mass per PS, planned by
  ``balanced_vocab_ranges`` and re-planned live by
  ``repro.core.sharding_service.HotTableTracker`` — the placement-time fix
  for the paper's hot-PS problem, replacing blind uniform striping.

Every parameter/activation is annotated with *logical* axis names; per
(arch × shape × mesh) the policy resolves them to mesh axes, handling
non-divisible cases (e.g. 24 query heads on a 16-way model axis) by falling
back to sequence sharding for attention.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.kernels.common import TILE_ROWS

# logical axis vocabulary ----------------------------------------------------
#   batch     activation batch dim
#   qseq      query sequence dim (activations)
#   kvseq     KV-cache sequence dim (decode)
#   heads     attention query heads (params + activations)
#   kv_heads  attention KV heads
#   vocab     embedding-table rows / logits vocab dim
#   fsdp      weight dim sharded ZeRO-style over the data axis
#   tp        weight hidden dim sharded over the model axis (ffn/d_inner/lru)
#   expert    MoE expert dim
#   (None)    replicated

_STATE = threading.local()


@dataclass(frozen=True)
class ShardingPolicy:
    """Resolved logical-axis rules for one (arch × shape × mesh) cell.

    ``rules`` maps each logical axis name to the mesh axes it shards over.
    ``vocab_ranges``, when set, is the frequency-balanced contiguous
    pooled-row plan for the PS ("vocab") axis — the paper's hot-PS fix.
    GSPMD NamedShardings can only express equal splits, so the ranges ride
    on the policy for every layer that *places* rows (the replan
    orchestrator, PS cost/placement models, benchmarks), while ``spec``
    keeps producing the equal-split approximation for compiled collectives.
    """
    mesh: Optional[Mesh]
    rules: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    vocab_ranges: Optional[Tuple[Tuple[int, int], ...]] = None

    # -- resolution ---------------------------------------------------------
    def spec(self, names: Sequence[Optional[str]]) -> P:
        """Resolve logical axis names to a concrete ``PartitionSpec``.

        Args:
          names: one logical axis name (or None = replicated) per array dim.

        Returns a ``PartitionSpec`` where each mesh axis is used at most once
        (duplicates later in ``names`` fall back to replication).
        """
        parts = []
        used = set()
        for n in names:
            axes = tuple(a for a in self.rules.get(n, ()) if a not in used) if n else ()
            used.update(axes)
            if len(axes) == 0:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(axes)
        return P(*parts)

    def sharding(self, names: Sequence[Optional[str]]) -> Optional[NamedSharding]:
        """``spec(names)`` bound to this policy's mesh (None without a mesh)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(names))

    def axis_size(self, logical: str) -> int:
        """Number of shards a logical axis is split into (1 = replicated)."""
        if self.mesh is None:
            return 1
        n = 1
        for a in self.rules.get(logical, ()):
            n *= self.mesh.shape[a]
        return n

    # -- balanced PS row placement (hot-PS fix, §2.1/Fig 12) ----------------
    def with_vocab_ranges(
            self, ranges: Optional[Sequence[Tuple[int, int]]]) -> "ShardingPolicy":
        """Copy of this policy carrying a balanced vocab-range plan.

        Args:
          ranges: contiguous pooled-row ``(start, end)`` per PS shard (e.g.
                  from ``balanced_vocab_ranges`` or a ``ReplanDecision``), or
                  None to drop back to uniform striping.
        """
        if ranges is None:
            return replace(self, vocab_ranges=None)
        return replace(self, vocab_ranges=tuple(
            (int(s), int(e)) for s, e in ranges))

    def ps_row_ranges(self, total_rows: int) -> List[Tuple[int, int]]:
        """Pooled-row range each PS shard owns under this policy.

        The balanced plan when one is attached, otherwise the uniform
        striping the "vocab" rule implies (``axis_size("vocab")`` equal
        contiguous splits — what GSPMD physically materializes).

        Args:
          total_rows: pooled embedding row count (``sum(table_rows)``).

        Returns one ``(start, end)`` half-open range per PS shard.
        """
        if self.vocab_ranges is not None:
            return list(self.vocab_ranges)
        return uniform_vocab_ranges(total_rows, self.axis_size("vocab"))


NULL_POLICY = ShardingPolicy(mesh=None, rules={})


def current_policy() -> ShardingPolicy:
    """The thread-active policy installed by ``use_policy`` (or NULL_POLICY)."""
    return getattr(_STATE, "policy", NULL_POLICY)


@contextlib.contextmanager
def use_policy(policy: ShardingPolicy):
    """Context manager installing ``policy`` as the thread-active policy."""
    prev = getattr(_STATE, "policy", NULL_POLICY)
    _STATE.policy = policy
    try:
        yield policy
    finally:
        _STATE.policy = prev


def constrain(x, names: Sequence[Optional[str]]):
    """with_sharding_constraint under the active policy (no-op without mesh)."""
    pol = current_policy()
    if pol.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, pol.sharding(names))


def logical_spec(tree, spec_tree, policy: Optional[ShardingPolicy] = None):
    """Map a logical-axis spec tree to NamedShardings mirroring ``tree``."""
    pol = policy or current_policy()
    return jax.tree.map(
        lambda names: pol.sharding(names),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x),
    )


# ---------------------------------------------------------------------------
def make_policy(mesh: Optional[Mesh], cfg: ModelConfig, shape: ShapeConfig,
                overrides: Optional[Dict[str, Tuple[str, ...]]] = None) -> ShardingPolicy:
    """Resolve logical-axis rules for one (arch × shape × mesh) cell."""
    if mesh is None:
        return NULL_POLICY
    axes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    model_ax = ("model",) if "model" in axes else ()
    model_size = axes.get("model", 1)
    data_size = 1
    for a in data_axes:
        data_size *= axes[a]

    rules: Dict[str, Tuple[str, ...]] = {
        "vocab": model_ax,
        "fsdp": ("data",) if "data" in axes else (),
        "tp": model_ax,
        "ffn": model_ax,
    }

    # Decode is weight-streaming-bound: if the bf16 params fit in HBM when
    # sharded over "model" alone, replicate across "data" (no per-step FSDP
    # all-gather; each chip reads weights from local HBM). Large MoE (e.g.
    # mixtral-8x22b) keeps FSDP sharding and streams weights over ICI.
    if shape.kind == "decode":
        params_bf16 = cfg.param_count() * 2.0
        if params_bf16 / max(model_size, 1) <= 12e9:
            rules["fsdp"] = ()

    # --- batch -------------------------------------------------------------
    if shape.global_batch % max(data_size, 1) == 0 and shape.global_batch >= data_size:
        rules["batch"] = data_axes
    else:
        # e.g. long_500k batch=1: free the data axis for sequence sharding
        rules["batch"] = ()

    # --- attention heads vs sequence sharding ------------------------------
    heads_ok = cfg.n_heads > 0 and cfg.n_heads % max(model_size, 1) == 0
    kv_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % max(model_size, 1) == 0
    rules["heads"] = model_ax if heads_ok else ()
    rules["kv_heads"] = model_ax if (heads_ok and kv_ok) else ()
    # when heads cannot shard, shard the query sequence over the model axis
    rules["qseq"] = () if heads_ok else model_ax

    # --- KV-cache sequence (decode) -----------------------------------------
    rules["kvseq"] = ()
    if shape.kind == "decode":
        if rules["batch"] == ():
            # flash-decode: single long sequence, cache sharded over data axes
            rules["kvseq"] = data_axes
        elif not kv_ok:
            # kv heads don't divide the model axis: shard the cache sequence
            # over "model" instead (distributed softmax); q heads replicated
            rules["kvseq"] = model_ax
            rules["heads"] = ()
            rules["kv_heads"] = ()

    # --- experts -------------------------------------------------------------
    # Expert weights are TP-sharded inside each expert (ffn dim over "model")
    # rather than placing the expert dim on the mesh: dispatch then stays
    # fully shard-local (no all-to-all), and weights stream via the FSDP
    # all-gather — cheaper than moving token activations for these configs
    # (tokens·k·d  >>  expert param bytes per layer). Measured on
    # granite-moe: expert-dim sharding + global dispatch cost 245 GB/step of
    # collectives; this layout costs ~8 GB/step.
    rules["expert"] = ()
    rules["expert_ffn"] = model_ax

    # --- ssm / recurrent hidden ----------------------------------------------
    di = cfg.d_inner if cfg.ssm_state else (cfg.lru_width or 0)
    rules["inner"] = model_ax if (di and di % max(model_size, 1) == 0) else ()
    nh_ssm = cfg.ssm_nheads if cfg.ssm_state else 0
    rules["ssm_heads"] = model_ax if (nh_ssm and nh_ssm % max(model_size, 1) == 0) else ()

    if overrides:
        rules.update(overrides)
    return ShardingPolicy(mesh=mesh, rules=rules)


def make_dlrm_policy(mesh: Optional[Mesh],
                     vocab_ranges: Optional[Sequence[Tuple[int, int]]] = None
                     ) -> ShardingPolicy:
    """Policy for the paper's own DLRM workloads (pooled tables over PSes).

    The pooled embedding rows ("vocab") shard over the "model" axis — the PS
    fleet of §2.1 — and activations ("batch") over the data axes. A balanced
    ``vocab_ranges`` plan (from ``balanced_vocab_ranges`` or a live
    ``ReplanDecision``) rides on the policy so every placement-aware layer
    sees frequency-balanced PS ranges instead of uniform striping.

    Args:
      mesh:         device mesh (None = single host, no sharding).
      vocab_ranges: optional balanced contiguous pooled-row plan.

    Returns the resolved ``ShardingPolicy``.
    """
    if mesh is None:
        return NULL_POLICY.with_vocab_ranges(vocab_ranges)
    axes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    rules: Dict[str, Tuple[str, ...]] = {
        "vocab": ("model",) if "model" in axes else (),
        "batch": data_axes,
    }
    return ShardingPolicy(mesh=mesh, rules=rules).with_vocab_ranges(vocab_ranges)


# ---------------------------------------------------------------------------
# Frequency-aware pooled-row placement (RecShard-style, feeds the fused
# embedding engine's hot-row cache and the PS row-range assignment)
# ---------------------------------------------------------------------------
def pack_hot_ranges(counts: np.ndarray, table_rows: Sequence[int],
                    budget: int) -> Tuple[int, ...]:
    """Per-table hot-prefix sizes from pooled row-access counts.

    Picks the globally most-frequent ``budget`` rows and returns how many of
    them land in each table — the ``table_hot`` argument of the fused
    embedding engine. Assumes rows are frequency-packed within each table
    (hot ids lead; see ``frequency_permutation`` for hashed layouts), so the
    returned prefix of table ``t`` covers exactly its selected hot rows.
    RecShard's statistical tiering applied to the VMEM cache (paper §2.1's
    lookup hot spot).

    Args:
      counts:     (sum(table_rows),) pooled per-row access counts.
      table_rows: per-table row counts (defines table boundaries).
      budget:     total cache rows to plan (clipped to the pool size).

    Returns per-table hot-prefix sizes; never caches never-touched rows, so
    the sizes may sum to less than ``budget``.
    """
    counts = np.asarray(counts)
    table_rows = tuple(int(r) for r in table_rows)
    assert counts.shape == (sum(table_rows),), (counts.shape, sum(table_rows))
    budget = min(int(budget), counts.size)
    if budget <= 0:
        return (0,) * len(table_rows)
    top = np.argpartition(counts, -budget)[-budget:]
    top = top[counts[top] > 0]              # never cache rows never touched
    bounds = np.cumsum((0,) + table_rows)
    per_table = np.histogram(top, bins=bounds)[0]
    return tuple(int(k) for k in per_table)


def frequency_permutation(counts: np.ndarray,
                          table_rows: Sequence[int]) -> np.ndarray:
    """Per-table remap old-local-id -> frequency rank (hot rows first).

    ``perm[global_row] = new_global_row`` keeps every row inside its own
    table but reorders each table by descending access count, producing the
    frequency-packed layout `pack_hot_ranges` and the hot-row cache assume.
    Apply it to the pool rows once at (re)build time and to incoming ids at
    ingestion — the remap itself never sits on the training hot path. Live
    re-plans re-derive it from decayed counts and apply it with
    ``repro.train.replan.permute_train_state`` (bit-exact, §5.2-style
    restore onto the new layout).

    Args:
      counts:     (sum(table_rows),) pooled per-row access counts.
      table_rows: per-table row counts (permutation never crosses tables).

    Returns the (sum(table_rows),) int64 permutation, stable within ties.
    """
    counts = np.asarray(counts)
    perm = np.empty((counts.size,), np.int64)
    off = 0
    for rows in table_rows:
        rows = int(rows)
        order = np.argsort(-counts[off:off + rows], kind="stable")
        perm[off + order] = off + np.arange(rows)
        off += rows
    return perm


def uniform_vocab_ranges(total_rows: int, n_shards: int) -> List[Tuple[int, int]]:
    """Equal-size contiguous pooled-row range per PS shard (blind striping).

    The skew-oblivious baseline that ``balanced_vocab_ranges`` replaces —
    and what GSPMD equal splits physically materialize. Kept as the single
    source of the striping formula for the policy, the hot tracker's initial
    plan, and the benchmarks' baseline rows.

    Args:
      total_rows: pooled embedding row count.
      n_shards:   PS shard count.

    Returns ``n_shards`` half-open ``(start, end)`` ranges covering
    ``[0, total_rows)``.
    """
    n = max(1, int(n_shards))
    return [(i * total_rows // n, (i + 1) * total_rows // n) for i in range(n)]


def balanced_vocab_ranges(counts: np.ndarray,
                          n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous pooled-row ranges with ~equal access mass per PS shard.

    Replaces uniform row striping over the "vocab" axis: a uniform split
    sends nearly all the skewed traffic to whichever shard holds the hot
    head, while equal-mass boundaries (inverse-CDF of the access histogram)
    keep per-PS lookup load balanced — the paper's hot-PS mitigation, applied
    at placement time instead of after the fact. Attach the result to a
    ``ShardingPolicy`` via ``with_vocab_ranges`` so the sharded training path
    carries the plan alongside its NamedShardings.

    Args:
      counts:   (R,) pooled per-row access counts (zeros = uniform split).
      n_shards: PS shard count.

    Returns ``n_shards`` contiguous half-open ``(start, end)`` ranges
    covering ``[0, R)``; boundary rows go to whichever side leaves the left
    shard's mass closer to its equal-mass target.
    """
    counts = np.asarray(counts, np.float64)
    n_shards = max(1, int(n_shards))
    total = counts.sum()
    if total <= 0:                           # no signal: uniform striping
        edges = np.linspace(0, counts.size, n_shards + 1).astype(np.int64)
    else:
        cum = np.cumsum(counts)
        targets = total * np.arange(1, n_shards) / n_shards
        idx = np.searchsorted(cum, targets)
        # the target falls inside row `idx`: put that row on whichever side
        # leaves the left shard's mass closer to its target
        left = np.where(idx > 0, cum[np.maximum(idx - 1, 0)], 0.0)
        inner = np.where(np.abs(left - targets) <= np.abs(cum[idx] - targets),
                         idx, idx + 1)
        edges = np.concatenate(([0], inner, [counts.size]))
        edges = np.maximum.accumulate(np.clip(edges, 0, counts.size))
    return [(int(edges[i]), int(edges[i + 1])) for i in range(n_shards)]


# ---------------------------------------------------------------------------
# Padded physical PS shards: make the balanced plan what GSPMD places
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PaddedLayout:
    """Physical padded ``(n_ps, max_range, D)`` placement of a range plan.

    GSPMD ``NamedSharding``s can only express *equal* splits of an array
    axis, so a flat ``(sum(rows), D)`` pool sharded over the PS axis always
    materializes uniform striping — a balanced ``vocab_ranges`` plan riding
    on the policy stays advisory. This layout makes the plan physical:
    shard ``p`` owns exactly ``ranges[p]``'s rows, stored at
    ``padded[p, 0:size_p]`` and tail-padded with zero rows to ``max_range``,
    a whole number of TPU tiles of rows.
    A ``NamedSharding`` of ``P("model", None, None)`` over the leading axis
    then places *exactly* the balanced plan on the mesh — physically-unequal
    PS shards via an equal split of the padded leading axis.

    Addressing: a flat pooled row ``g`` in ``ranges[p] = (start, end)``
    lives at shard ``p``, slot ``g - start``; equivalently at *padded row*
    ``p * max_range + (g - start)`` of the ``(n_ps * max_range, D)`` reshape
    the fused embedding engine consumes. Padded slots hold zeros, are never
    addressed by a translated index, and therefore contribute nothing to
    pooling and receive zero gradient.

    The dataclass is frozen and tuple-only, hence hashable — it rides in
    jit-static metadata (``fused_embedding_bag``'s custom-VJP meta) and
    recompiles the step exactly when the physical layout changes.
    """
    ranges: Tuple[Tuple[int, int], ...]

    # -- static geometry ----------------------------------------------------
    @property
    def n_ps(self) -> int:
        """PS shard count (leading axis of the padded pool)."""
        return len(self.ranges)

    @property
    def max_range(self) -> int:
        """Rows per physical shard: the largest range rounded up to whole
        ``TILE_ROWS`` (floor one tile).

        On a TPU the pool's row axis is the lane-tiled axis, so a shard
        that is not a whole number of tiles turns every reshape of the pool
        (the ``(R, D)`` view, its lane-packed lines, the gradient's way
        back) into an element loop over the whole pool.
        """
        largest = max(e - s for s, e in self.ranges)
        return max(1, -(-largest // TILE_ROWS)) * TILE_ROWS

    @property
    def total_rows(self) -> int:
        """Real pooled rows covered (``sum(table_rows)`` of the job)."""
        return self.ranges[-1][1]

    @property
    def padded_rows(self) -> int:
        """Rows of the ``(n_ps * max_range, D)`` flattened padded pool."""
        return self.n_ps * self.max_range

    @property
    def shard_starts(self) -> Tuple[int, ...]:
        """Flat pooled row where each shard's range begins."""
        return tuple(s for s, _ in self.ranges)

    @property
    def shard_sizes(self) -> Tuple[int, ...]:
        """Real (unpadded) rows each shard physically owns."""
        return tuple(e - s for s, e in self.ranges)

    # -- row translation ----------------------------------------------------
    def shard_slot(self, rows) -> Tuple[np.ndarray, np.ndarray]:
        """Flat pooled rows → ``(shard, slot)`` coordinates.

        Args:
          rows: int array-like of flat pooled row ids in ``[0, total_rows)``.

        Returns ``(shard, slot)`` int64 arrays: ``padded[shard, slot]`` holds
        each row. Empty shards are never selected (their start equals the
        next shard's, and the rightmost match wins).
        """
        rows = np.asarray(rows, np.int64)
        starts = np.asarray(self.shard_starts, np.int64)
        shard = np.clip(np.searchsorted(starts, rows, side="right") - 1,
                        0, self.n_ps - 1)
        return shard, rows - starts[shard]

    def flat_to_padded(self, rows) -> np.ndarray:
        """Flat pooled rows → rows of the flattened padded pool.

        ``flat_to_padded(g) == shard * max_range + slot``; the inverse of
        ``padded_to_flat`` on real (non-padding) rows.
        """
        shard, slot = self.shard_slot(rows)
        return shard * self.max_range + slot

    def padded_to_flat(self, padded) -> np.ndarray:
        """Rows of the flattened padded pool → flat pooled rows.

        Args:
          padded: int array-like of padded row ids; callers must only pass
                  real rows (``padding_mask`` is True), padding slots map
                  onto whatever flat row the arithmetic lands on.
        """
        padded = np.asarray(padded, np.int64)
        shard, slot = padded // self.max_range, padded % self.max_range
        starts = np.asarray(self.shard_starts, np.int64)
        return starts[shard] + slot

    def row_translation(self) -> np.ndarray:
        """The full ``(total_rows,)`` flat → padded row map (int64).

        Memoized on the instance (read-only array): pad/unpad walk several
        pooled leaves per checkpoint or re-plan, and the map is O(rows) to
        build — compute it once per layout, not once per leaf. The cache
        rides outside the dataclass fields, so eq/hash are untouched.
        """
        cached = self.__dict__.get("_row_translation")
        if cached is None:
            cached = self.flat_to_padded(
                np.arange(self.total_rows, dtype=np.int64))
            cached.setflags(write=False)
            object.__setattr__(self, "_row_translation", cached)
        return cached

    def padding_mask(self) -> np.ndarray:
        """(n_ps, max_range) bool mask; True where a real row lives.

        ``mask.sum(axis=1)`` equals ``shard_sizes`` — the materialized
        per-shard row counts the Fig 12 bench checks against the plan.
        """
        sizes = np.asarray(self.shard_sizes, np.int64)[:, None]
        return np.arange(self.max_range, dtype=np.int64)[None, :] < sizes

    # -- array movement -----------------------------------------------------
    def pad_rows(self, flat):
        """(total_rows, ...) flat row array → (n_ps, max_range, ...) padded.

        Real rows are scattered to their (shard, slot); padding slots are
        zeros. Values move, never change — the round trip through
        ``unpad_rows`` is bit-exact.
        """
        import jax.numpy as jnp
        flat = jnp.asarray(flat)
        assert flat.shape[0] == self.total_rows, (flat.shape, self.total_rows)
        out = jnp.zeros((self.padded_rows,) + flat.shape[1:], flat.dtype)
        out = out.at[jnp.asarray(self.row_translation())].set(flat)
        return out.reshape((self.n_ps, self.max_range) + flat.shape[1:])

    def unpad_rows(self, padded):
        """(n_ps, max_range, ...) padded row array → (total_rows, ...) flat."""
        import jax.numpy as jnp
        padded = jnp.asarray(padded)
        assert padded.shape[:2] == (self.n_ps, self.max_range), padded.shape
        flat2d = padded.reshape((self.padded_rows,) + padded.shape[2:])
        return jnp.take(flat2d, jnp.asarray(self.row_translation()), axis=0)


@dataclass(frozen=True)
class EmbeddingPlan:
    """The complete static plan one fused embedding call compiles against.

    Collapses the kwargs that had accreted on ``fused_embedding_bag``
    (``offsets``, ``combiner``, ``block_b``, ``table_hot``, ``layout``) plus
    the fused sparse-update knobs into one frozen, hashable value — the
    single object threaded from the launcher through the trainer, the
    re-planner and ``kernels/ops.py`` down to the kernel's jit-static
    custom-VJP metadata. Hashability means a plan change (a live re-plan
    swapping ``table_hot``/``layout``) recompiles the step exactly once,
    and two calls with equal plans share a compilation cache entry.

    Fields:
      offsets:       static per-table flat-pool row offsets
                     (``kernels.fused_embedding.table_offsets`` output);
                     ``None`` means indices are already global flat rows.
      combiner:      "sum" | "mean" | "max" bag pooling.
      block_b:       batch rows per Pallas grid step (forward kernel).
      table_hot:     per-table hot-prefix sizes for the VMEM hot-row cache;
                     ``None``/all-zero disables the cache.
      layout:        optional ``PaddedLayout`` — the padded physical
                     placement of the pool this plan addresses.
      sparse_update: opt the training step into the fused sparse backward +
                     row-wise optimizer update (``Optimizer.update_rows``)
                     instead of the dense ``segment_sum`` gradient path.
      update_block:  rows per grid step of the fused row-update kernel.
    """
    offsets: Optional[Tuple[int, ...]] = None
    combiner: str = "sum"
    block_b: int = 8
    table_hot: Optional[Tuple[int, ...]] = None
    layout: Optional[PaddedLayout] = None
    sparse_update: bool = False
    update_block: int = 8

    def __post_init__(self) -> None:
        if self.combiner not in ("sum", "mean", "max"):
            raise ValueError(f"unknown combiner: {self.combiner!r}")
        if self.offsets is not None:
            object.__setattr__(
                self, "offsets", tuple(int(o) for o in self.offsets))
        if self.table_hot is not None:
            object.__setattr__(
                self, "table_hot", tuple(int(k) for k in self.table_hot))
        object.__setattr__(self, "block_b", int(self.block_b))
        object.__setattr__(self, "update_block", int(self.update_block))

    @property
    def n_tables(self) -> Optional[int]:
        """Table count the plan describes (``None`` when offsets are unset)."""
        return None if self.offsets is None else len(self.offsets)

    def with_combiner(self, combiner: str) -> "EmbeddingPlan":
        """Same plan, different bag pooling (the wide tower's sum view)."""
        return replace(self, combiner=combiner)

    def with_replan(self, table_hot: Optional[Sequence[int]],
                    layout: Optional[PaddedLayout]) -> "EmbeddingPlan":
        """The plan a live re-plan recompiles with: new cache + placement."""
        hot = None if table_hot is None else tuple(int(k) for k in table_hot)
        return replace(self, table_hot=hot, layout=layout)


def padded_layout_for_ranges(
        ranges: Sequence[Tuple[int, int]]) -> PaddedLayout:
    """Plan the physical padded pool layout for a contiguous range plan.

    Args:
      ranges: one half-open ``(start, end)`` flat pooled-row range per PS
              shard, contiguous from 0 (``balanced_vocab_ranges`` /
              ``uniform_vocab_ranges`` output, or a ``ReplanDecision``'s
              ``vocab_ranges``). Empty ranges are allowed — that shard is
              a fully-padded tail of zeros.

    Returns the validated ``PaddedLayout``.
    """
    rs = tuple((int(s), int(e)) for s, e in ranges)
    assert rs, "at least one shard range required"
    assert rs[0][0] == 0, f"ranges must start at 0, got {rs[0]}"
    for (s, e), (s2, _) in zip(rs, rs[1:]):
        assert e >= s and s2 == e, f"ranges must be contiguous: {rs}"
    assert rs[-1][1] >= rs[-1][0], rs[-1]
    return PaddedLayout(ranges=rs)


def placement_imbalance(counts: np.ndarray,
                        ranges: Sequence[Tuple[int, int]]) -> float:
    """max/mean per-shard access mass (1.0 = perfectly balanced).

    The hot-PS metric of Fig 12 and the live re-plan trigger quantity
    (``HotTableTracker.trigger`` compares against this).

    Args:
      counts: (R,) pooled per-row access counts.
      ranges: one ``(start, end)`` pooled-row range per PS shard.

    Returns the max/mean per-shard lookup load (1.0 when no mass observed).
    """
    counts = np.asarray(counts, np.float64)
    loads = np.array([counts[s:e].sum() for s, e in ranges])
    mean = loads.mean()
    return float(loads.max() / mean) if mean > 0 else 1.0
