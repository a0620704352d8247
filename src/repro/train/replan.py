"""Live embedding re-planning: observed skew → re-sharded, recompiled step.

DLRover-RM's core claim is *dynamic* adjustment (§4–§5): the job master
watches a running job and re-allocates mid-flight. For embeddings the thing
worth re-allocating is row placement — which rows sit in the fused engine's
VMEM hot-row cache and how the pooled rows split across PS shards — because
skew drifts (RecShard / MTrainS): yesterday's hot head is today's lukewarm
middle, and a plan frozen at compile time re-creates the hot-PS problem it
was built to solve.

This module closes the loop around ``HotTableTracker``'s ``ReplanDecision``:

    observe (decayed rolling counts, worker-side ids)
      → trigger (imbalance over threshold, hysteresis)
        → snapshot   (FlashCheckpoint, old layout — §5.2 flash checkpoint)
        → permute    (pooled rows + optimizer moments, within-table only)
        → re-plan    (balanced vocab ranges onto the ShardingPolicy,
                      measured ``table_hot`` prefixes for the VMEM cache)
        → recompile  (``make_dlrm_train_step(..., table_hot=new plan)``)
        → remap      (incoming ids, off the hot path, composable)

Everything is **bit-exact**: a permutation gathers identical row values, ids
are remapped consistently, and the backward ``segment_sum`` sees the same
per-row contributions in the same flat order — so the resumed step's forward
loss equals the pre-replan checkpoint's to the last ulp (test_replan.py
asserts this), and an OLD-plan checkpoint restores losslessly onto a NEW
plan via ``restore_on_plan`` / ``elastic.resume_dlrm_on_mesh``.

Padded physical shards ride the same loop: on a job running the
``(n_ps, max_range, D)`` padded pool (``--padded-shards``), a re-plan's new
balanced ranges imply a new *physical* layout, so ``apply_replan`` unpads
the state to the canonical flat row space, permutes there, and re-pads onto
``padded_layout_for_ranges(decision.vocab_ranges)`` — GSPMD then
materializes exactly the new plan. ``pad_train_state`` /
``unpad_train_state`` are the bit-exact movers; checkpoints always store
the flat order (see ``save_with_layout``), making every blob restorable
onto any layout and shard count. ``docs/EMBEDDING_LAYOUT.md`` is the
authoritative walkthrough of the id spaces and their lifecycles.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dlrm_models import DLRMConfig
from repro.core.flash_checkpoint import FlashCheckpoint
from repro.core.sharding_service import ReplanDecision
from repro.kernels.fused_embedding import table_offsets
from repro.sharding.policy import (
    EmbeddingPlan, PaddedLayout, ShardingPolicy, make_dlrm_policy,
    padded_layout_for_ranges, uniform_vocab_ranges,
)
from repro.spans import span
from repro.train import elastic
from repro.train import trainer as trainer_mod
from repro.train.optim import Optimizer


class EmbeddingRemapper:
    """Composable raw-id → current-layout remap (ingestion side of a re-plan).

    The data stream keeps emitting *raw* per-table-local ids; after each
    applied re-plan the pooled rows move, so lookups must go through the
    composed permutation. The remap is a single numpy take per batch on the
    input pipeline — it never touches the jit-compiled training step.
    """

    def __init__(self, table_rows):
        self.table_rows = tuple(int(r) for r in table_rows)
        self.offsets = np.asarray(table_offsets(self.table_rows), np.int64)
        self.total_rows = int(sum(self.table_rows))
        # raw global row -> current layout global row (identity before any plan)
        self.map = np.arange(self.total_rows, dtype=np.int64)
        self.n_plans = 0

    def compose(self, permutation: np.ndarray) -> None:
        """Fold one applied re-plan's layout permutation into the remap.

        Args:
          permutation: ``(total_rows,)`` flat-row map of the applied
                       ``ReplanDecision`` (``perm[old_row] = new_row``).
                       Always expressed in the canonical FLAT space — padded
                       jobs compose the same permutations, since padding is
                       a placement of the flat order, not a reordering.
        """
        self.map = np.asarray(permutation, np.int64)[self.map]
        self.n_plans += 1

    def remap(self, sparse: np.ndarray) -> np.ndarray:
        """(B, T, H) raw per-table-local ids → current-layout local ids.

        Permutations never cross table boundaries, so the result is again a
        valid per-table-local id tensor (same dtype as the input).

        Out-of-range raw ids raise ``ValueError`` naming the offending
        table and its bound — an id past its table's rows would otherwise
        silently index a *neighboring table's* rows after the offset shift
        (or garbage past the pool), corrupting gradients with no error.

        Args:
          sparse: (B, T, H) raw per-table-local int ids from the stream.

        Returns the remapped (B, T, H) local ids under the current layout.
        """
        sparse = np.asarray(sparse)
        rows = np.asarray(self.table_rows, np.int64)
        bad = (sparse < 0) | (sparse.astype(np.int64) >= rows[None, :, None])
        if bad.any():
            b, t, h = (int(i[0]) for i in np.nonzero(bad))
            raise ValueError(
                f"sparse id {int(sparse[b, t, h])} out of range for table "
                f"{t} (rows={int(rows[t])}): raw ids must lie in "
                f"[0, {int(rows[t])}) — refusing to index garbage rows")
        g = sparse.astype(np.int64) + self.offsets[None, :, None]
        return (self.map[g] - self.offsets[None, :, None]).astype(sparse.dtype)

    def remap_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Copy of a criteo-style batch dict with its "sparse" ids remapped."""
        with span("remap"):
            out = dict(batch)
            out["sparse"] = self.remap(batch["sparse"])
            return out


def permute_train_state(state, total_rows: int, permutation: np.ndarray):
    """Move every pooled-row leaf of a DLRM train state to a new layout.

    Applies ``new[perm[i]] = old[i]`` along axis 0 of the embedding-table
    leaves — ``params["tables"]``, the wide part, and their optimizer-state
    mirrors (adagrad accumulators, adam moments), identified by carrying a
    ``tables``/``wide`` path key AND a leading dim of ``total_rows``. Dense
    MLP/cross/CIN leaves and scalars pass through untouched.

    Args:
      state:       {params, opt, step} pytree (host or device arrays).
      total_rows:  ``cfg.total_embedding_rows`` of the job.
      permutation: layout permutation from a ``ReplanDecision``.

    Returns a new state pytree; row *values* are moved, never changed, which
    is what makes re-planning bit-exact.
    """
    inv = jnp.asarray(np.argsort(np.asarray(permutation)))

    def visit(path, leaf):
        keys = {p.key for p in path if isinstance(p, jax.tree_util.DictKey)}
        if not ({"tables", "wide"} & keys):
            return leaf
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == total_rows:
            return jnp.take(jnp.asarray(leaf), inv, axis=0)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, state)


def _map_pooled_leaves(state, match, move):
    """Apply ``move`` to every pooled-row leaf of a DLRM train state.

    Shared walker for pad/unpad: a leaf qualifies when its path carries a
    ``tables``/``wide`` dict key AND ``match(leaf)`` accepts its shape —
    params and their optimizer-state mirrors (adagrad accumulators, adam
    moments) alike. Everything else passes through untouched.
    """
    def visit(path, leaf):
        keys = {p.key for p in path if isinstance(p, jax.tree_util.DictKey)}
        if ({"tables", "wide"} & keys) and match(leaf):
            return move(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, state)


def pad_train_state(state, total_rows: int, layout: PaddedLayout):
    """Flat-layout DLRM train state → padded physical layout.

    Every pooled-row leaf — ``params["tables"]``, the wide part, their
    optimizer moments — of shape ``(total_rows, ...)`` is scattered to
    ``(n_ps, max_range, ...)`` per ``layout`` (padding slots zero). Values
    move, never change: ``unpad_train_state`` inverts this bit-exactly.

    Args:
      state:      {params, opt, step} pytree on the flat layout.
      total_rows: ``cfg.total_embedding_rows`` of the job.
      layout:     target physical layout.

    Returns the padded state pytree.
    """
    return _map_pooled_leaves(
        state,
        lambda leaf: getattr(leaf, "ndim", 0) >= 1
        and leaf.shape[0] == total_rows,
        layout.pad_rows)


def unpad_train_state(state, total_rows: int, layout: PaddedLayout):
    """Padded-layout DLRM train state → the canonical flat layout.

    Inverse of ``pad_train_state``: gathers the real rows of every
    ``(n_ps, max_range, ...)`` pooled leaf back into ``(total_rows, ...)``
    order, dropping the padding. Bit-exact.

    Args:
      state:      {params, opt, step} pytree on ``layout``.
      total_rows: ``cfg.total_embedding_rows`` of the job.
      layout:     the layout ``state`` currently lives on.

    Returns the flat state pytree.
    """
    del total_rows  # shape is implied by the layout; kept for symmetry
    return _map_pooled_leaves(
        state,
        lambda leaf: getattr(leaf, "ndim", 0) >= 2
        and leaf.shape[:2] == (layout.n_ps, layout.max_range),
        layout.unpad_rows)


@dataclass
class ReplanResult:
    """Everything the training loop swaps in after an applied re-plan."""
    state: Dict[str, Any]                   # permuted (and re-placed) state
    step_fn: Callable                       # recompiled with the new table_hot
    policy: ShardingPolicy                  # carries the balanced vocab ranges
    decision: ReplanDecision
    layout: Optional[PaddedLayout] = None   # physical layout of `state`
    plan: Optional[EmbeddingPlan] = None    # the plan `step_fn` compiled with


def apply_replan(state, cfg: DLRMConfig, optimizer: Optimizer,
                 decision: ReplanDecision, *,
                 remapper: Optional[EmbeddingRemapper] = None,
                 mesh=None, opt_name: str = "adagrad",
                 grad_compress: bool = False,
                 layout: Optional[PaddedLayout] = None,
                 plan: Optional[EmbeddingPlan] = None) -> ReplanResult:
    """Execute one live re-plan on a running job's state.

    The seamless-migration recipe of §5.2 applied to row placement: permute
    the pooled rows and their optimizer moments to the decision's
    frequency-packed layout, attach the balanced vocab ranges to the
    sharding policy (re-placing the state when a mesh is present), and
    recompile the train step with the measured ``table_hot`` cache plan.
    The caller must also route future batches through ``remapper`` (composed
    here) and call ``tracker.mark_applied(decision)`` so observation follows
    the layout. For crash safety, write a layout-stamped snapshot of the
    OLD state with ``save_with_layout`` *before* calling this (stamping the
    pre-compose map) — ``restore_on_plan`` then resumes it onto the new
    plan bit-exactly; a single blob schema, no format ambiguity.

    On a padded job (``layout`` given), the new balanced ranges imply a NEW
    physical layout (different shard boundaries, possibly a different
    ``max_range``): the state is unpadded to the canonical flat space,
    permuted there, and re-padded onto the layout planned from
    ``decision.vocab_ranges`` — so the compiled shards materialize exactly
    the new plan. Still bit-exact end to end.

    Args:
      state:     live {params, opt, step} pytree (old layout; padded on
                 ``layout`` when one is given).
      cfg:       the DLRM job config.
      optimizer: the job's optimizer (for the recompiled step).
      decision:  an accepted ``HotTableTracker.maybe_replan`` decision.
      remapper:  optional id remapper to compose with the new permutation.
      mesh:      optional device mesh; the permuted state is re-placed under
                 the new policy's shardings.
      opt_name:  optimizer name for state specs ("adagrad", "adam", ...).
      grad_compress: forwarded to the recompiled train step.
      layout:    the padded physical layout ``state`` currently lives on
                 (None = flat). Padded jobs come back padded on the NEW
                 layout (``result.layout``).
      plan:      the ``EmbeddingPlan`` the OLD step was compiled with (None
                 = the config default). The recompiled step runs under
                 ``plan.with_replan(decision.table_hot, new_layout)`` —
                 every other knob (combiner, ``sparse_update``, blocks)
                 carries over, so a fused sparse-update job stays fused
                 across a re-plan. The applied plan rides back on
                 ``result.plan``.

    Returns a ``ReplanResult``; training continues with ``result.state`` and
    ``result.step_fn`` on remapped batches.
    """
    R = cfg.total_embedding_rows
    flat_state = state if layout is None else \
        unpad_train_state(state, R, layout)
    new_state = permute_train_state(flat_state, R, decision.permutation)
    new_layout = None
    if layout is not None:
        new_layout = padded_layout_for_ranges(decision.vocab_ranges)
        new_state = pad_train_state(new_state, R, new_layout)
    if remapper is not None:
        remapper.compose(decision.permutation)
    policy = make_dlrm_policy(mesh, vocab_ranges=decision.vocab_ranges)
    if mesh is not None:
        shardings = elastic.dlrm_state_shardings(cfg, opt_name, policy,
                                                 layout=new_layout)
        new_state = jax.device_put(new_state, shardings)
    base_plan = plan if plan is not None else cfg.embedding_plan()
    new_plan = base_plan.with_replan(decision.table_hot, new_layout)
    step_fn = jax.jit(trainer_mod.make_dlrm_train_step(
        cfg, optimizer, grad_compress=grad_compress, plan=new_plan))
    return ReplanResult(state=new_state, step_fn=step_fn, policy=policy,
                        decision=decision, layout=new_layout, plan=new_plan)


def restore_on_plan(cfg: DLRMConfig, optimizer: Optimizer, opt_name: str,
                    ckpt: FlashCheckpoint, decision: ReplanDecision, *,
                    mesh=None, step: Optional[int] = None,
                    grad_compress: bool = False, padded: bool = False,
                    plan: Optional[EmbeddingPlan] = None
                    ) -> Tuple[Dict[str, Any], int, Callable, ShardingPolicy,
                               EmbeddingRemapper]:
    """Restore an OLD-plan layout-stamped checkpoint onto a NEW plan.

    The elastic-restart half of re-planning: a worker that joins (or a job
    that restarts) after a re-plan only has checkpoints written under the
    previous layout (via ``save_with_layout``). Restoring through the
    decision's permutation yields a state whose forward loss on remapped
    batches is bit-identical to what the old layout would have produced —
    the restored remapper is returned already composed with the decision.

    Args:
      cfg, optimizer, opt_name: the job being resumed.
      ckpt:     flash checkpoint holding the old-layout stamped snapshot.
      decision: the applied re-plan to restore onto.
      mesh:     optional target mesh.
      step:     checkpoint step (None = latest).
      grad_compress: forwarded to the recompiled train step.
      padded:   materialize the new plan physically — the returned state is
                padded onto ``padded_layout_for_ranges(decision.vocab_ranges)``
                and ``step_fn`` is compiled for it. A checkpoint stamped
                padded implies this automatically (a padded job stays
                padded across restarts).
      plan:     the job's ``EmbeddingPlan`` template (None = config
                default); the step recompiles under
                ``plan.with_replan(decision.table_hot, new layout)``, so
                fused sparse-update jobs resume fused.

    Returns ``(state, restored_step, step_fn, policy, remapper)``; when
    padded, rebuild the layout with
    ``padded_layout_for_ranges(decision.vocab_ranges)``.
    """
    R = cfg.total_embedding_rows
    state, restored_step, remapper, _old_hot, _old_ranges, old_layout = \
        restore_with_layout(cfg, optimizer, ckpt, step=step)
    if old_layout is not None:      # stamped padded: back to flat to permute
        state = unpad_train_state(state, R, old_layout)
    state = permute_train_state(state, R, decision.permutation)
    new_layout = None
    if padded or old_layout is not None:
        new_layout = padded_layout_for_ranges(decision.vocab_ranges)
        state = pad_train_state(state, R, new_layout)
    remapper.compose(decision.permutation)
    policy = make_dlrm_policy(mesh, vocab_ranges=decision.vocab_ranges)
    if mesh is not None:
        state = jax.device_put(
            state, elastic.dlrm_state_shardings(cfg, opt_name, policy,
                                                layout=new_layout))
    base_plan = plan if plan is not None else cfg.embedding_plan()
    new_plan = base_plan.with_replan(decision.table_hot, new_layout)
    step_fn = jax.jit(trainer_mod.make_dlrm_train_step(
        cfg, optimizer, grad_compress=grad_compress, plan=new_plan))
    return state, restored_step, step_fn, policy, remapper


# --------------------------------------------------------- layout-stamped ckpt
def save_with_layout(ckpt: FlashCheckpoint, state, step: int,
                     remapper: EmbeddingRemapper,
                     table_hot: Optional[Tuple[int, ...]] = None,
                     vocab_ranges: Optional[Sequence[Tuple[int, int]]] = None,
                     layout: Optional[PaddedLayout] = None) -> None:
    """Checkpoint the state together with its row-layout provenance.

    A plain state snapshot is only restorable by a process that still holds
    the ``ReplanDecision`` history (the permutations live in memory). This
    variant stamps the remapper's composed raw-id → layout map, the active
    ``table_hot`` cache plan and the applied PS ``vocab_ranges`` into the
    blob, making the checkpoint self-describing: a *fresh* process restores
    with ``restore_with_layout`` and keeps training (and re-planning from
    the correct baseline) no matter how many re-plans preceded it.

    Padded states are stored in the **canonical flat row order** (unpadded
    before flattening) plus a ``padded_n_ps`` stamp: one blob schema
    round-trips bit-exactly between flat and padded jobs, onto any future
    shard count — padding is a restore-time placement choice, not a storage
    format.

    Args:
      ckpt:      flash checkpoint to write to.
      state:     live {params, opt, step} pytree (current layout; padded on
                 ``layout`` when one is given).
      step:      checkpoint step key.
      remapper:  the job's id remapper (its map matches ``state``'s layout).
      table_hot: the cache plan compiled into the current step (None = the
                 config default).
      vocab_ranges: the applied balanced PS ranges (None = uniform striping,
                 i.e. no placement plan applied yet).
      layout:    the padded physical layout ``state`` lives on (None = flat).
                 Stamped as ``padded_n_ps`` so a fresh ``--resume`` comes
                 back padded on the same plan.
    """
    with span("ckpt_stamp"):
        hot = (np.full(len(remapper.table_rows), -1, np.int64)
               if table_hot is None else np.asarray(table_hot, np.int64))
        ranges = (np.zeros((0,), np.int64) if vocab_ranges is None
                  else np.asarray(vocab_ranges, np.int64).reshape(-1))
        if layout is not None:
            state = unpad_train_state(state, remapper.total_rows, layout)
        blob = {"state": state, "layout": np.asarray(remapper.map, np.int64),
                "table_hot": hot, "vocab_ranges": ranges,
                "padded_n_ps": np.asarray(
                    0 if layout is None else layout.n_ps, np.int64)}
    ckpt.save(blob, step)


def restore_with_layout(cfg: DLRMConfig, optimizer: Optimizer,
                        ckpt: FlashCheckpoint, *, step: Optional[int] = None
                        ) -> Tuple[Dict[str, Any], int, EmbeddingRemapper,
                                   Optional[Tuple[int, ...]],
                                   Optional[Tuple[Tuple[int, int], ...]],
                                   Optional[PaddedLayout]]:
    """Restore a ``save_with_layout`` checkpoint in a fresh process.

    Args:
      cfg, optimizer: the job being resumed (shape source for the restore).
      ckpt: flash checkpoint holding layout-stamped blobs.
      step: checkpoint step (None = latest).

    Returns ``(state, restored_step, remapper, table_hot, vocab_ranges,
    layout)``: the remapper is reconstructed from the stamped map (route raw
    batches through it), ``table_hot`` is the cache plan to recompile with
    (None = config default), ``vocab_ranges`` is the applied placement plan
    to seed a fresh ``HotTableTracker``'s baseline with (None = uniform),
    and ``layout`` is the stamped padded physical layout — when not None the
    returned state is already padded onto it (rebuilt from the stamped
    ranges, or uniform striping when no plan was applied yet); compile the
    step with ``layout=layout``. Blobs written before the padded-shard era
    lack the stamp and restore as flat (``layout=None``).
    """
    n_tables = len(cfg.table_rows)
    like = {
        "state": jax.eval_shape(
            lambda k: trainer_mod.make_dlrm_train_state(cfg, optimizer, k),
            jax.random.PRNGKey(0)),
        "layout": jax.ShapeDtypeStruct((cfg.total_embedding_rows,), jnp.int64),
        "table_hot": jax.ShapeDtypeStruct((n_tables,), jnp.int64),
        # placeholder shape: restore takes leaf shapes from the stored blob
        "vocab_ranges": jax.ShapeDtypeStruct((0,), jnp.int64),
        # absent in pre-padded-era blobs: zero-fills to 0 (= flat); every
        # OTHER missing leaf still raises (truncated blobs must not restore)
        "padded_n_ps": jax.ShapeDtypeStruct((), jnp.int64),
    }
    blob, restored_step = ckpt.restore(
        like, step,
        optional_leaves=(jax.tree_util.keystr(
            (jax.tree_util.DictKey("padded_n_ps"),)),))
    remapper = EmbeddingRemapper(cfg.table_rows)
    remapper.map = np.asarray(blob["layout"], np.int64)
    hot = np.asarray(blob["table_hot"])
    table_hot = None if (hot < 0).any() else tuple(int(k) for k in hot)
    flat_ranges = np.asarray(blob["vocab_ranges"]).reshape(-1, 2)
    vocab_ranges = (None if flat_ranges.size == 0 else
                    tuple((int(s), int(e)) for s, e in flat_ranges))
    state = blob["state"]
    n_ps = int(np.asarray(blob["padded_n_ps"]))
    layout = None
    if n_ps > 0:
        layout = padded_layout_for_ranges(
            vocab_ranges if vocab_ranges is not None
            else uniform_vocab_ranges(cfg.total_embedding_rows, n_ps))
        state = pad_train_state(state, cfg.total_embedding_rows, layout)
    return state, restored_step, remapper, table_hot, vocab_ranges, layout
