"""Physically-unequal PS shards: the padded (n_ps, max_range, D) pooled layout.

Covers the acceptance contract of the padded placement path:
  * the layout planner's row translation is a bijection on real rows, every
    shard is a whole number of TPU tiles of rows, empty shards stay fully
    padded, and n_ps=1 degenerates to the flat rows at the head of one shard.
  * fused-engine forward AND backward are bit-exact vs the flat reference on
    every impl/combiner, with and without the hot-row cache; padding slots
    receive exactly zero gradient.
  * pad/unpad of a full train state (params + optimizer moments) round-trips
    bit-exactly, and flat/padded inits from one key are value-equal.
  * a live re-plan crosses layouts (old padded plan -> new padded plan built
    from the new balanced ranges) with bit-exact forward loss, matching the
    flat job's replan to the ulp.
  * layout-stamped checkpoints store the canonical flat order: they
    round-trip flat <-> padded, resume onto a different n_ps, and a blob
    saved before shards were tile-aligned restores onto the aligned layout.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dlrm_models import WIDE_DEEP, reduced_dlrm
from repro.core.flash_checkpoint import FlashCheckpoint
from repro.core.sharding_service import HotTableTracker
from repro.data.synthetic import criteo_batch
from repro.kernels.common import TILE_ROWS
from repro.kernels.fused_embedding import (fused_embedding_bag, table_offsets,
                                           translate_rows, translate_rows_np)
from repro.models.dlrm import dlrm_loss
from repro.sharding.policy import (EmbeddingPlan, PaddedLayout,
                                   balanced_vocab_ranges,
                                   padded_layout_for_ranges,
                                   uniform_vocab_ranges)
from repro.train import elastic, optim, replan, trainer

jax.config.update("jax_platform_name", "cpu")

ROWS = 512
CFG = dataclasses.replace(reduced_dlrm(WIDE_DEEP), table_rows=(ROWS,) * 6,
                          zipf_alpha=1.05, hot_rows_k=48)
N_PS = 4


def _batch(seed, lo, shift=0):
    b = criteo_batch(CFG, seed, np.arange(lo, lo + 256))
    if shift:
        b = dict(b, sparse=((b["sparse"].astype(np.int64) + shift) % ROWS
                            ).astype(b["sparse"].dtype))
    return b


def _jb(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


# ------------------------------------------------------------- layout planner
def test_planner_geometry_and_translation_bijection():
    lay = padded_layout_for_ranges([(0, 100), (100, 101), (101, 224)])
    assert (lay.n_ps, lay.max_range, lay.total_rows) == (3, 1024, 224)
    assert lay.padded_rows == 3 * 1024
    assert lay.shard_sizes == (100, 1, 123)
    tr = lay.row_translation()
    assert len(np.unique(tr)) == lay.total_rows          # injective
    np.testing.assert_array_equal(lay.padded_to_flat(tr),
                                  np.arange(lay.total_rows))
    # mask row-sums ARE the materialized physical shard sizes
    np.testing.assert_array_equal(lay.padding_mask().sum(axis=1),
                                  lay.shard_sizes)
    # boundary rows land at slot 0 of their shard
    shard, slot = lay.shard_slot([0, 100, 101, 223])
    np.testing.assert_array_equal(shard, [0, 1, 2, 2])
    np.testing.assert_array_equal(slot, [0, 0, 0, 122])


def test_planner_rejects_gaps_and_wrong_origin():
    with pytest.raises(AssertionError):
        padded_layout_for_ranges([(1, 4), (4, 8)])       # must start at 0
    with pytest.raises(AssertionError):
        padded_layout_for_ranges([(0, 4), (5, 8)])       # gap
    with pytest.raises(AssertionError):
        padded_layout_for_ranges([])                     # no shards


def test_empty_shard_is_fully_padded_tail():
    """A zero-width range is legal: that shard is max_range rows of padding
    and no flat row ever translates into it."""
    lay = padded_layout_for_ranges([(0, 6), (6, 6), (6, 10)])
    assert lay.shard_sizes == (6, 0, 4)
    assert not lay.padding_mask()[1].any()               # all padding
    shard, _ = lay.shard_slot(np.arange(10))
    assert 1 not in shard.tolist()                       # never selected
    flat = jnp.arange(10.0)[:, None] * jnp.ones((1, 3))
    padded = lay.pad_rows(flat)
    np.testing.assert_array_equal(np.asarray(padded[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(lay.unpad_rows(padded)),
                                  np.asarray(flat))


def test_n_ps_1_degenerate_layout_is_flat_plus_leading_axis():
    """One shard: the flat rows at the head of it, zero rows after."""
    lay = padded_layout_for_ranges(uniform_vocab_ranges(224, 1))
    assert (lay.n_ps, lay.max_range, lay.padded_rows) == (1, 1024, 1024)
    np.testing.assert_array_equal(lay.row_translation(), np.arange(224))
    flat = jnp.arange(1.0, 225.0)[:, None]
    padded = np.asarray(lay.pad_rows(flat))[0]
    np.testing.assert_array_equal(padded[:224], np.asarray(flat))
    np.testing.assert_array_equal(padded[224:], 0.0)


@pytest.mark.parametrize("ranges", [
    [(0, 100), (100, 101), (101, 224)],                  # tiny
    [(0, 6), (6, 6), (6, 10), (10, 10)],                 # empty shards
    [(0, 0), (0, 0)],                                    # no rows at all
    [(0, 3000)],                                         # one shard
    [(0, 2048), (2048, 3072)],                           # already whole tiles
    [(0, 1025), (1025, 2049), (2049, 3000)],             # just over a tile
    [(0, 2049), (2049, 2050)],                           # just over two
])
def test_shards_are_whole_tiles_and_translation_stays_exact(ranges):
    lay = padded_layout_for_ranges(ranges)
    largest = max(1, max(e - s for s, e in ranges))
    assert lay.max_range % TILE_ROWS == 0
    assert largest <= lay.max_range < largest + TILE_ROWS
    assert lay.padded_rows == lay.n_ps * lay.max_range
    # flat_to_padded / translate_rows: a bijection onto the real slots
    flat_rows = np.arange(lay.total_rows)
    padded = lay.flat_to_padded(flat_rows)
    mask = lay.padding_mask().reshape(-1)
    np.testing.assert_array_equal(np.sort(padded), np.flatnonzero(mask))
    np.testing.assert_array_equal(lay.padded_to_flat(padded), flat_rows)
    np.testing.assert_array_equal(
        np.asarray(translate_rows(jnp.asarray(flat_rows, jnp.int32), lay)),
        padded)
    # pad -> unpad is exact, and the padding slots hold zeros
    flat = jnp.arange(1.0, 1.0 + 3 * lay.total_rows).reshape(-1, 3)
    store = lay.pad_rows(flat)
    assert store.shape == (lay.n_ps, lay.max_range, 3)
    np.testing.assert_array_equal(np.asarray(lay.unpad_rows(store)),
                                  np.asarray(flat))
    np.testing.assert_array_equal(
        np.asarray(store).reshape(-1, 3)[~mask], 0.0)


def test_traced_translation_matches_host_translation():
    rng = np.random.default_rng(0)
    lay = padded_layout_for_ranges(
        balanced_vocab_ranges(rng.zipf(1.7, 224).astype(float), N_PS))
    rows = rng.integers(0, 224, 1000)
    np.testing.assert_array_equal(
        np.asarray(translate_rows(jnp.asarray(rows, jnp.int32), lay)),
        translate_rows_np(rows, lay))
    np.testing.assert_array_equal(translate_rows_np(rows, lay),
                                  lay.flat_to_padded(rows))


# ------------------------------------------------ fused engine bit-exactness
TABLE_ROWS = (64, 40, 96, 24)
OFFSETS = table_offsets(TABLE_ROWS)
TABLE_HOT = (16, 8, 24, 6)


def _stream(B=13, H=4, D=16, seed=0):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((sum(TABLE_ROWS), D), np.float32))
    idx = np.stack([rng.integers(0, r, (B, H)) for r in TABLE_ROWS], axis=1)
    w = jnp.asarray(rng.uniform(0.1, 2.0, (B, len(TABLE_ROWS), H))
                    .astype(np.float32))
    # skewed mass so the balanced plan is genuinely unequal
    counts = np.concatenate([np.arange(r, 0, -1.0) ** 2 for r in TABLE_ROWS])
    lay = padded_layout_for_ranges(balanced_vocab_ranges(counts, 3))
    assert len(set(lay.shard_sizes)) > 1                 # physically unequal
    return pool, jnp.asarray(idx.astype(np.int32)), w, lay


@pytest.mark.parametrize("combiner", ["sum", "mean", "max"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("method", ["xla", "interpret"])
@pytest.mark.parametrize("hot", [None, TABLE_HOT])
def test_padded_forward_bitmatches_flat(combiner, weighted, method, hot):
    pool, idx, w, lay = _stream()
    weights = w if weighted else None
    ppool = lay.pad_rows(pool).reshape(lay.padded_rows, -1)
    plan = EmbeddingPlan(offsets=OFFSETS, combiner=combiner, block_b=4,
                         table_hot=hot)
    out_flat = fused_embedding_bag(pool, idx, weights, method=method,
                                   plan=plan)
    out_pad = fused_embedding_bag(ppool, idx, weights, method=method,
                                  plan=plan.with_replan(hot, lay))
    np.testing.assert_array_equal(np.asarray(out_flat), np.asarray(out_pad))


@pytest.mark.parametrize("combiner", ["sum", "mean", "max"])
def test_padded_backward_bitmatches_flat_and_zeroes_padding(combiner):
    pool, idx, w, lay = _stream(seed=3)
    D = pool.shape[1]

    plan = EmbeddingPlan(offsets=OFFSETS, combiner=combiner)

    def loss_flat(p):
        return jnp.sum(fused_embedding_bag(p, idx, w, plan=plan) * 1.3)

    def loss_pad(p3):
        return jnp.sum(fused_embedding_bag(
            p3.reshape(-1, D), idx, w,
            plan=plan.with_replan(None, lay)) * 1.3)

    g_flat = jax.grad(loss_flat)(pool)
    g_pad = jax.grad(loss_pad)(lay.pad_rows(pool))
    np.testing.assert_array_equal(np.asarray(lay.unpad_rows(g_pad)),
                                  np.asarray(g_flat))
    mask = jnp.asarray(lay.padding_mask())[..., None]
    assert float(jnp.abs(jnp.where(mask, 0.0, g_pad)).max()) == 0.0


# --------------------------------------------------- train-state pad/unpad
def test_pad_unpad_train_state_roundtrip_and_init_equivalence():
    opt = optim.adagrad(0.05)
    lay = padded_layout_for_ranges(
        uniform_vocab_ranges(CFG.total_embedding_rows, N_PS))
    flat = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(0))
    padded = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(0),
                                           layout=lay)
    # padded init == pad(flat init) leaf for leaf (same keys drawn)
    want = replan.pad_train_state(flat, CFG.total_embedding_rows, lay)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), want, padded)
    assert padded["params"]["tables"].shape[:2] == (N_PS, lay.max_range)
    assert padded["opt"]["acc"]["tables"].shape[:2] == (N_PS, lay.max_range)
    # round trip back to flat
    back = replan.unpad_train_state(padded, CFG.total_embedding_rows, lay)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), flat, back)
    # dense leaves never grow a padded shape
    assert padded["params"]["mlp"]["w0"].shape == flat["params"]["mlp"]["w0"].shape


def test_padded_train_step_matches_flat_step_bit_exactly():
    """One full optimizer step on the padded layout == the flat step, to the
    bit, on params AND losses (adagrad moments ride the same layout)."""
    opt = optim.adagrad(0.05)
    lay = padded_layout_for_ranges(
        uniform_vocab_ranges(CFG.total_embedding_rows, N_PS))
    s_flat = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(1))
    s_pad = replan.pad_train_state(s_flat, CFG.total_embedding_rows, lay)
    step_flat = jax.jit(trainer.make_dlrm_train_step(CFG, opt))
    step_pad = jax.jit(trainer.make_dlrm_train_step(CFG, opt, layout=lay))
    b = _jb(_batch(7, 0))
    for _ in range(3):
        s_flat, m_flat = step_flat(s_flat, b)
        s_pad, m_pad = step_pad(s_pad, b)
    assert float(m_pad["loss"]) == float(m_flat["loss"])
    np.testing.assert_array_equal(
        np.asarray(lay.unpad_rows(s_pad["params"]["tables"])),
        np.asarray(s_flat["params"]["tables"]))
    np.testing.assert_array_equal(
        np.asarray(lay.unpad_rows(s_pad["opt"]["acc"]["tables"])),
        np.asarray(s_flat["opt"]["acc"]["tables"]))


# ------------------------------------------------------- replan across layouts
def _drifted_decision(tracker_seed=3):
    tracker = HotTableTracker(CFG.table_rows, n_ps=N_PS,
                              hot_budget=CFG.hot_rows_k, decay=0.8,
                              trigger=1.2, cooldown=0, min_lookups=512)
    for i in range(6):
        tracker.observe(_batch(tracker_seed, 256 * i)["sparse"])
    decision = tracker.maybe_replan()
    assert decision is not None
    return decision


def test_replan_padded_job_matches_flat_replan_bit_exactly():
    """The same decision applied to a flat job and to a padded job (crossing
    to the NEW plan's physical layout) produces bit-identical losses."""
    opt = optim.adagrad(0.05)
    old_lay = padded_layout_for_ranges(
        uniform_vocab_ranges(CFG.total_embedding_rows, N_PS))
    s_flat = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(2))
    s_pad = replan.pad_train_state(s_flat, CFG.total_embedding_rows, old_lay)
    decision = _drifted_decision()

    rm_flat = replan.EmbeddingRemapper(CFG.table_rows)
    rm_pad = replan.EmbeddingRemapper(CFG.table_rows)
    res_flat = replan.apply_replan(s_flat, CFG, opt, decision,
                                   remapper=rm_flat)
    res_pad = replan.apply_replan(s_pad, CFG, opt, decision,
                                  remapper=rm_pad, layout=old_lay)
    assert res_flat.layout is None
    assert res_pad.layout == padded_layout_for_ranges(decision.vocab_ranges)
    # physical rows per shard == the balanced plan, exactly
    np.testing.assert_array_equal(
        res_pad.layout.padding_mask().sum(axis=1),
        [e - s for s, e in decision.vocab_ranges])

    probe = rm_flat.remap_batch(_batch(13, 10_000))
    loss_flat = float(dlrm_loss(res_flat.state["params"], _jb(probe), CFG,
                                table_hot=decision.table_hot))
    loss_pad = float(dlrm_loss(res_pad.state["params"], _jb(probe), CFG,
                               table_hot=decision.table_hot,
                               layout=res_pad.layout))
    assert loss_pad == loss_flat
    # and one resumed train step stays bit-identical
    _, m_flat = res_flat.step_fn(res_flat.state, _jb(probe))
    _, m_pad = res_pad.step_fn(res_pad.state, _jb(probe))
    assert float(m_pad["loss"]) == float(m_flat["loss"])


def test_layout_stamped_checkpoint_roundtrips_flat_and_padded():
    """save_with_layout stores the canonical flat order: a padded job's blob
    restores padded (stamp honored) AND unpads to the original flat state."""
    opt = optim.adagrad(0.05)
    decision = _drifted_decision()
    lay = padded_layout_for_ranges(decision.vocab_ranges)
    s_flat = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(4))
    s_flat = replan.permute_train_state(s_flat, CFG.total_embedding_rows,
                                        decision.permutation)
    s_pad = replan.pad_train_state(s_flat, CFG.total_embedding_rows, lay)
    remapper = replan.EmbeddingRemapper(CFG.table_rows)
    remapper.compose(decision.permutation)

    ckpt = FlashCheckpoint()
    replan.save_with_layout(ckpt, s_pad, 5, remapper, decision.table_hot,
                            decision.vocab_ranges, layout=lay)
    state2, step2, rm2, hot2, ranges2, lay2 = replan.restore_with_layout(
        CFG, opt, ckpt)
    assert step2 == 5 and lay2 == lay
    assert hot2 == decision.table_hot and ranges2 == decision.vocab_ranges
    np.testing.assert_array_equal(rm2.map, remapper.map)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state2, s_pad)
    back = replan.unpad_train_state(state2, CFG.total_embedding_rows, lay2)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), back, s_flat)

    raw = _batch(13, 20_000)
    want = float(dlrm_loss(s_flat["params"],
                           _jb(remapper.remap_batch(raw)), CFG,
                           table_hot=decision.table_hot))
    got = float(dlrm_loss(state2["params"], _jb(rm2.remap_batch(raw)), CFG,
                          table_hot=hot2, layout=lay2))
    assert got == want


def test_elastic_resume_onto_different_n_ps():
    """A plain blob saved padded on 4 shards resumes onto 2 shards (and onto
    the flat layout) with bit-identical forward loss."""
    opt = optim.adagrad(0.05)
    R = CFG.total_embedding_rows
    lay4 = padded_layout_for_ranges(uniform_vocab_ranges(R, 4))
    lay2 = padded_layout_for_ranges(uniform_vocab_ranges(R, 2))
    state = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(5),
                                          layout=lay4)
    b = _jb(_batch(11, 0))
    want = float(dlrm_loss(state["params"], b, CFG, layout=lay4))

    ckpt = FlashCheckpoint()
    ckpt.save(state, 3)
    s2, step2, _pol = elastic.resume_dlrm_on_mesh(
        CFG, opt, "adagrad", ckpt, None, from_layout=lay4, layout=lay2)
    assert step2 == 3
    assert s2["params"]["tables"].shape[:2] == (2, lay2.max_range)
    assert float(dlrm_loss(s2["params"], b, CFG, layout=lay2)) == want
    s3, _, _ = elastic.resume_dlrm_on_mesh(
        CFG, opt, "adagrad", ckpt, None, from_layout=lay4, layout=None)
    assert s3["params"]["tables"].shape[0] == R
    assert float(dlrm_loss(s3["params"], b, CFG)) == want


class _UnalignedLayout(PaddedLayout):
    """The placement before shards were tile-aligned: the largest range."""

    @property
    def max_range(self) -> int:
        return max(1, max(e - s for s, e in self.ranges))


def test_checkpoint_saved_unaligned_restores_onto_aligned_layout():
    """A blob saved by a job on the unaligned layout stores flat rows and
    its ranges: it restores onto the tile-aligned layout of those ranges
    with the real rows unchanged and zero rows after them."""
    opt = optim.adagrad(0.05)
    R = CFG.total_embedding_rows
    decision = _drifted_decision()
    old = _UnalignedLayout(tuple(decision.vocab_ranges))
    assert old.max_range % TILE_ROWS                     # really unaligned
    s_flat = trainer.make_dlrm_train_state(CFG, opt, jax.random.PRNGKey(6))
    s_old = replan.pad_train_state(s_flat, R, old)
    ckpt = FlashCheckpoint()
    replan.save_with_layout(ckpt, s_old, 9,
                            replan.EmbeddingRemapper(CFG.table_rows),
                            decision.table_hot, decision.vocab_ranges,
                            layout=old)
    state, step, _, hot, ranges, lay = replan.restore_with_layout(
        CFG, opt, ckpt)
    assert step == 9 and hot == decision.table_hot
    assert lay == padded_layout_for_ranges(ranges) and lay.ranges == old.ranges
    assert lay.max_range % TILE_ROWS == 0 and lay.max_range > old.max_range
    assert state["params"]["tables"].shape[:2] == (N_PS, lay.max_range)
    # real rows: zero difference from the unaligned job's; padding zero
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        replan.unpad_train_state(state, R, lay),
        replan.unpad_train_state(s_old, R, old))
    pad = ~lay.padding_mask()
    for leaf in (state["params"]["tables"], state["opt"]["acc"]["tables"]):
        np.testing.assert_array_equal(np.asarray(leaf)[pad], 0.0)
