"""Fused sparse backward + row-wise optimizer update.

Covers the acceptance contract of the sparse-update engine:
  * ``dedupe_rows`` collapses duplicate store rows into one summed COO entry
    each — adversarial duplicate/hot/padding-boundary indices included — and
    pads the tail with the inert sentinel ``num_rows``.
  * scattering ``sparse_row_grads`` reproduces the dense pool cotangent BIT
    for bit (both backward paths share the same dedupe + segment step), on
    the flat and the padded physical layout.
  * the fused row update (XLA fallback and Pallas kernel in interpret mode)
    matches the dense full-pool optimizer on every touched row and is an
    exact no-op on every untouched row, for adagrad and (lazy) adam.
  * the sparse train step equals the dense train step: identical loss and
    grad norm, bit-identical adagrad pooled stores.

Property tests ride the hypothesis shim (``tests/_hypothesis_compat``): a
deterministic example sweep when hypothesis is not installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.dlrm_models import DCN, WIDE_DEEP, reduced_dlrm
from repro.data.synthetic import criteo_batch
from repro.kernels import ops
from repro.kernels.fused_embedding import (dedupe_rows, fused_embedding_bag,
                                           table_offsets)
from repro.sharding.policy import (EmbeddingPlan, balanced_vocab_ranges,
                                   padded_layout_for_ranges)
from repro.train import optim, trainer

jax.config.update("jax_platform_name", "cpu")

ROWS_PER_TABLE = (40, 24, 64, 8)
OFFSETS = table_offsets(ROWS_PER_TABLE)
TOTAL = sum(ROWS_PER_TABLE)
TABLE_HOT = (8, 4, 16, 2)


def _plan(combiner="sum", *, table_hot=None, layout=None):
    return EmbeddingPlan(offsets=OFFSETS, combiner=combiner, block_b=4,
                         table_hot=table_hot, layout=layout)


def _assert_ulp_close(a, b, max_ulp, msg=""):
    """Float32 arrays equal up to ``max_ulp`` units in the last place.

    XLA is free to contract ``a*b + c`` into an FMA, and whether it does so
    differs between lowerings (gather/scatter fallback vs the interpreted
    Pallas kernel body) and across shapes — so cross-lowering comparisons
    are ULP-bounded, not bit-exact.  Exactness claims (untouched rows,
    sentinel no-ops) stay ``assert_array_equal``.
    """
    a = np.ascontiguousarray(np.asarray(a, np.float32))
    b = np.ascontiguousarray(np.asarray(b, np.float32))
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    # fold the sign-magnitude float encoding onto a monotone integer line
    ai = np.where(ai < 0, np.int64(-2**31) - ai, ai)
    bi = np.where(bi < 0, np.int64(-2**31) - bi, bi)
    ulp = int(np.abs(ai - bi).max()) if a.size else 0
    assert ulp <= max_ulp, (
        f"{msg}max ULP distance {ulp} > {max_ulp} "
        f"(max abs diff {np.abs(a - b).max():.3e})")


def _layout():
    """A physically-unequal padded layout over the pooled rows."""
    counts = np.concatenate(
        [np.arange(r, 0, -1.0) ** 2 for r in ROWS_PER_TABLE])
    lay = padded_layout_for_ranges(balanced_vocab_ranges(counts, 3))
    assert len(set(lay.shard_sizes)) > 1
    return lay


def _inputs(B=6, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((TOTAL, D), np.float32))
    idx = np.stack([rng.integers(0, r, (B, H)) for r in ROWS_PER_TABLE],
                   axis=1)
    g = jnp.asarray(
        rng.standard_normal((B, len(ROWS_PER_TABLE), D), np.float32))
    return pool, jnp.asarray(idx.astype(np.int32)), g


# ---------------------------------------------------------------------------
# dedupe: duplicate / hot / boundary rows collapse into one entry each
# ---------------------------------------------------------------------------
def test_dedupe_rows_adversarial_duplicates():
    """Hot row repeated across bags, in-bag duplicates, boundary rows 0 and
    R-1 — every duplicate collapses to one entry with the exact sum."""
    R, D = 50, 4
    store = jnp.asarray(
        [7, 7, 7, 7, 0, 49, 0, 7, 3, 49, 49, 7], jnp.int32)
    g = jnp.asarray(np.arange(12 * D, dtype=np.float32).reshape(12, D))
    rows, vals = jax.jit(
        lambda s, gr: dedupe_rows(s, gr, R))(store, g)
    rows_np, vals_np = np.asarray(rows), np.asarray(vals)
    touched = rows_np[rows_np < R]
    assert sorted(touched.tolist()) == [0, 3, 7, 49]
    assert len(set(touched.tolist())) == len(touched)   # unique
    assert (rows_np[len(touched):] == R).all()          # sentinel tail
    assert (vals_np[len(touched):] == 0.0).all()
    want = np.zeros((R, D), np.float64)
    np.add.at(want, np.asarray(store), np.asarray(g, np.float64))
    got = np.zeros((R, D), np.float64)
    got[touched] = vals_np[rows_np < R]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-6)


@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_duplicate_rows_within_bag_backward_regression(combiner):
    """Regression for the in-bag-duplicate ordering bug: the backward no
    longer leans on segment_sum visit order — duplicates are deduped into
    one summed contribution, so the fused dense grad, the scattered COO
    grad, and plain autodiff all agree."""
    pool, idx, _ = _inputs()
    # force duplicates inside every bag AND a cross-bag hot row at a table
    # boundary (local 0 of table 2 = pooled row OFFSETS[2])
    idx = idx.at[:, :, 1].set(idx[:, :, 0])
    idx = idx.at[:, 2, 2].set(0)
    plan = _plan(combiner)

    def loss(p):
        return jnp.sum(fused_embedding_bag(p, idx, plan=plan) * 1.3)

    g_dense = jax.jit(jax.grad(loss))(pool)

    def scatter(p):
        ct = jax.grad(lambda o: jnp.sum(o * 1.3))(
            fused_embedding_bag(p, idx, plan=plan))
        rows, vals, _ = ops.sparse_row_grads(p, idx, ct, plan=plan)
        return jnp.zeros_like(p).at[rows].add(vals)

    # both paths share one dedupe: bit-identical, not merely close
    np.testing.assert_array_equal(np.asarray(jax.jit(scatter)(pool)),
                                  np.asarray(g_dense))

    from repro.kernels import ref
    g_ref = jax.jit(jax.grad(lambda p: jnp.sum(ref.fused_embedding_bag_ref(
        p, idx, offsets=OFFSETS, combiner=combiner) * 1.3)))(pool)
    np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-5)


def test_sparse_row_grads_padded_layout_never_touches_padding():
    pool, idx, g = _inputs(seed=2)
    lay = _layout()
    ppool = lay.pad_rows(pool).reshape(lay.padded_rows, -1)
    plan = _plan(layout=lay)
    rows, vals, _ = jax.jit(lambda p, i, ct: ops.sparse_row_grads(
        p, i, ct, plan=plan))(ppool, idx, g)
    rows_np = np.asarray(rows)
    live = rows_np[rows_np < lay.padded_rows]
    mask = np.asarray(lay.padding_mask()).reshape(-1)
    assert mask[live].all()                       # only real rows touched
    # scattering reproduces the padded dense cotangent bit for bit
    dpool = jax.jit(lambda p, i, ct: jax.vjp(
        lambda q: fused_embedding_bag(q, i, plan=plan), p)[1](ct)[0])(
            ppool, idx, g)
    scat = jnp.zeros_like(ppool).at[rows].add(vals)
    np.testing.assert_array_equal(np.asarray(scat), np.asarray(dpool))


# ---------------------------------------------------------------------------
# fused row update: property test against the dense-grad reference
# ---------------------------------------------------------------------------
def _dense_reference(kind, pool, dense_grad, state, lr):
    """Row-wise optimizer expression applied from the DENSE cotangent,
    written in the fused update's own operation order."""
    if kind == "adagrad":
        acc = state["acc"] + jnp.square(dense_grad)
        upd = -lr * dense_grad / (jnp.sqrt(acc) + 1e-10)
        return pool + upd, {"acc": acc}
    m = 0.9 * state["m"] + (1 - 0.9) * dense_grad
    v = 0.999 * state["v"] + (1 - 0.999) * jnp.square(dense_grad)
    tc = (state["count"] + 1).astype(jnp.float32)
    mh = m / (1 - 0.9 ** tc)
    vh = v / (1 - 0.999 ** tc)
    return pool + -lr * (mh / (jnp.sqrt(vh) + 1e-8)), {"m": m, "v": v}


@settings(max_examples=12, deadline=None)
@given(
    combiner=st.sampled_from(["sum", "mean", "max"]),
    padded=st.booleans(),
    hot=st.booleans(),
    kind=st.sampled_from(["adagrad", "adam"]),
    seed=st.integers(0, 99),
)
def test_fused_update_matches_dense_reference(combiner, padded, hot, kind,
                                              seed):
    """fused backward+update == dense-grad reference on touched rows
    (ULP-bounded), exact no-op on untouched rows — across combiners x
    {flat, padded} x table_hot on/off, adagrad and (lazy) adam."""
    pool, idx, g = _inputs(seed=seed)
    lay = _layout() if padded else None
    plan = _plan(combiner, table_hot=TABLE_HOT if hot else None, layout=lay)
    store = lay.pad_rows(pool).reshape(lay.padded_rows, -1) if padded \
        else pool
    rng = np.random.default_rng(seed + 1000)
    lr = 0.05
    if kind == "adagrad":
        state = {"acc": jnp.asarray(
            np.abs(rng.standard_normal(store.shape)).astype(np.float32))}
    else:
        state = {"m": jnp.asarray(
                     rng.standard_normal(store.shape).astype(np.float32)),
                 "v": jnp.asarray(
                     np.abs(rng.standard_normal(store.shape))
                     .astype(np.float32)),
                 "count": jnp.asarray(3, jnp.int32)}

    def sparse(p, st_, ct):
        rows, vals, _ = ops.sparse_row_grads(p, idx, ct, plan=plan)
        if kind == "adagrad":
            new_p, acc = ops.fused_row_update(
                p, rows, vals, st_["acc"], kind="adagrad", impl="xla",
                lr=lr, eps=1e-10)
            return new_p, {"acc": acc}
        tc = (st_["count"] + 1).astype(jnp.float32)
        new_p, m, v = ops.fused_row_update(
            p, rows, vals, st_["m"], st_["v"], kind="adam", impl="xla",
            lr=lr, count=tc, eps=1e-8)
        return new_p, {"m": m, "v": v}

    def dense(p, st_, ct):
        dp = jax.vjp(lambda q: fused_embedding_bag(q, idx, plan=plan),
                     p)[1](ct)[0]
        return _dense_reference(kind, p, dp, st_, lr), dp

    # both sides op by op: under jit XLA contracts ``b1 * m + (1 - b1) * g``
    # into an FMA in one program and not in the other, and where the two
    # terms cancel that rounding difference is many ULPs of the result
    with jax.disable_jit():
        new_p, new_st = sparse(store, state, g)
        (ref_p, ref_st), dp = dense(store, state, g)

    touched = np.unique(np.asarray(
        jax.jit(lambda p, ct: ops.sparse_row_grads(
            p, idx, ct, plan=plan)[0])(store, g)))
    touched = touched[touched < store.shape[0]]
    untouched = np.setdiff1d(np.arange(store.shape[0]), touched)

    # touched rows: ULP-bounded vs the dense reference.  params get the
    # wider bound: a 1-ULP FMA divergence in the moment accumulate is
    # amplified by sqrt/divide and the near-cancelling ``p + upd``.
    _assert_ulp_close(np.asarray(new_p)[touched],
                      np.asarray(ref_p)[touched], 64, "params: ")
    # untouched rows: params bit-unchanged; moments bit-unchanged (adagrad
    # is exact; adam is LAZY — no decay off the lookup path)
    np.testing.assert_array_equal(np.asarray(new_p)[untouched],
                                  np.asarray(store)[untouched])
    for name in ("acc", "m", "v"):
        if name in state:
            _assert_ulp_close(np.asarray(new_st[name])[touched],
                              np.asarray(ref_st[name])[touched], 4,
                              f"{name}: ")
            np.testing.assert_array_equal(
                np.asarray(new_st[name])[untouched],
                np.asarray(state[name])[untouched])
    # dense grad really had zero mass on the untouched rows (sanity)
    assert float(jnp.abs(jnp.asarray(dp)[untouched]).max()) == 0.0


@pytest.mark.parametrize("kind", ["adagrad", "adam"])
def test_row_update_interpret_matches_xla(kind):
    """The Pallas row-update kernel (interpret) == XLA fallback to within a
    few ULPs under jit (XLA may contract the multiply-adds into FMAs
    differently between the two lowerings)."""
    rng = np.random.default_rng(7)
    R, D, N = 40, 8, 24
    params = jnp.asarray(rng.standard_normal((R, D)).astype(np.float32))
    rows = jnp.asarray(
        np.concatenate([rng.choice(R, N - 4, replace=False),
                        [R] * 4]).astype(np.int32))   # sentinel tail
    vals = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    vals = vals.at[N - 4:].set(0.0)

    acc = jnp.asarray(np.abs(rng.standard_normal((R, D))).astype(np.float32))
    m0 = jnp.asarray(rng.standard_normal((R, D)).astype(np.float32))
    v0 = jnp.asarray(np.abs(rng.standard_normal((R, D))).astype(np.float32))

    def run(impl):
        if kind == "adagrad":
            f = jax.jit(lambda p, a: ops.fused_row_update(
                p, rows, vals, a, kind="adagrad", impl=impl, block=5,
                lr=0.1, eps=1e-10))
            return f(params, acc)
        f = jax.jit(lambda p, m_, v_: ops.fused_row_update(
            p, rows, vals, m_, v_, kind="adam", impl=impl, block=5,
            lr=0.1, count=jnp.asarray(1.0), eps=1e-8, weight_decay=0.01))
        return f(params, m0, v0)

    for a, b in zip(run("xla"), run("interpret")):
        _assert_ulp_close(a, b, 8)


def test_row_update_sentinel_rows_are_inert():
    """Entries >= R (dedupe padding) must not touch any pool row."""
    R, D = 10, 4
    params = jnp.ones((R, D), jnp.float32)
    acc = jnp.ones((R, D), jnp.float32)
    rows = jnp.asarray([R, R, R, R], jnp.int32)
    vals = jnp.full((4, D), 123.0, jnp.float32)    # non-zero on purpose
    for impl in ("xla", "interpret"):
        new_p, new_a = jax.jit(lambda p, a: ops.fused_row_update(
            p, rows, vals, a, kind="adagrad", impl=impl, block=4,
            lr=0.1, eps=1e-10))(params, acc)
        np.testing.assert_array_equal(np.asarray(new_p), np.asarray(params))
        np.testing.assert_array_equal(np.asarray(new_a), np.asarray(acc))


def test_fused_row_update_unknown_kind():
    with pytest.raises(ValueError, match="unknown row-update kind"):
        ops.fused_row_update(jnp.zeros((4, 2)), jnp.zeros((1,), jnp.int32),
                             jnp.zeros((1, 2)), jnp.zeros((4, 2)),
                             kind="rmsprop")


# ---------------------------------------------------------------------------
# Optimizer.update_rows seam
# ---------------------------------------------------------------------------
def test_optimizer_update_rows_seam():
    assert optim.adagrad(0.05).update_rows is not None
    assert optim.adam(1e-3).update_rows is not None
    assert optim.adam(1e-3, master_weights=True).update_rows is None
    assert optim.sgd(0.1).update_rows is None


def test_sparse_row_grad_leaf_to_dense():
    rows = jnp.asarray([1, 3, 5, 6], jnp.int32)    # 6 == num_rows: dropped
    vals = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    dense = optim.SparseRowGrad(rows, vals).to_dense(6)
    assert dense.shape == (6, 2)
    np.testing.assert_array_equal(np.asarray(dense[1]), [0.0, 1.0])
    np.testing.assert_array_equal(np.asarray(dense[0]), [0.0, 0.0])


# ---------------------------------------------------------------------------
# the sparse train step == the dense train step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("base", [WIDE_DEEP, DCN])
@pytest.mark.parametrize("opt_name", ["adagrad", "adam"])
def test_sparse_step_matches_dense_step(base, opt_name):
    cfg = reduced_dlrm(base)
    opt = optim.make(opt_name, 0.05)
    state = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in criteo_batch(cfg, 7, np.arange(16)).items()}
    dense_step = jax.jit(trainer.make_dlrm_train_step(cfg, opt))
    sparse_step = jax.jit(trainer.make_dlrm_train_step(
        cfg, opt, plan=cfg.embedding_plan(sparse_update=True)))
    s_d, m_d = dense_step(state, batch)
    s_s, m_s = sparse_step(state, batch)
    assert float(m_d["loss"]) == float(m_s["loss"])
    assert float(m_d["grad_norm"]) == float(m_s["grad_norm"])
    if opt_name == "adagrad":       # bit-exact (adam differs on untouched
        for k in ("tables",):       # moments: lazy vs decaying)
            np.testing.assert_array_equal(
                np.asarray(s_d["params"][k]), np.asarray(s_s["params"][k]))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=1e-6, rtol=1e-6), s_d["params"], s_s["params"])
    assert int(s_s["step"]) == 1


def test_sparse_step_requires_update_rows_falls_back():
    """sgd has no row-update seam: the plan's sparse_update flag quietly
    compiles the dense step instead (documented fallback)."""
    cfg = reduced_dlrm(WIDE_DEEP)
    opt = optim.sgd(0.1)
    state = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in criteo_batch(cfg, 7, np.arange(8)).items()}
    step = jax.jit(trainer.make_dlrm_train_step(
        cfg, opt, plan=cfg.embedding_plan(sparse_update=True)))
    s1, m1 = step(state, batch)
    dense = jax.jit(trainer.make_dlrm_train_step(cfg, opt))
    s2, m2 = dense(state, batch)
    assert float(m1["loss"]) == float(m2["loss"])
    np.testing.assert_array_equal(np.asarray(s1["params"]["tables"]),
                                  np.asarray(s2["params"]["tables"]))


# ---------------------------------------------------------------------------
# EmbeddingPlan surface
# ---------------------------------------------------------------------------
def test_embedding_plan_frozen_hashable_validated():
    plan = _plan("mean", table_hot=TABLE_HOT)
    assert isinstance(hash(plan), int)              # jit-cache key material
    assert plan.n_tables == len(ROWS_PER_TABLE)
    with pytest.raises(Exception):
        plan.combiner = "sum"                       # frozen
    with pytest.raises(ValueError):
        EmbeddingPlan(combiner="median")
    assert plan.with_combiner("sum").combiner == "sum"
    assert plan.with_combiner("sum").table_hot == plan.table_hot
    rep = plan.with_replan((1, 1, 1, 1), None)
    assert rep.table_hot == (1, 1, 1, 1) and rep.layout is None
    assert rep.combiner == "mean" and rep.offsets == plan.offsets
