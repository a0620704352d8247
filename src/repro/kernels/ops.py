"""Public jit-friendly kernel wrappers with implementation dispatch.

impl:
  "pallas"    — Pallas TPU kernels, compiled by Mosaic. The default on a TPU
                backend.
  "xla"       — pure-JAX path (chunked flash attention, one fused gather per
                embedding call). The default on every other backend, and the
                plain float32 reference the kernels are tested against.
  "interpret" — Pallas kernels in interpret mode: the kernel bodies' numerics
                on CPU. Never a default; tests ask for it per call with
                ``impl=`` or pin it with ``set_default_impl``.

Calls without ``impl=`` resolve it when they are traced, from
``jax.default_backend()``. Asking for "pallas" on a backend that is not a
TPU raises instead of quietly running the reference.

Embedding calls are planned by a single ``repro.sharding.policy
.EmbeddingPlan`` value (``plan=``): the frozen, hashable bundle of the
static knobs (``offsets``/``combiner``/``block_b``/``table_hot``/
``layout``/sparse-update flags) that used to accrete as loose kwargs. The
loose kwargs survive as a deprecation shim that builds a plan and warns
once per process.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax

from repro.models import attention as _xla_attn

IMPLS = ("xla", "pallas", "interpret")

_PINNED_IMPL: Optional[str] = None      # set_default_impl (tests only)

_LEGACY_KWARGS_WARNED = False


def _shim_plan(offsets, combiner, block_b, table_hot, layout):
    """Build an ``EmbeddingPlan`` from the deprecated loose kwargs.

    Warns once per process — but only when a loose kwarg was actually
    passed; a bare call (all defaults) silently gets the default plan.
    """
    global _LEGACY_KWARGS_WARNED
    legacy = (offsets is not None or combiner is not None
              or block_b is not None or table_hot is not None
              or layout is not None)
    if legacy and not _LEGACY_KWARGS_WARNED:
        _LEGACY_KWARGS_WARNED = True
        warnings.warn(
            "loose embedding kwargs (offsets/combiner/block_b/table_hot/"
            "layout) are deprecated; pass plan=EmbeddingPlan(...) instead",
            DeprecationWarning, stacklevel=3)
    from repro.sharding.policy import EmbeddingPlan
    return EmbeddingPlan(
        offsets=None if offsets is None else tuple(int(o) for o in offsets),
        combiner=combiner or "sum",
        block_b=8 if block_b is None else block_b,
        table_hot=None if table_hot is None else
        tuple(int(k) for k in table_hot),
        layout=layout)


def _backend() -> str:
    return jax.default_backend()


def set_default_impl(impl: Optional[str]) -> None:
    """Pin the implementation of calls made without ``impl=`` (tests);
    ``None`` restores the platform default."""
    global _PINNED_IMPL
    assert impl is None or impl in IMPLS, impl
    _PINNED_IMPL = impl


def get_default_impl() -> str:
    """The implementation a call without ``impl=`` runs: the pinned one, else
    "pallas" on a TPU backend and "xla" on any other."""
    if _PINNED_IMPL is not None:
        return _PINNED_IMPL
    return "pallas" if _backend() == "tpu" else "xla"


def resolve_impl(impl: Optional[str]) -> str:
    """``impl`` or the default; "pallas" only where a TPU compiles it."""
    impl = impl or get_default_impl()
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; one of {IMPLS}")
    if impl == "pallas" and _backend() != "tpu":
        raise RuntimeError(
            f"impl='pallas' needs a TPU backend, not {_backend()!r}; use "
            "'interpret' to run the kernel bodies on this backend")
    return impl


def flash_attention(q, k, v, *, causal=True, window=None, softcap=0.0,
                    q_chunk=1024, k_chunk=1024, q_offset=0, impl=None):
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            interpret=(impl == "interpret"))
    return _xla_attn.chunked_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_chunk=q_chunk, k_chunk=k_chunk, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, cache_pos, pos, *, window=None,
                     softcap=0.0, impl=None):
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        from repro.kernels import decode_attention as da
        return da.decode_attention(
            q, k_cache, v_cache, cache_pos, pos, window=window,
            softcap=softcap, interpret=(impl == "interpret"))
    return _xla_attn.decode_attention(
        q, k_cache, v_cache, cache_pos, pos, window=window, softcap=softcap)


def fused_embedding_bag(pool, indices, weights=None, *, plan=None, impl=None,
                        offsets=None, combiner=None, block_b=None,
                        table_hot=None, layout=None, mesh=None, batch_axes=()):
    """Multi-table fused embedding engine (one call for all tables).

    pool (R, D) row-concatenated tables — or, with a padded ``plan.layout``
    (a ``repro.sharding.policy.PaddedLayout``), the (n_ps * max_range, D)
    flattening of the padded physically-sharded store; indices (B, T, H)
    per-table-local rows; weights (B, T, H)? -> (B, T, D).

    ``plan`` (a ``repro.sharding.policy.EmbeddingPlan``) carries every
    static knob: per-table ``offsets``, the ``combiner``, the Pallas
    ``block_b``, the hot-row cache plan ``table_hot`` and the physical
    ``layout``. Plans are frozen and hashable compile-time values: a live
    re-plan (``repro.train.replan``) permutes (and re-pads) the pool rows
    and re-enters here with ``plan.with_replan(...)`` — numerics are
    identical for any plan, so old-plan checkpoints restore bit-exactly
    onto new ones. All impls share a custom VJP whose backward dedupes and
    scatter-adds sparse table gradients.

    ``mesh`` is the device mesh the calling step is partitioned over, and
    ``batch_axes`` the mesh axes its batch is split over: the Pallas kernel
    then runs on each device's batch shard with the pool replicated (GSPMD
    cannot partition a Mosaic kernel).

    The loose ``offsets``/``combiner``/``block_b``/``table_hot``/``layout``
    kwargs are deprecated (warn-once shim building a plan internally).
    """
    impl = resolve_impl(impl)
    if plan is None:
        plan = _shim_plan(offsets, combiner, block_b, table_hot, layout)
    else:
        assert (offsets is None and combiner is None and block_b is None
                and table_hot is None and layout is None), \
            "pass the static knobs inside plan=, not alongside it"
    from repro.kernels import fused_embedding as fe
    return fe.fused_embedding_bag(pool, indices, weights, method=impl,
                                  plan=plan, mesh=mesh, batch_axes=batch_axes)


def sparse_row_grads(pool, indices, g, weights=None, *, plan):
    """Fused sparse backward: bag cotangents → deduped COO row gradients.

    The training-step entry to ``fused_embedding.sparse_row_grads`` (see
    there for the contract): returns ``(rows, vals, dweights)`` where
    scattering ``vals`` at ``rows`` reproduces the dense pool gradient bit
    for bit, and ``(rows, vals)`` feed ``Optimizer.update_rows`` /
    ``fused_row_update`` directly.
    """
    from repro.kernels import fused_embedding as fe
    return fe.sparse_row_grads(pool, indices, g, weights, plan=plan)


def fused_row_update(params, rows, vals, *state, kind, impl=None, block=8,
                     **hyper):
    """Row-wise optimizer update on deduped COO row grads.

    params (R, D) pool; rows (N,) deduplicated store rows (entries >= R are
    inert padding); vals (N, D) summed row grads; ``state`` the optimizer's
    moment pools in the same row space — ``(acc,)`` for ``kind="adagrad"``,
    ``(m, v)`` for ``kind="adam"``. Returns the updated ``(params, *state)``.
    Dispatches to the Pallas fused kernel ("pallas"/"interpret") or the XLA
    gather/scatter fallback ("xla"); hyperparameters ride in ``hyper``
    (see ``repro.kernels.fused_update``).
    """
    impl = resolve_impl(impl)
    from repro.kernels import fused_update as fu
    if kind == "adagrad":
        (acc,) = state
        return fu.adagrad_row_update(params, acc, rows, vals, method=impl,
                                     block=block, **hyper)
    if kind == "adam":
        m, v = state
        return fu.adam_row_update(params, m, v, rows, vals, method=impl,
                                  block=block, **hyper)
    raise ValueError(f"unknown row-update kind: {kind!r}")


def embedding_bag(table, indices, weights=None, *, plan=None, combiner=None,
                  impl=None):
    """Fused embedding gather + pooling. table (R, D); indices (B, n); -> (B, D).

    Single-table convenience wrapper over ``fused_embedding_bag`` (T=1), so
    every caller gets the same combiner semantics (weights apply before
    sum/mean/max) and the sparse-gradient VJP. Prefer ``plan=`` (an
    ``EmbeddingPlan``); the loose ``combiner=`` kwarg is the deprecated
    shim form.
    """
    if plan is None:
        plan = _shim_plan(None, combiner, None, None, None)
    else:
        assert combiner is None, \
            "pass the combiner inside plan=, not alongside it"
    out = fused_embedding_bag(
        table, indices[:, None, :],
        None if weights is None else weights[:, None, :],
        plan=plan, impl=impl)
    return out[:, 0]
