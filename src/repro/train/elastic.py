"""Elastic re-meshing: resume a job on a different device mesh or row layout.

TPU analog of the paper's horizontal scaling: the flash-checkpoint stores
mesh-agnostic host arrays; this module rebuilds shardings for the *new* mesh
(via the logical-axis policy) and device_puts the restored state — i.e. a
seamless worker/PS count change without re-partitioning logic in user code.

``resume_dlrm_on_mesh`` is the same substrate for the paper's own DLRM
workloads, with two extra degrees of freedom: an optional ``ReplanDecision``
from the live re-planning loop, applied as a bit-exact pooled-row
permutation after restore — so a checkpoint written under the OLD placement
plan resumes under the NEW one (see ``repro.train.replan``) — and optional
``from_layout``/``layout`` padded physical layouts, so a job checkpointed
with ``n_ps`` physically-unequal PS shards resumes onto a different shard
count (or back to the flat pool) bit-exactly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax

from repro.configs.base import ShapeConfig
from repro.configs.dlrm_models import DLRMConfig
from repro.core.flash_checkpoint import FlashCheckpoint
from repro.models.registry import ModelAPI
from repro.sharding.policy import (
    ShardingPolicy, logical_spec, make_dlrm_policy, make_policy,
)
from repro.train import trainer as trainer_mod
from repro.train.optim import Optimizer


def state_shardings(api: ModelAPI, opt_name: str, policy: ShardingPolicy):
    """NamedShardings for the full train state under a policy."""
    specs = trainer_mod.train_state_specs(api, opt_name)
    return logical_spec(None, specs, policy)


def save_for_elasticity(ckpt: FlashCheckpoint, state, step: int) -> None:
    ckpt.save(state, step)


def resume_on_mesh(api: ModelAPI, optimizer: Optimizer, opt_name: str,
                   ckpt: FlashCheckpoint, mesh, shape: ShapeConfig,
                   *, step: Optional[int] = None) -> Tuple[Dict[str, Any], int, ShardingPolicy]:
    """Restore the latest checkpoint onto a (possibly different) mesh."""
    policy = make_policy(mesh, api.cfg, shape)
    like = jax.eval_shape(
        lambda k: trainer_mod.make_train_state(api, optimizer, k),
        jax.random.PRNGKey(0))
    shardings = state_shardings(api, opt_name, policy) if mesh is not None else None
    state, restored_step = ckpt.restore(like, step, shardings=shardings)
    return state, restored_step, policy


# --- DLRM (paper workloads) -------------------------------------------------
def dlrm_state_shardings(cfg: DLRMConfig, opt_name: str,
                         policy: ShardingPolicy, layout=None):
    """NamedShardings for the full DLRM train state under a policy.

    ``layout`` (a ``PaddedLayout``) switches the pooled-store specs to the
    padded ``(n_ps, max_range, ...)`` form, whose leading axis the "vocab"
    rule splits equally — one balanced range per PS device.
    """
    specs = trainer_mod.dlrm_train_state_specs(cfg, opt_name, layout=layout)
    return logical_spec(None, specs, policy)


def make_dlrm_mesh_step(cfg: DLRMConfig, optimizer: Optimizer, opt_name: str,
                        mesh, plan):
    """The DLRM train step jitted over ``mesh`` under the DLRM policy.

    The state is sharded by ``dlrm_state_shardings`` (pooled rows — the
    padded ``(n_ps, max_range, D)`` store under ``plan.layout`` — over the
    "model"/PS axis, dense params replicated) and the batch over "data";
    the policy is active while the step traces, so the model's
    ``constrain`` calls see the mesh.

    Returns ``(step_fn, state_shardings, batch_sharding)``; place the state
    with ``jax.device_put(state, state_shardings)``.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding.policy import use_policy
    policy = make_dlrm_policy(mesh)
    shardings = dlrm_state_shardings(cfg, opt_name, policy, layout=plan.layout)
    batch_sharding = NamedSharding(mesh, PartitionSpec(policy.rules["batch"]))
    step = trainer_mod.make_dlrm_train_step(cfg, optimizer, plan=plan)

    def traced(state, batch):
        with use_policy(policy):
            return step(state, batch)

    step_fn = jax.jit(traced, in_shardings=(shardings, batch_sharding),
                      out_shardings=(shardings, None))
    return step_fn, shardings, batch_sharding


def resume_dlrm_on_mesh(cfg: DLRMConfig, optimizer: Optimizer, opt_name: str,
                        ckpt: FlashCheckpoint, mesh, *,
                        decision=None, step: Optional[int] = None,
                        from_layout=None, layout=None
                        ) -> Tuple[Dict[str, Any], int, ShardingPolicy]:
    """Restore a DLRM checkpoint onto a mesh and (optionally) a new row plan.

    The layout degrees of freedom make this the "resume onto a different
    PS count" path for physically-padded jobs: a blob saved padded on
    ``from_layout`` (say 4 shards) restores bit-exactly onto ``layout``
    (say 2 shards, or flat) — the checkpointed rows are re-based through
    the canonical flat space, so any (from_layout, layout) pair composes,
    including with a ``ReplanDecision`` permutation in between.

    Args:
      cfg, optimizer, opt_name: the job being resumed.
      ckpt:     flash-checkpoint holding mesh-agnostic host arrays.
      mesh:     target mesh (None = single host).
      decision: optional ``ReplanDecision``; its permutation is applied to
                the restored pooled rows (bit-exact) and its balanced
                ``vocab_ranges`` ride on the returned policy.
      step:     checkpoint step (None = latest).
      from_layout: the ``PaddedLayout`` the blob was *saved* on (None =
                saved flat). Plain ``ckpt.save`` blobs store whatever layout
                the live state had, so the caller must say which.
      layout:   the ``PaddedLayout`` to resume *onto* (None = flat). The
                caller compiles its step with the same ``layout``.

    Returns ``(state, restored_step, policy)``; the caller recompiles its
    train step with ``table_hot=decision.table_hot`` (and ``layout``) to
    finish the re-plan.
    """
    from repro.train.replan import (pad_train_state, permute_train_state,
                                    unpad_train_state)
    R = cfg.total_embedding_rows
    ranges = None if decision is None else decision.vocab_ranges
    policy = make_dlrm_policy(mesh, vocab_ranges=ranges)
    like = jax.eval_shape(
        lambda k: trainer_mod.make_dlrm_train_state(cfg, optimizer, k,
                                                    layout=from_layout),
        jax.random.PRNGKey(0))
    state, restored_step = ckpt.restore(like, step)
    if from_layout is not None:
        state = unpad_train_state(state, R, from_layout)
    if decision is not None:
        state = permute_train_state(state, R, decision.permutation)
    if layout is not None:
        state = pad_train_state(state, R, layout)
    if mesh is not None:
        state = jax.device_put(
            state, dlrm_state_shardings(cfg, opt_name, policy, layout=layout))
    return state, restored_step, policy


def resume_dlrm_stamped(cfg: DLRMConfig, optimizer: Optimizer,
                        ckpt: FlashCheckpoint, *,
                        onto_n_ps: Optional[int] = None, mesh=None,
                        opt_name: str = "adagrad", step: Optional[int] = None):
    """Elastic re-resume of a *layout-stamped* blob, e.g. after a PS loss.

    The stamped-blob analog of ``resume_dlrm_on_mesh(from_layout=, layout=)``:
    the blob's own ``padded_n_ps`` stamp plays the ``from_layout`` role, and
    ``onto_n_ps`` — the *surviving* shard count after a PS-shard loss — the
    ``layout`` role. Checkpoints store the canonical flat row order, so a
    job padded on N shards re-resumes bit-exactly onto any smaller (or
    larger) shard count; the supervisor's ``PSShardLoss`` recovery is this
    call with ``onto_n_ps = n_ps - n_lost``.

    The shrunk placement is the uniform plan over the survivors — the live
    re-planning loop re-balances it from real counts at its next trigger.

    Args:
      cfg, optimizer: the job being resumed.
      ckpt:      flash checkpoint holding ``save_with_layout`` blobs.
      onto_n_ps: surviving PS shard count (None = keep the stamped layout;
                 ignored for flat jobs, which have no physical shards).
      mesh:      optional target mesh for re-placement.
      opt_name:  optimizer name for sharding specs when a mesh is given.
      step:      checkpoint step (None = newest valid).

    Returns ``(state, restored_step, remapper, table_hot, vocab_ranges,
    layout)`` exactly like ``replan.restore_with_layout``, with ``state``
    padded onto (and ``layout``/``vocab_ranges`` describing) the surviving
    shard count.
    """
    from repro.sharding.policy import (padded_layout_for_ranges,
                                       uniform_vocab_ranges)
    from repro.train import replan as replan_mod
    R = cfg.total_embedding_rows
    state, restored_step, remapper, table_hot, vocab_ranges, layout = \
        replan_mod.restore_with_layout(cfg, optimizer, ckpt, step=step)
    if onto_n_ps is not None and layout is not None and \
            onto_n_ps != layout.n_ps:
        state = replan_mod.unpad_train_state(state, R, layout)
        ranges = uniform_vocab_ranges(R, onto_n_ps)
        layout = padded_layout_for_ranges(ranges)
        state = replan_mod.pad_train_state(state, R, layout)
        vocab_ranges = tuple((int(s), int(e)) for s, e in ranges)
    if mesh is not None:
        policy = make_dlrm_policy(mesh, vocab_ranges=vocab_ranges)
        state = jax.device_put(
            state, dlrm_state_shardings(cfg, opt_name, policy, layout=layout))
    return state, restored_step, remapper, table_hot, vocab_ranges, layout
