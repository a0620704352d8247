"""Train-step construction: loss+grad+optimizer under pjit with logical sharding.

``make_train_step`` builds the jit-able step for any ModelAPI (LM families,
whisper) — this is what the launcher runs and what the multi-pod dry-run
lowers. ``make_dlrm_train_step`` is the analogous step for the paper's own
DLRM workloads. Distributed-optimization knobs:

* ``remat``            — activation checkpointing over pattern groups
* ``grad_compress``    — bf16-cast gradients before the cross-replica
                          all-reduce (halves DP sync bytes; §7 [20] analog)
* sharded optimizer state (ZeRO) via ``optim.state_specs``
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.dlrm_models import DLRMConfig
from repro.models.dlrm import dlrm_loss
from repro.models.registry import ModelAPI
from repro.spans import scope
from repro.train import optim as optim_mod
from repro.train.optim import Optimizer


def make_train_state(api: ModelAPI, optimizer: Optimizer, key) -> Dict[str, Any]:
    params = api.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def train_state_specs(api: ModelAPI, opt_name: str) -> Dict[str, Any]:
    pspecs = api.param_specs()
    return {"params": pspecs, "opt": optim_mod.state_specs(opt_name, pspecs),
            "step": ()}


def make_train_step(api: ModelAPI, optimizer: Optimizer, *,
                    remat: bool = True,
                    grad_compress: bool = False) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""

    def train_step(state, batch):
        def loss_fn(params):
            return api.loss(params, batch, remat=remat)

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        if grad_compress:
            grads = optim_mod.compress_grads(grads)
        gnorm = optim_mod.global_norm(grads)
        updates, opt_state = optimizer.update(grads, state["opt"], state["params"])
        params = optim_mod.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt": opt_state, "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_eval_step(api: ModelAPI) -> Callable:
    def eval_step(state, batch):
        return api.loss(state["params"], batch, remat=False)
    return eval_step


def with_step_hooks(step_fn: Callable, *,
                    before: Callable = None, after: Callable = None) -> Callable:
    """Wrap a compiled train step with host-side hooks.

    ``before(state, batch)`` runs on the host immediately before dispatching
    the step — this is the trainer-layer seam the fault injector
    (``repro.core.faults.FaultInjector.before_step``) fires through, so
    scripted crashes/stalls happen exactly where the step executes;
    ``after(new_state, metrics)`` runs once the step returns. Apply to the
    *jitted* callable: the hooks stay outside the traced computation and
    run on every invocation (not once at trace time).
    """
    def wrapped(state, batch):
        if before is not None:
            before(state, batch)
        new_state, metrics = step_fn(state, batch)
        if after is not None:
            after(new_state, metrics)
        return new_state, metrics

    return wrapped


# --- DLRM ---------------------------------------------------------------------
def make_dlrm_train_state(cfg: DLRMConfig, optimizer: Optimizer,
                          key, layout=None) -> Dict[str, Any]:
    """Fresh DLRM train state {params, opt, step} (shape source for restores).

    ``layout`` (a ``PaddedLayout``) builds the pooled stores — and their
    optimizer-state mirrors — on the padded physical layout; row values are
    bit-identical to the flat init from the same key.
    """
    from repro.models.dlrm import init_dlrm
    params = init_dlrm(cfg, key, layout=layout)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def dlrm_train_state_specs(cfg: DLRMConfig, opt_name: str,
                           layout=None) -> Dict[str, Any]:
    """Logical-axis spec tree mirroring ``make_dlrm_train_state``'s output."""
    from repro.models.dlrm import dlrm_param_specs
    pspecs = dlrm_param_specs(cfg, layout=layout)
    return {"params": pspecs, "opt": optim_mod.state_specs(opt_name, pspecs),
            "step": ()}


def make_dlrm_train_step(cfg: DLRMConfig, optimizer: Optimizer,
                         grad_compress: bool = False, *,
                         table_hot=None, layout=None, plan=None) -> Callable:
    """DLRM train step compiled against one ``EmbeddingPlan``.

    ``plan`` bakes every static knob of the fused embedding engine into the
    compiled step — the hot-row cache plan, the padded physical placement,
    and whether the step runs the fused sparse backward + row-wise
    optimizer update (``plan.sparse_update``, requires an optimizer with an
    ``update_rows`` seam; otherwise the dense path runs). The legacy
    ``table_hot``/``layout`` kwargs build the config's default plan. A live
    re-plan recompiles with a new plan.
    """
    if plan is None:
        plan = cfg.embedding_plan(table_hot=table_hot, layout=layout)
    if plan.sparse_update and optimizer.update_rows is not None:
        return _make_dlrm_sparse_step(cfg, optimizer, grad_compress, plan)

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: dlrm_loss(p, batch, cfg, plan=plan))(state["params"])
        with scope("opt_update"):
            if grad_compress:
                grads = optim_mod.compress_grads(grads)
            gnorm = optim_mod.global_norm(grads)
            updates, opt_state = optimizer.update(grads, state["opt"],
                                                  state["params"])
            params = optim_mod.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt": opt_state, "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def _split_opt_state(opt_state, sparse_keys):
    """Split a dict-of-mirrors optimizer state at the pooled-store leaves.

    Entries mirroring the param tree (dicts containing every sparse key)
    are split into a dense remainder + one slice per pooled store; shared
    scalars (adam's ``count``) stay in the dense state AND ride along in
    every per-leaf slice, as ``Optimizer.update_rows`` expects.
    """
    dense_state, leaf_state = {}, {k: {} for k in sparse_keys}
    for name, sub in opt_state.items():
        if isinstance(sub, dict) and all(k in sub for k in sparse_keys):
            dense_state[name] = {k: v for k, v in sub.items()
                                 if k not in sparse_keys}
            for k in sparse_keys:
                leaf_state[k][name] = sub[k]
        else:
            dense_state[name] = sub
            for k in sparse_keys:
                leaf_state[k][name] = sub
    return dense_state, leaf_state


def _make_dlrm_sparse_step(cfg: DLRMConfig, optimizer: Optimizer,
                           grad_compress: bool, plan) -> Callable:
    """The fused sparse-update DLRM step (``plan.sparse_update=True``).

    Instead of materializing dense (R, D) gradients for the pooled stores
    and letting the optimizer touch every row, the step (a) differentiates
    only the dense interaction network via ``jax.vjp`` at the
    ``dlrm_embeddings`` seam, (b) turns each store's bag cotangent into
    deduped COO row grads (``ops.sparse_row_grads``, a ``SparseRowGrad``
    grad leaf), and (c) applies the row-wise optimizer update to exactly
    those rows (``Optimizer.update_rows`` → the fused row-update kernel,
    moments updated in place in the pool layout). Clipping happens once
    over the joint dense+sparse tree (``optimizer.clip_norm``), so the
    dense-subtree clip inside ``optimizer.update`` is an exact no-op.
    """
    from repro.kernels import ops as kernel_ops
    from repro.models import dlrm as dlrm_mod

    sparse_keys = dlrm_mod.sparse_param_keys(cfg)
    emb_of = {"tables": "deep", "wide": "wide"}
    plan_of = {"tables": plan, "wide": plan.with_combiner("sum")}

    def train_step(state, batch):
        params = state["params"]
        embs = dlrm_mod.dlrm_embeddings(params, batch, cfg, plan)
        dense_params = {k: v for k, v in params.items()
                        if k not in sparse_keys}
        loss, vjp = jax.vjp(
            lambda dp, e: dlrm_mod.dlrm_loss_from_embeddings(
                dp, batch, e, cfg),
            dense_params, embs)
        dense_grads, g_embs = vjp(jnp.ones((), loss.dtype))

        grads = dict(dense_grads)
        for k in sparse_keys:
            pool = dlrm_mod._pool2d(params[k], plan.layout)
            rows, vals, _ = kernel_ops.sparse_row_grads(
                pool, batch["sparse"], g_embs[emb_of[k]], plan=plan_of[k])
            grads[k] = optim_mod.SparseRowGrad(rows, vals)

        with scope("opt_update"):
            if grad_compress:
                grads = optim_mod.compress_grads(grads)
            gnorm = optim_mod.global_norm(grads)
            if optimizer.clip_norm is not None:
                grads, _ = optim_mod.clip_by_global_norm(grads,
                                                         optimizer.clip_norm)

            dense_state, leaf_state = _split_opt_state(state["opt"], sparse_keys)
            dense_only = {k: v for k, v in grads.items() if k not in sparse_keys}
            updates, new_dense_state = optimizer.update(
                dense_only, dense_state, dense_params)
            new_params = dict(optim_mod.apply_updates(dense_params, updates))
            new_opt = dict(new_dense_state)
            for k in sparse_keys:
                store = params[k]
                pool = dlrm_mod._pool2d(store, plan.layout)
                leaf = {name: (dlrm_mod._pool2d(arr, plan.layout)
                               if getattr(arr, "shape", None) == store.shape
                               else arr)
                        for name, arr in leaf_state[k].items()}
                new_pool, new_leaf = optimizer.update_rows(
                    grads[k].rows, grads[k].vals, leaf, pool)
                new_params[k] = new_pool.reshape(store.shape)
                for name, arr in new_leaf.items():
                    new_opt[name] = dict(new_opt[name])
                    new_opt[name][k] = arr.reshape(leaf_state[k][name].shape)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step
