"""The plain reference of a DLRM training step, in straightforward jax.numpy.

It imports nothing of the program. Its weights come from the kind's file
under ``reference/`` (the same call that makes the program's initial state);
its lookups are a gather and a sum; its loss is binary cross-entropy with
logits; its optimizer is dense adagrad over every row. In float32 it runs
at the matmul precision the configuration states (``precision.matmul``):
at a finer one the gaps of the first steps swing by seed with the
precision itself, not with how the program computes (``PERF.md``). Run in
bfloat16 (every weight, accumulator and activation) it is the control,
which the comparison has to call not correct.

``train`` gives the readings the comparison uses:

* ``losses``  - the loss of each step;
* ``grad_sq`` - per leaf, the sum of squares of the first gradient, read
  from adagrad's accumulator after one step (it starts at 0);
* ``change``  - per leaf, the norm of the parameters' change after all the
  steps.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def leaf_name(path) -> str:
    """``['mlp']['w0']`` -> ``mlp.w0``: one name per leaf, on both sides."""
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def named_leaves(tree) -> Dict[str, jnp.ndarray]:
    return {leaf_name(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(float(fan_in))


def mlp_init(key, mlp_dims: Sequence[int], d_in: int):
    """The deep MLP's weights, ``w{i}``/``b{i}`` then ``w_out``/``b_out``."""
    keys = jax.random.split(key, len(mlp_dims) + 1)
    mlp, prev = {}, d_in
    for li, h in enumerate(mlp_dims):
        mlp[f"w{li}"] = normal(keys[li], (prev, h), prev)
        mlp[f"b{li}"] = jnp.zeros((h,), jnp.float32)
        prev = h
    mlp["w_out"] = normal(keys[-1], (prev, 1), prev)
    mlp["b_out"] = jnp.zeros((1,), jnp.float32)
    return mlp


def mlp_apply(mlp, dense, deep):
    """The MLP over the dense features and the flattened bags -> (B,)."""
    x = jnp.concatenate([dense, deep.reshape(deep.shape[0], -1)], axis=-1)
    n = sum(1 for k in mlp if k.startswith("b") and k != "b_out")
    for li in range(n):
        x = jax.nn.relu(x @ mlp[f"w{li}"] + mlp[f"b{li}"])
    return (x @ mlp["w_out"] + mlp["b_out"])[:, 0]


def table_offsets(table_rows: Sequence[int]) -> np.ndarray:
    return np.concatenate(([0], np.cumsum(table_rows)[:-1])).astype(np.int32)


def bag(pool, sparse, offsets):
    """Sum of each bag's rows: pool (R, D), sparse (B, T, H) -> (B, T, D)."""
    rows = jnp.take(pool, sparse + offsets[None, :, None], axis=0)
    return jnp.sum(rows, axis=2)


def bce(logit, label):
    return jnp.mean(jnp.maximum(logit, 0) - logit * label
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def loss_fn(logit_fn: Callable, params, batch, offsets, keep: int):
    """Mean loss over the first ``keep`` samples of the batch (all of them,
    except in the half-batch fault)."""
    embs = {"deep": bag(params["tables"], batch["sparse"], offsets)}
    if "wide" in params:
        embs["wide"] = bag(params["wide"], batch["sparse"], offsets)
    logit = logit_fn(params, batch["dense"], embs)
    return bce(logit[:keep], batch["label"][:keep].astype(logit.dtype))


def make_step(logit_fn: Callable, offsets, *, lr: float, eps: float,
              keep: int):
    """One dense adagrad step of the reference: (params, acc, batch) ->
    (params, acc, loss)."""
    def step(params, acc, batch):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(logit_fn, p, batch, offsets, keep))(params)
        acc = jax.tree.map(lambda a, gi: a + gi * gi, acc, g)
        params = jax.tree.map(
            lambda p, gi, a: p - lr * gi / (jnp.sqrt(a) + eps), params, g, acc)
        return params, acc, loss
    return step


@functools.lru_cache(maxsize=8)
def _compiled_step(logit_fn, offsets_key, lr, eps, keep, precision):
    offsets = jnp.asarray(np.asarray(offsets_key, np.int32))
    step = make_step(logit_fn, offsets, lr=lr, eps=eps, keep=keep)

    def run(params, acc, batch):
        with jax.default_matmul_precision(precision):
            return step(params, acc, batch)
    return jax.jit(run)


@jax.jit
def sum_sq(tree):
    """Per leaf, the sum of squares (as float32)."""
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
            for k, v in named_leaves(tree).items()}


@jax.jit
def change_norms(new, old):
    """Per leaf, the norm of ``new - old``; ``old`` by leaf name."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                           - old[k].astype(jnp.float32))))
            for k, v in named_leaves(new).items()}


def train(logit_fn: Callable, params: Mapping, batches: List[Mapping], *,
          table_rows: Sequence[int], lr: float, eps: float,
          matmul_precision: str, dtype=jnp.float32,
          half: bool = False) -> Dict:
    """Run ``len(batches)`` reference steps from ``params``; the readings.

    ``matmul_precision`` is the configuration's; ``dtype`` bfloat16 makes
    the control, at the default precision; ``half`` keeps the first half of
    every batch and takes the mean over it (a fault the comparison must
    catch).
    """
    precision = matmul_precision if dtype == jnp.float32 else "default"
    B = int(np.asarray(batches[0]["label"]).shape[0])
    keep = B // 2 if half else B
    step = _compiled_step(logit_fn, tuple(int(o) for o in table_offsets(
        table_rows)), float(lr), float(eps), keep, precision)
    p = jax.tree.map(lambda x: jnp.asarray(x, dtype), dict(params))
    p0 = p
    acc = jax.tree.map(jnp.zeros_like, p)
    losses, grad_sq = [], None
    for i, b in enumerate(batches):
        dev = {"dense": jnp.asarray(b["dense"], dtype),
               "sparse": jnp.asarray(b["sparse"], jnp.int32),
               "label": jnp.asarray(b["label"], jnp.float32)}
        p, acc, loss = step(p, acc, dev)
        losses.append(float(loss))
        if i == 0:
            grad_sq = {k: float(v) for k, v in sum_sq(acc).items()}
    change = {k: float(v) for k, v in change_norms(p, named_leaves(p0)).items()}
    return {"losses": losses, "grad_sq": grad_sq, "change": change}
