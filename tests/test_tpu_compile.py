"""The main path compiled for a TPU v5e chip that is described, not attached.

Interpret mode runs the Pallas kernel bodies on CPU but accepts block
shapes, slices and DMAs that Mosaic, the TPU kernel compiler, refuses. Here
the installed TPU compiler compiles, at the published ``wide_deep`` widths
(B=512, T=26 tables, H=4 lookups, D=16 and the D=1 wide pool):

* the fused embedding forward, plain and with the hot-row cache + weights;
* the adagrad and adam row-update kernels;
* the whole train step, dense and with the fused sparse update;
* the dense step over all four chips of the host, pooled store on "model"
  and batch on "data", as (data=1, model=4) and (data=2, model=2).

Each compiled program must contain the kernel (``tpu_custom_call``) and fit
one chip's 16 GB of HBM; the one-chip step must carry no pool through a
``while`` loop (tile-aligned PS shards keep every pool reshape a copy). Nothing runs: these are compiles, not chip runs.
The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.dlrm_models import WIDE_DEEP
from repro.kernels import fused_embedding as fe
from repro.kernels import fused_update as fu
from repro.kernels import ops
from repro.sharding.policy import padded_layout_for_ranges, uniform_vocab_ranges
from repro.train import optim, trainer

HBM_BYTES = 16e9                       # one v5e chip
CFG = dataclasses.replace(WIDE_DEEP, zipf_alpha=1.05, hot_rows_k=64)
B, T, H = CFG.batch_size, CFG.n_tables, CFG.multi_hot
R = CFG.total_embedding_rows


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs can be cached but never read back
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """Kernel selection as on a TPU backend (this process runs on CPU)."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    assert ops.get_default_impl() == "pallas"


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_on_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("D", [16, 1])
@pytest.mark.parametrize("cached_weighted", [False, True])
def test_fused_forward_compiles(one_chip, D, cached_weighted):
    plan = CFG.embedding_plan(table_hot=None if cached_weighted else (0,) * T)
    args = [_spec(one_chip, (R, D)), _spec(one_chip, (B, T, H), jnp.int32)]
    if cached_weighted:
        args.append(_spec(one_chip, (B, T, H)))
    _compile_on_chip(lambda *a: fe.fused_embedding_bag(
        *a, method="pallas", plan=plan), *args)


@pytest.mark.parametrize("D", [16, 1])
def test_adagrad_row_update_compiles(one_chip, D):
    N = B * T * H
    _compile_on_chip(
        lambda p, a, r, v: fu.adagrad_row_update(p, a, r, v, lr=0.05,
                                                 method="pallas"),
        _spec(one_chip, (R, D)), _spec(one_chip, (R, D)),
        _spec(one_chip, (N,), jnp.int32), _spec(one_chip, (N, D)))


def test_adam_row_update_compiles(one_chip):
    N, D = B * T * H, 16
    _compile_on_chip(
        lambda p, m, v, r, g: fu.adam_row_update(p, m, v, r, g, lr=1e-3,
                                                 count=1.0, method="pallas"),
        _spec(one_chip, (R, D)), _spec(one_chip, (R, D)),
        _spec(one_chip, (R, D)), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N, D)))


@pytest.mark.parametrize("sparse_update", [False, True])
def test_full_width_train_step_compiles(one_chip, tpu_backend, sparse_update):
    opt = optim.make("adagrad", 0.05)
    layout = padded_layout_for_ranges(uniform_vocab_ranges(R, 4))
    plan = CFG.embedding_plan(layout=layout, sparse_update=sparse_update)
    state = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda k: trainer.make_dlrm_train_state(
            CFG, opt, k, layout=layout), jax.random.PRNGKey(0)))
    batch = {"dense": _spec(one_chip, (B, CFG.n_dense)),
             "sparse": _spec(one_chip, (B, T, H), jnp.int32),
             "label": _spec(one_chip, (B,))}
    compiled = _compile_on_chip(
        trainer.make_dlrm_train_step(CFG, opt, plan=plan), state, batch)
    text = compiled.as_text()
    # forward kernels (deep + wide), plus the row updates on the sparse path
    assert text.count("tpu_custom_call") >= (4 if sparse_update else 2)
    # tile-aligned shards: no reshape of a pool lowers to an element loop
    loops = _pool_loops(text, layout.max_range)
    assert not loops, loops


def _pool_loops(text, rows):
    """The ``while`` loops of an HLO text that carry an f32 array of at
    least ``rows`` elements, as ``(instruction, shape)`` pairs."""
    found = []
    for line in text.splitlines():
        if " while(" not in line or " = " not in line:
            continue
        name, rest = line.split(" = ", 1)
        for dims in re.findall(r"f32\[([\d,]*)\]", rest.split(" while(")[0]):
            if math.prod(int(d) for d in dims.split(",") if d) >= rows:
                found.append((name.strip(), f"f32[{dims}]"))
    return found


def _nbytes(x, shard_shape=None):
    return math.prod(shard_shape or x.shape) * jnp.dtype(x.dtype).itemsize


@pytest.mark.parametrize("data,model", [(1, 4), (2, 2)])
def test_sharded_train_step_compiles_on_four_chips(topo, tpu_backend, data,
                                                   model):
    """The pooled store split over the "model" axis of the host's four chips,
    with the batch split over "data": GSPMD cannot partition a Mosaic
    kernel, so the step all-gathers each pool and runs the kernel on each
    chip's batch shard (``shard_map``) — and must compile."""
    from repro.launch.mesh import make_local_mesh
    from repro.train import elastic
    opt = optim.make("adagrad", 0.05)
    layout = padded_layout_for_ranges(uniform_vocab_ranges(R, 4))
    plan = CFG.embedding_plan(layout=layout)
    mesh = make_local_mesh(data=data, model=model, devices=topo.devices)
    step, shardings, batch_sh = elastic.make_dlrm_mesh_step(
        CFG, opt, "adagrad", mesh, plan)
    shapes = jax.eval_shape(lambda k: trainer.make_dlrm_train_state(
        CFG, opt, k, layout=layout), jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, shardings)
    batch = {"dense": _spec(batch_sh, (B, CFG.n_dense)),
             "sparse": _spec(batch_sh, (B, T, H), jnp.int32),
             "label": _spec(batch_sh, (B,))}
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    ma = compiled.memory_analysis()          # per device
    # the arguments are each chip's shards, not the one-chip state
    leaves = jax.tree.leaves(state) + jax.tree.leaves(batch)
    shard_bytes = sum(_nbytes(x, x.sharding.shard_shape(x.shape))
                      for x in leaves)
    whole_bytes = sum(_nbytes(x) for x in jax.tree.leaves(shapes))
    assert shard_bytes < 1.1 * whole_bytes / model
    assert ma.argument_size_in_bytes <= 1.01 * shard_bytes, (
        ma.argument_size_in_bytes, shard_bytes)
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < HBM_BYTES
    # the tables pool comes back whole around the kernel
    assert f"f32[{layout.n_ps},{layout.max_range},16]" in text
    assert "all-gather" in text
