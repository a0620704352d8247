"""Sharding-policy resolution + real multi-device execution (subprocess).

The child process fakes 8 CPU devices (the parent must keep seeing 1, per the
dry-run isolation rule), builds meshes, checks rule resolution for every
(arch × shape), runs a REAL sharded train step, and performs an ELASTIC
RE-MESH: checkpoint on a (4,2) mesh, restore + resume on (2,4).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, tempfile
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import SHAPES, reduce_config
    from repro.configs.registry import ARCHS
    from repro.sharding.policy import make_policy, use_policy, logical_spec
    from repro.models.registry import build_model
    from repro.train import optim, trainer, elastic
    from repro.core.flash_checkpoint import FlashCheckpoint
    from repro.launch.mesh import make_local_mesh

    assert len(jax.devices()) == 8

    # ---- rule resolution for every (arch x shape) on a 4x2 mesh ----------
    mesh = make_local_mesh(4, 2)
    for arch, cfg in ARCHS.items():
        for shape in SHAPES.values():
            pol = make_policy(mesh, cfg, shape)
            spec = pol.spec(("batch", "qseq", "heads", None))
            used = [a for part in spec if part for a in
                    (part if isinstance(part, tuple) else (part,))]
            assert len(used) == len(set(used)), (arch, shape.name, spec)

    # decode policy: small models replicate weights across "data" (no FSDP
    # gather per token); mixtral-8x22b (too big per model shard) keeps FSDP
    pol_small = make_policy(mesh, ARCHS["llama3.2-3b"], SHAPES["decode_32k"])
    assert pol_small.rules["fsdp"] == ()
    pol_big = make_policy(mesh, ARCHS["mixtral-8x22b"], SHAPES["decode_32k"])
    assert pol_big.rules["fsdp"] == ("data",)

    # ---- real sharded training + elastic re-mesh -------------------------
    cfg = reduce_config(ARCHS["llama3.2-3b"], d_model=64, n_heads=4,
                        n_kv_heads=2, head_dim=16, vocab_size=256)
    api = build_model(cfg)
    opt = optim.adam(1e-3)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=8)

    def run_steps(mesh_shape, state_host, n, ckpt):
        mesh = make_local_mesh(*mesh_shape)
        pol = make_policy(mesh, cfg, shape)
        with mesh, use_policy(pol):
            shardings = elastic.state_shardings(api, "adam", pol)
            if state_host is None:
                state = trainer.make_train_state(api, opt, jax.random.PRNGKey(0))
                state = jax.device_put(state, shardings)
            else:
                like = jax.eval_shape(
                    lambda k: trainer.make_train_state(api, opt, k),
                    jax.random.PRNGKey(0))
                state, _ = ckpt.restore(like, shardings=shardings)
            step = jax.jit(trainer.make_train_step(api, opt, remat=True),
                           in_shardings=(shardings, None),
                           out_shardings=(shardings, None))
            batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                     "targets": jnp.ones((8, 32), jnp.int32)}
            losses = []
            for _ in range(n):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            return state, losses

    ckpt = FlashCheckpoint(None)
    state, losses_a = run_steps((4, 2), None, 3, ckpt)
    ckpt.save(state, 3)
    # elastic re-mesh: same training continues on a different mesh layout
    state2, losses_b = run_steps((2, 4), "restore", 3, ckpt)
    assert losses_b[0] < losses_a[0], (losses_a, losses_b)
    assert all(np.isfinite(losses_a + losses_b))
    print("MULTIDEVICE_OK", losses_a, losses_b)
""")


@pytest.mark.slow
def test_multidevice_policy_and_elastic_remesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEVICE_OK" in proc.stdout


_DLRM_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.dlrm_models import WIDE_DEEP, reduced_dlrm
    from repro.data.synthetic import criteo_batch
    from repro.kernels import ops
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.policy import (padded_layout_for_ranges,
                                       uniform_vocab_ranges)
    from repro.train import elastic, optim, trainer

    assert len(jax.devices()) == 4
    data, model = int(sys.argv[1]), int(sys.argv[2])
    cfg = dataclasses.replace(reduced_dlrm(WIDE_DEEP), zipf_alpha=1.05,
                              hot_rows_k=8)
    opt = optim.make("adagrad", 0.05)
    layout = padded_layout_for_ranges(
        uniform_vocab_ranges(cfg.total_embedding_rows, 4))
    plan = cfg.embedding_plan(layout=layout)
    state0 = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0),
                                           layout=layout)
    B = cfg.batch_size
    batches = [{k: jnp.asarray(v) for k, v in criteo_batch(
        cfg, 11, np.arange(s * B, (s + 1) * B)).items()} for s in range(3)]

    def run(step_fn, state, place=lambda b: b):
        out = []
        for b in batches:
            state, m = step_fn(state, place(b))
            out.append(float(m["loss"]))
        return state, out

    one = jax.jit(trainer.make_dlrm_train_step(cfg, opt, plan=plan))
    _, ref = run(one, jax.device_put(state0, jax.devices()[0]))

    mesh = make_local_mesh(data=data, model=model)
    got = {}
    # "interpret" runs the kernel bodies per batch shard under shard_map,
    # the path a TPU mesh takes; "xla" is partitioned by GSPMD
    for impl in ("xla", "interpret"):
        ops.set_default_impl(impl)
        step, shardings, batch_sh = elastic.make_dlrm_mesh_step(
            cfg, opt, "adagrad", mesh, plan)
        state = jax.device_put(state0, shardings)
        pool = state["params"]["tables"]
        assert len(pool.sharding.device_set) == 4
        # n_ps / model PS shards on each device
        assert pool.addressable_shards[0].data.shape[0] == 4 // model
        end, got[impl] = run(step, state, lambda b: jax.device_put(b, batch_sh))
        assert end["params"]["tables"].sharding.is_equivalent_to(
            shardings["params"]["tables"], 3)
    np.testing.assert_allclose(got["xla"], ref, rtol=1e-6)
    # f32 bag sums in the kernel's order vs XLA's reduce
    np.testing.assert_allclose(got["interpret"], ref, rtol=1e-5)
    print("MESH_DLRM_OK", ref, got)
""")


@pytest.mark.parametrize("data,model", [(1, 4), (2, 2)])
def test_sharded_dlrm_step_matches_one_device(data, model):
    """The pooled store sharded over the "model" axis of a (1, 4) and a
    (2, 2) mesh (``elastic.make_dlrm_mesh_step``, the chip smoke's
    four-chip path at reduced size) trains with the single-device losses,
    through the XLA lookup and through the kernel bodies run per batch
    shard."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _DLRM_CHILD, str(data), str(model)], env=env,
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_DLRM_OK" in proc.stdout
