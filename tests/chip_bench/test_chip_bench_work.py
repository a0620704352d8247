"""Each model kind's FLOP count is the one the program's jaxpr holds."""
import dataclasses

import pytest

from conftest import TINY_SIZES


@pytest.mark.parametrize("kind", ["wide_deep", "xdeepfm"])
@pytest.mark.parametrize("sizes", [TINY_SIZES, "config"], ids=["tiny", "full"])
def test_forward_flops_match_flops_of(manifest, kind, sizes):
    import jax
    import jax.numpy as jnp
    import harness
    from repro.kernels import ops
    from repro.launch.costs import flops_of
    from repro.models.dlrm import dlrm_forward
    conf = manifest.config(kind)
    if sizes != "config":
        conf = dict(conf, sizes={k: sizes[k] for k in conf["sizes"]})
    job = harness.build_job(manifest, conf, manifest.traffic("uniform"))
    cfg = job.cfg
    params = jax.eval_shape(lambda k: job.ref.init(k, conf["sizes"]),
                            jax.random.PRNGKey(0))
    batch = {"dense": jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_dense), jnp.float32),
             "sparse": jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_tables, cfg.multi_hot),
                                            jnp.int32)}
    assert ops.get_default_impl() == "xla"
    plan = dataclasses.replace(job.plan, layout=None)
    want = flops_of(lambda p, b: dlrm_forward(p, b, cfg, plan=plan), params, batch)
    assert job.work.fwd_flops(conf["sizes"]) == want
    assert job.work.step_flops(conf["sizes"]) == 3 * want
