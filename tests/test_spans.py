"""The program's layer names reach the compiled step: every device scope of
``repro.spans`` is in the op metadata of the tiny DLRM step, and each
Pallas kernel carries its own name."""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs.dlrm_models import WIDE_DEEP, reduced_dlrm
from repro.data.synthetic import criteo_batch
from repro.kernels import ops
from repro.sharding.policy import padded_layout_for_ranges, uniform_vocab_ranges
from repro.train import optim, trainer


def _kernel_names(jaxpr) -> set:
    """Names of the ``pallas_call``s in a jaxpr and its sub-jaxprs."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    names |= _kernel_names(sub)
    return names


@pytest.mark.parametrize("sparse_update,kernels", [
    (False, {"emb_fwd"}), (True, {"emb_fwd", "row_update_adagrad"})],
    ids=["dense_update", "sparse_update"])
def test_the_step_carries_every_scope_and_kernel_name(sparse_update, kernels):
    cfg = dataclasses.replace(reduced_dlrm(WIDE_DEEP), hot_rows_k=4)
    opt = optim.make("adagrad", 3e-3)
    layout = padded_layout_for_ranges(uniform_vocab_ranges(cfg.total_embedding_rows, 4))
    plan = cfg.embedding_plan(layout=layout, sparse_update=sparse_update)
    state = jax.eval_shape(lambda: trainer.make_dlrm_train_state(
        cfg, opt, jax.random.PRNGKey(0), layout=layout))
    batch = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
             for k, v in criteo_batch(cfg, 0, np.arange(cfg.batch_size)).items()}
    ops.set_default_impl("interpret")
    try:
        step = jax.jit(trainer.make_dlrm_train_step(cfg, opt, plan=plan))
        text = step.lower(state, batch).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    finally:
        ops.set_default_impl(None)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    for name in spans.SCOPES:
        assert any(re.search(rf"(^|[/(]){name}([/)]|$)", p) for p in paths), name
    assert kernels <= _kernel_names(jaxpr)


def test_an_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        spans.scope("emb-fwd")
