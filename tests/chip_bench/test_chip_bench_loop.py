"""The benchmark's loop is the launcher's: the same losses, step for step."""
import numpy as np
import pytest

from repro.configs.dlrm_models import WIDE_DEEP, XDEEPFM, reduced_dlrm
from repro.data.synthetic import criteo_batch
from repro.launch import train
from repro.sharding.policy import padded_layout_for_ranges, uniform_vocab_ranges
from repro.train import optim, trainer

#: past the launcher's first loss read every ``loop.LOG_EVERY`` steps
STEPS = 21


@pytest.mark.parametrize("base", [WIDE_DEEP, XDEEPFM], ids=lambda c: c.name)
def test_loop_losses_equal_the_launchers(base):
    import dataclasses

    import jax
    import loop
    run = train.main(["--arch", base.name, "--steps", str(STEPS),
                      "--padded-shards", "--hot-rows", "4",
                      "--zipf-alpha", "1.05"])
    cfg = dataclasses.replace(reduced_dlrm(base), zipf_alpha=1.05, hot_rows_k=4)
    opt = optim.make("adagrad", 3e-3)       # the launcher's default --lr
    layout = padded_layout_for_ranges(uniform_vocab_ranges(cfg.total_embedding_rows, 4))
    B = cfg.batch_size
    ring = [criteo_batch(cfg, 11, np.arange(i * B, (i + 1) * B)) for i in range(STEPS)]
    state = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0), layout=layout)
    lp = loop.LauncherLoop(cfg, opt, cfg.embedding_plan(layout=layout), layout,
                           state, ring, n_ps=4, imbalance_threshold=1.2)
    for _ in range(STEPS):
        lp.step()
    assert [float(x) for x in lp.losses] == [run["losses"][s] for s in range(STEPS)]
    assert lp.tracker.observes == STEPS
