"""The comparison that decides ``correct``: sound runs pass, the control
and the faults a training cell can have fail.

Every run here is a whole run of a cell (set-up, window, reference) at a
size a test holds, on the CPU, past the harness's look for a chip, with
the configuration's own limits.
"""
import jax.numpy as jnp
import pytest

from conftest import tiny_cell

CELLS = ["wide_deep.zipf", "xdeepfm.zipf", "wide_deep.zipf.ckpt", "wide_deep.uniform"]
SEED = 2**41 + 17


def _run(manifest, cell, **kw):
    import harness
    conf, traffic = tiny_cell(manifest, cell)
    return harness.run(manifest, cell, SEED, 0.3, conf=conf, traffic=traffic,
                       log=lambda s: None, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(manifest, cell):
    result = _run(manifest, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("config", ["wide_deep", "xdeepfm"])
def test_the_control_is_not_correct(manifest, config):
    """The reference in bfloat16 in the program's place."""
    import compare
    import harness
    import ringgen
    cell = f"{config}.zipf"
    conf, traffic = tiny_cell(manifest, cell)
    job = harness.build_job(manifest, conf, traffic)
    ring = ringgen.make_ring(SEED, conf["sizes"], traffic, n=harness.CHECK_STEPS)
    ref = harness.reference_readings(job, SEED, ring)
    control = harness.reference_readings(job, SEED, ring, dtype=jnp.bfloat16)
    ok, checks = compare.judge(compare.gaps(control, ref), conf["limits"])
    assert not ok, checks


def _unchanged(make_step):
    def make(*a, **kw):
        step = make_step(*a, **kw)

        def same_state(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return same_state
    return make


def _half_batch(make_step):
    def make(*a, **kw):
        step = make_step(*a, **kw)

        def half(state, batch):
            n = batch["label"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    return make


@pytest.mark.parametrize("cell", ["wide_deep.zipf", "xdeepfm.zipf"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(manifest, monkeypatch, cell, fault):
    from repro.train import trainer
    monkeypatch.setattr(trainer, "make_dlrm_train_step",
                        fault(trainer.make_dlrm_train_step))
    result = _run(manifest, cell)
    assert not result["correct"], result["checks"]


def test_an_altered_checkpoint_is_not_correct(manifest, monkeypatch):
    """One value of the blob altered where the save produces it."""
    from repro.core import flash_checkpoint
    flatten = flash_checkpoint._flatten

    def altered(state):
        flat = flatten(state)
        key = next(k for k in flat if "tables" in k and "acc" not in k)
        flat[key] = flat[key].copy()
        flat[key].flat[0] += 1.0
        return flat
    monkeypatch.setattr(flash_checkpoint, "_flatten", altered)
    result = _run(manifest, "wide_deep.zipf.ckpt")
    assert not result["correct"]
    assert result["checks"]["ckpt_restore_diff"]["value"] > 0


def test_the_checkpoint_window_ends_on_a_save(manifest):
    """The window runs on to the end of the checkpoint interval in which
    its time ran out, so that it holds whole intervals."""
    import harness
    _, traffic = tiny_cell(manifest, "wide_deep.zipf.ckpt")
    result = _run(manifest, "wide_deep.zipf.ckpt")
    steps = harness.CHECK_STEPS + harness.WARM_STEPS + result["attempted"]
    assert steps % traffic["ckpt_every"] == 0
