"""Hardened flash checkpoint: atomicity, checksums, newest-valid fallback.

The property tests at the bottom ride the hypothesis shim
(``tests/_hypothesis_compat``): under arbitrary combinations of truncated /
bit-flipped blobs and torn manifest dirs, ``restore`` must return the newest
fully-valid step bit-exactly or raise cleanly — never hand back damaged
state. The fork-based regression pins the atomic-rename commit point: a
SIGKILL anywhere before ``_commit``'s ``os.replace`` (even with every byte
of the staging dir already written) must leave nothing ``valid_steps``
counts as valid.
"""
import json
import os
import shutil
import signal
import tempfile

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.faults import corrupt_blob
from repro.core.flash_checkpoint import (
    CheckpointCorruptError, FlashCheckpoint,
)


def _state(x: float):
    return {"w": np.full(16, x, np.float32), "b": np.arange(4.0)}


def _dirname(step: int) -> str:
    return f"ckpt_{step:012d}"


@pytest.fixture()
def store():
    with tempfile.TemporaryDirectory() as d:
        yield FlashCheckpoint(d, keep=3, async_persist=False), d


# -------------------------------------------------------------------- basics
def test_save_restore_round_trip(store):
    ck, d = store
    ck.save(_state(1.5), 10)
    assert os.path.isdir(os.path.join(d, _dirname(10)))
    manifest = json.load(open(os.path.join(d, _dirname(10), "MANIFEST.json")))
    assert manifest["step"] == 10 and len(manifest["leaves"]) == 2
    restored, step = ck.restore(_state(0.0))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["w"]), _state(1.5)["w"])


def test_no_staging_dirs_left_behind(store):
    ck, d = store
    for s in (5, 10, 15):
        ck.save(_state(s), s)
    assert not [n for n in os.listdir(d) if ".tmp-" in n]


def test_eviction_keeps_newest(store):
    ck, d = store
    for s in (5, 10, 15, 20, 25):
        ck.save(_state(s), s)
    assert ck.valid_steps() == [15, 20, 25]     # keep=3


# ------------------------------------------------------- corruption handling
def test_corrupt_latest_falls_back_to_newest_valid(store):
    ck, d = store
    for s in (5, 10, 15):
        ck.save(_state(s), s)
    ck.drop_memory_tier()
    corrupt_blob(os.path.join(d, _dirname(15)))
    restored, step = ck.restore(_state(0.0))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["w"]), _state(10)["w"])
    assert any(e["kind"] == "corrupt_blob_fallback" and e["step"] == 15
               for e in ck.events)
    assert ck.valid_steps() == [5, 10]


def test_truncated_blob_detected(store):
    ck, d = store
    ck.save(_state(1.0), 5)
    ck.save(_state(2.0), 10)
    ck.drop_memory_tier()
    corrupt_blob(os.path.join(d, _dirname(10)), mode="truncate")
    _, step = ck.restore(_state(0.0))
    assert step == 5


def test_explicit_corrupt_step_raises(store):
    ck, d = store
    ck.save(_state(1.0), 5)
    ck.save(_state(2.0), 10)
    ck.drop_memory_tier()
    corrupt_blob(os.path.join(d, _dirname(10)))
    with pytest.raises(CheckpointCorruptError):
        ck.restore(_state(0.0), step=10)        # asked for that exact blob


def test_all_blobs_corrupt_raises_filenotfound(store):
    ck, d = store
    ck.save(_state(1.0), 5)
    ck.drop_memory_tier()
    corrupt_blob(os.path.join(d, _dirname(5)))
    with pytest.raises(FileNotFoundError, match="no valid checkpoint"):
        ck.restore(_state(0.0))


def test_memory_tier_shadows_corrupt_disk(store):
    ck, d = store
    ck.save(_state(3.0), 5)
    corrupt_blob(os.path.join(d, _dirname(5)))  # disk damaged, memory intact
    restored, step = ck.restore(_state(0.0))
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]), _state(3.0)["w"])


def test_manifest_leaf_set_mismatch_detected(store):
    ck, d = store
    ck.save(_state(1.0), 5)
    ck.drop_memory_tier()
    mpath = os.path.join(d, _dirname(5), "MANIFEST.json")
    manifest = json.load(open(mpath))
    manifest["leaves"]["['extra']"] = {"crc32": 0, "shape": [1],
                                       "dtype": "float32"}
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(FileNotFoundError):
        ck.restore(_state(0.0))                 # sole blob fails verification


# ----------------------------------------------- malformed neighbors skipped
def test_malformed_entries_skipped_and_logged(store):
    ck, d = store
    ck.save(_state(1.0), 5)
    os.makedirs(os.path.join(d, "ckpt_garbage"))
    os.makedirs(os.path.join(d, "ckpt_000000000099.tmp-123"))  # dead staging
    os.makedirs(os.path.join(d, _dirname(50)))  # step dir without manifest
    ck.drop_memory_tier()
    _, step = ck.restore(_state(0.0))           # neighbors don't break restore
    assert step == 5
    kinds = {e["kind"] for e in ck.events}
    assert {"skip_malformed", "skip_staging_dir",
            "skip_missing_manifest"} <= kinds
    ck.save(_state(2.0), 10)                    # eviction survives them too
    assert 10 in ck.valid_steps()


def test_eviction_does_not_remove_staging_or_malformed(store):
    ck, d = store
    os.makedirs(os.path.join(d, "ckpt_notastep"))
    for s in (5, 10, 15, 20):
        ck.save(_state(s), s)
    assert os.path.isdir(os.path.join(d, "ckpt_notastep"))


# ------------------------------------------------------------- legacy format
def test_legacy_npz_blob_still_restores(store):
    ck, d = store
    flat = {"['w']": _state(7.0)["w"], "['b']": _state(7.0)["b"]}
    np.savez(os.path.join(d, "ckpt_000000000007.npz"), **flat)
    restored, step = ck.restore(_state(0.0))
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]), _state(7.0)["w"])


def test_missing_leaf_raises_not_zero_fills(store):
    ck, d = store
    ck.save({"w": np.ones(4)}, 5)
    with pytest.raises(KeyError, match="missing leaf"):
        ck.restore({"w": np.zeros(4), "extra": np.zeros(2)})


def test_optional_leaves_zero_fill(store):
    ck, d = store
    ck.save({"w": np.ones(4)}, 5)
    like = {"w": np.zeros(4), "extra": np.ones(2, np.float32)}
    restored, _ = ck.restore(like, optional_leaves=("['extra']",))
    np.testing.assert_array_equal(np.asarray(restored["extra"]),
                                  np.zeros(2, np.float32))


# --------------------------------------- property: damage never lies upward
STEPS = (5, 10, 15, 20)
DAMAGE = ("none",            # leave the blob intact
          "flip",            # bit-flip bytes mid-file (bad DMA / bit rot)
          "truncate",        # cut leaves.npz in half (mid-write kill)
          "flip_manifest",   # corrupt the manifest JSON itself
          "drop_manifest",   # torn dir: data present, manifest missing
          "drop_leaves")     # torn dir: manifest present, data missing


def _apply_damage(d: str, step: int, action: str) -> None:
    path = os.path.join(d, _dirname(step))
    if action == "flip":
        corrupt_blob(path, mode="flip", seed=step)
    elif action == "truncate":
        corrupt_blob(path, mode="truncate")
    elif action == "flip_manifest":
        corrupt_blob(os.path.join(path, "MANIFEST.json"), seed=step)
    elif action == "drop_manifest":
        os.remove(os.path.join(path, "MANIFEST.json"))
    elif action == "drop_leaves":
        os.remove(os.path.join(path, "leaves.npz"))


@settings(max_examples=30, deadline=None)
@given(damage=st.lists(st.sampled_from(DAMAGE), min_size=len(STEPS),
                       max_size=len(STEPS)),
       torn_staging=st.booleans())
def test_restore_newest_fully_valid_or_clean_raise(damage, torn_staging):
    """Whatever subset of blobs is damaged however, restore returns the
    newest untouched step bit-exactly — or raises FileNotFoundError when
    none survive. Damaged steps also vanish from valid_steps()."""
    with tempfile.TemporaryDirectory() as d:
        ck = FlashCheckpoint(d, keep=len(STEPS), async_persist=False)
        for s in STEPS:
            ck.save(_state(float(s)), s)
        ck.drop_memory_tier()               # force the disk tier under test
        for s, action in zip(STEPS, damage):
            _apply_damage(d, s, action)
        if torn_staging:                    # a kill-during-save leftover
            os.makedirs(os.path.join(d, "ckpt_000000000099.tmp-1"))
        survivors = [s for s, a in zip(STEPS, damage) if a == "none"]

        if not survivors:
            with pytest.raises(FileNotFoundError, match="no valid checkpoint"):
                ck.restore(_state(0.0))
            return
        restored, step = ck.restore(_state(0.0))
        assert step == max(survivors)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      _state(float(step))["w"])
        assert ck.valid_steps() == survivors


# ------------------------------------------- regression: the commit point
def _fork_save_killed_in_pre_commit(d: str, step: int) -> int:
    """Fork a child that SIGKILLs itself inside the pre-commit window of
    ``save(step)`` — after every staging byte is written and fsynced, before
    the atomic rename. The nastiest torn-write case a real kill produces."""
    pid = os.fork()
    if pid == 0:                            # pragma: no cover - dies by signal
        ck = FlashCheckpoint(
            d, keep=3, async_persist=False,
            pre_commit_hook=lambda tmp, s: os.kill(os.getpid(),
                                                   signal.SIGKILL))
        ck.save(_state(float(step)), step)
        os._exit(1)                         # unreachable: hook killed us
    _, status = os.waitpid(pid, 0)
    assert os.WIFSIGNALED(status) and os.WTERMSIG(status) == signal.SIGKILL
    return pid


def test_midwrite_sigkill_never_counts_as_valid(tmp_path):
    """Satellite fix: kill-during-save must never leave a directory that
    ``valid_steps`` counts as valid — commit is ONE atomic rename."""
    d = str(tmp_path)
    ck = FlashCheckpoint(d, keep=3, async_persist=False)
    ck.save(_state(1.0), 5)                 # one good committed blob
    child = _fork_save_killed_in_pre_commit(d, 10)

    # the stranded staging dir is byte-complete (data + manifest written,
    # only the rename missing) yet invisible to validity and restore
    staging = os.path.join(d, f"ckpt_{10:012d}.tmp-{child}")
    assert os.path.isdir(staging)
    assert os.path.exists(os.path.join(staging, "leaves.npz"))
    assert os.path.exists(os.path.join(staging, "MANIFEST.json"))
    assert ck.valid_steps() == [5]
    ck.drop_memory_tier()
    restored, step = ck.restore(_state(0.0))
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]), _state(1.0)["w"])

    # the survivor keeps checkpointing: a later save commits normally and
    # eviction never touches the stranded staging dir
    ck.save(_state(3.0), 15)
    assert ck.valid_steps() == [5, 15]
    assert os.path.isdir(staging)


def test_commit_is_last_step_before_fault_hook(tmp_path):
    """Hook ordering pins the commit point: pre_commit sees only the
    staging path (no final dir yet); fault_hook sees only the final dir."""
    calls = []

    def pre(tmp, step):
        calls.append(("pre", os.path.basename(tmp),
                      os.path.isdir(tmp.rsplit(".tmp-", 1)[0])))

    def post(final, step):
        calls.append(("post", os.path.basename(final), os.path.isdir(final)))

    ck = FlashCheckpoint(str(tmp_path), async_persist=False,
                         pre_commit_hook=pre, fault_hook=post)
    ck.save(_state(1.0), 7)
    assert calls == [("pre", f"ckpt_{7:012d}.tmp-{os.getpid()}", False),
                     ("post", f"ckpt_{7:012d}", True)]


def test_async_persist_waits(tmp_path):
    ck = FlashCheckpoint(str(tmp_path), keep=2, async_persist=True)
    for s in (5, 10):
        ck.save(_state(s), s)
    ck.wait()
    ck.drop_memory_tier()
    _, step = ck.restore(_state(0.0))
    assert step == 10


def test_last_save_bytes_counts_the_device_leaves():
    import jax.numpy as jnp
    ck = FlashCheckpoint(None)
    ck.save({"w": jnp.ones((8, 4), jnp.float32), "ids": jnp.arange(3, dtype=jnp.int32),
             "host": np.zeros(100), "step": 7}, 1)
    assert ck.last_save_bytes == 8 * 4 * 4 + 3 * 4
