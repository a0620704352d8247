"""The launcher's per-step loop, as ``repro.launch.train.train_dlrm`` runs it.

Every step, in the launcher's order: the ``ShardDataLoader`` hands the next
batch from a ``ShardingService``; ``EmbeddingRemapper.remap_batch`` maps its
ids into the current layout; ``HotTableTracker.observe`` folds them into
the rolling counts; ``jnp.asarray`` moves the batch to the device; the
jitted ``trainer.make_dlrm_train_step`` runs; its loss stays on the device,
except that after the first step and every ``LOG_EVERY`` steps the launcher
reads it and the tracker's imbalance for its progress line, which waits for
the device and so bounds the steps queued on it. With ``ckpt_every`` the loop writes ``replan.save_with_layout`` into a
``FlashCheckpoint`` every that many steps, as the launcher does. Re-planning
stays off, the launcher's default (``--replan-every 0``).

The launcher has no seam to take its batches from elsewhere or to stop after
a time, so this loop calls the same public pieces in the same order; a test
holds its losses to ``repro.launch.train.main``'s. Each call into a layer sits
in a ``TraceAnnotation`` named ``bench/<layer>``, which the traced run reads.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.flash_checkpoint import FlashCheckpoint
from repro.core.sharding_service import HotTableTracker, ShardingService
from repro.data.pipeline import ShardDataLoader
from repro.train import replan, trainer

#: the spans of one step: loader, remap, observe, h2d, dispatch, log on
#: the steps that read the loss, ckpt on the steps that save; "window"
#: around the measured window
SPAN = "bench/"
#: the launcher's progress line: every 20 steps it reads the loss
LOG_EVERY = 20
#: the loop runs round the ring; an epoch of the service is one lap
_EPOCHS = 10 ** 9


class LauncherLoop:
    """One training job's loop over a ring of host batches.

    Args:
      cfg:      the ``DLRMConfig`` the step is built for.
      opt:      the program's optimizer.
      plan:     the ``EmbeddingPlan`` the step is compiled against.
      layout:   the padded layout the state lives on (or None).
      state:    the initial train state, on the device.
      ring:     host batches, fed round and round in order.
      n_ps, imbalance_threshold: the tracker's plan targets.
      ckpt:     a ``FlashCheckpoint`` (with ``ckpt_every``).
      step_fn:  a jitted step to reuse (default: built here).
    """

    def __init__(self, cfg, opt, plan, layout, state, ring: Sequence[Dict],
                 *, n_ps: int, imbalance_threshold: float,
                 ckpt: Optional[FlashCheckpoint] = None,
                 ckpt_every: int = 0, step_fn=None):
        B = cfg.batch_size
        self.cfg, self.opt, self.plan, self.layout = cfg, opt, plan, layout
        self.state = state
        self.step_fn = step_fn or jax.jit(trainer.make_dlrm_train_step(
            cfg, opt, plan=plan))
        self.remapper = replan.EmbeddingRemapper(cfg.table_rows)
        self.tracker = HotTableTracker(
            cfg.table_rows, n_ps=n_ps, hot_budget=cfg.hot_rows_k,
            # cooldown max(--replan-every, 1), as the launcher sets it
            trigger=imbalance_threshold, cooldown=1,
            min_lookups=4 * B * cfg.n_tables * cfg.multi_hot)
        service = ShardingService(len(ring) * B, shard_size=max(B * 8, 64),
                                  num_epochs=_EPOCHS)
        self.loader = ShardDataLoader(
            service, "worker0", lambda idx: ring[(int(idx[0]) // B) % len(ring)],
            batch_size=B)
        self.ckpt, self.ckpt_every = ckpt, ckpt_every
        self.n = 0
        self.losses: List[jax.Array] = []
        self.save_stall_s: List[float] = []     # host time blocked per save
        self.save_flatten_s: List[float] = []   # FlashCheckpoint's own count
        self.saved = None                        # (step, state) of the newest save
        self.progress = None                     # (loss, imbalance) last read

    def step(self) -> None:
        with TraceAnnotation(SPAN + "loader"):
            raw = self.loader.next_batch()
        if raw is None:
            raise RuntimeError("the sharding service ran out of samples")
        with TraceAnnotation(SPAN + "remap"):
            batch = self.remapper.remap_batch(raw)
        with TraceAnnotation(SPAN + "observe"):
            self.tracker.observe(batch["sparse"])
        with TraceAnnotation(SPAN + "h2d"):
            dev = {k: jnp.asarray(v) for k, v in batch.items()}
        with TraceAnnotation(SPAN + "dispatch"):
            self.state, metrics = self.step_fn(self.state, dev)
        self.losses.append(metrics["loss"])
        self.n += 1
        if self.n % LOG_EVERY == 0 or self.n == 1:
            with TraceAnnotation(SPAN + "log"):
                self.progress = (float(metrics["loss"]),
                                 self.tracker.imbalance())
        if self.ckpt_every and self.n % self.ckpt_every == 0:
            with TraceAnnotation(SPAN + "ckpt"):
                self.save()

    def save(self) -> None:
        """One layout-stamped save, as the launcher makes it. The launcher's
        ``int(state["step"])`` waits for the queued steps; the count of host
        time blocked starts after it, from a drained pipeline, and covers
        ``save_with_layout`` (unpad, ``device_get``, flatten). The drain's
        cost to throughput shows in the window's rate."""
        step = int(self.state["step"])
        t0 = time.perf_counter()
        replan.save_with_layout(self.ckpt, self.state, step, self.remapper,
                                None, None, layout=self.layout)
        self.save_stall_s.append(time.perf_counter() - t0)
        self.save_flatten_s.append(self.ckpt.last_save_seconds)
        self.saved = (step, self.state)

    def run_for(self, seconds: float) -> float:
        """Steps until ``seconds`` have passed, then waits for the device;
        returns the elapsed seconds, that wait included. With ``ckpt_every``
        the window goes on to the end of the checkpoint interval in which
        its time ran out, its save included, so that it holds whole
        intervals after the first, and where in an interval the time runs
        out does not move the rate."""
        t0 = time.perf_counter()
        with TraceAnnotation(SPAN + "window"):
            while (time.perf_counter() - t0 < seconds
                   or (self.ckpt_every and self.n % self.ckpt_every)):
                self.step()
            jax.block_until_ready(self.state)
        return time.perf_counter() - t0
