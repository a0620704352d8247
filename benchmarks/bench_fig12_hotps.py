"""Fig 12: hot-PS handling — no-intervention vs stop-and-restart vs seamless.

Deterministic scenario: a PS goes hot (3 % effective speed) 5 minutes into a
job. Three strategies resolve it; the paper reports DLRover-RM cutting JCT by
36.4 % (vs no intervention) and 27.6 % (vs traditional migration), saving
~5 min of provisioning overlap and ~3 min of checkpoint time (flash vs RDS).

Also measures a REAL flash-checkpoint: in-memory save/restore of a ~40 MB
train state vs synchronous npz persistence.
"""
from __future__ import annotations

import tempfile
import time
from typing import List

import jax
import numpy as np

from benchmarks.common import Row, fast_mode
from repro.core.migration import MigrationPlan
from repro.sim.cluster import CloudSim, TIMINGS
from repro.sim.workload import generate_jobs


def _jct_with_strategy(strategy: str, seed: int = 9) -> float:
    """Same allocation for every strategy; only the hot-PS mitigation differs
    (isolates the mechanism, like the paper's Fig 12). The job runs with a
    small PS fleet (p=2, the paper's small-cluster regime) so one hot PS
    actually gates the iteration."""
    import dataclasses
    from repro.core.perf_model import JobResources
    jobs = generate_jobs(1, seed=seed, mean_msamples=40.0)
    jobs[0] = dataclasses.replace(
        jobs[0], oracle=JobResources(w=8, p=2, cpu_w=16, cpu_p=8, mem_p=32.0))
    sim = CloudSim("static_tuned", total_cpu=8192, total_mem_gb=65536, seed=3,
                   enable_failures=False, hotps_rate_per_pod_per_day=0.0)
    orig = CloudSim._throughput
    injected = [False]

    def patched(self, rj, now):
        if not injected[0] and now >= 300.0:
            injected[0] = True
            rj.record.hot_pses += 1
            if strategy == "dlrover":
                # seamless: provisioning overlaps training; flash-ckpt sync
                rj.hotps_until = now + TIMINGS.provision_s
                sync = TIMINGS.flash_ckpt_save_s + TIMINGS.flash_ckpt_load_s
                rj.blocked_until = now + TIMINGS.provision_s + sync
                rj.record.downtime_s += sync
            elif strategy == "traditional":
                # stop-and-restart: pause, RDS ckpt, provision, load
                dt = (TIMINGS.rds_ckpt_save_s + TIMINGS.provision_s
                      + TIMINGS.rds_ckpt_load_s)
                rj.hotps_until = now + dt
                rj.blocked_until = now + dt
                rj.record.downtime_s += dt
            else:
                rj.hotps_until = now + 3600.0          # unhealthy, no action
        return orig(self, rj, now)

    CloudSim._throughput = patched
    try:
        res = sim.run(jobs, horizon_s=10 * 3600)
    finally:
        CloudSim._throughput = orig
    return res.records[0].jct_s or float("nan")


def run() -> List[Row]:
    rows: List[Row] = []
    jct_none = _jct_with_strategy("none")
    jct_trad = _jct_with_strategy("traditional")
    jct_dlr = _jct_with_strategy("dlrover")
    rows.append(("jct_min.no_intervention", jct_none / 60, "minutes"))
    rows.append(("jct_min.traditional_migration", jct_trad / 60, "minutes"))
    rows.append(("jct_min.dlrover_seamless", jct_dlr / 60, "minutes"))
    rows.append(("reduction_vs_none", 1 - jct_dlr / jct_none, "paper: 0.364"))
    rows.append(("reduction_vs_traditional", 1 - jct_dlr / jct_trad,
                 "paper: 0.276"))

    # --- analytic downtime decomposition (MigrationPlan) --------------------
    seamless = MigrationPlan(seamless=True, use_flash_ckpt=True)
    trad = MigrationPlan(seamless=False, use_flash_ckpt=False)
    rows.append(("downtime_s.seamless_flash", seamless.downtime_seconds(),
                 "paper: seconds"))
    rows.append(("downtime_s.stop_restart_rds", trad.downtime_seconds(),
                 "paper: tens of minutes region"))

    # --- REAL flash-checkpoint timing ----------------------------------------
    from repro.core.flash_checkpoint import FlashCheckpoint
    n_arrays = 8 if fast_mode() else 40
    state = {"w": [jax.random.normal(jax.random.PRNGKey(i), (512, 512))
                   for i in range(n_arrays)]}    # ~40 MB (8 MB in fast mode)
    with tempfile.TemporaryDirectory() as d:
        ck = FlashCheckpoint(d, async_persist=False)
        ck.save(state, 1)
        mem_save = ck.last_save_seconds
        disk_save = ck.last_persist_seconds
        like = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), state)
        t0 = time.perf_counter()
        ck.restore(like, 1)
        restore_s = time.perf_counter() - t0
    rows.append(("flash_mem_save_s", mem_save, "critical path (in-memory)"))
    rows.append(("flash_disk_persist_s", disk_save, "async, off critical path"))
    rows.append(("flash_restore_s", restore_s, ""))
    rows.append(("flash_speedup", disk_save / max(mem_save, 1e-9),
                 "mem tier vs disk tier"))

    # --- hot-PS at placement time: skewed rows -> cache + balanced ranges ---
    # The same power-law row popularity that overloads one PS is what the
    # fused embedding engine's hot-row cache and the RecShard-style placement
    # plan exploit.
    import dataclasses as _dc
    from repro.configs.dlrm_models import WIDE_DEEP, reduced_dlrm
    from repro.core.sharding_service import ParameterPlacementService
    from repro.data.synthetic import criteo_batch
    from repro.sharding.policy import placement_imbalance

    cfg = _dc.replace(reduced_dlrm(WIDE_DEEP), table_rows=(512,) * 6,
                      zipf_alpha=1.05, hot_rows_k=96)
    svc = ParameterPlacementService(cfg.table_rows)
    for lo in range(0, 1024, 256):
        batch = criteo_batch(cfg, 11, np.arange(lo, lo + 256))
        svc.report_batch("w0", batch["sparse"])
    counts = svc.counts
    plan = svc.hot_plan(cfg.hot_rows_k)
    hot_mass = sum(int(counts[o:o + k].sum())
                   for o, k in zip(cfg.table_offsets, plan))
    rows.append(("hotps_cache_hit_rate", hot_mass / max(counts.sum(), 1),
                 f"VMEM cache absorbs this lookup share at K={cfg.hot_rows_k}"))
    rows.append(("hotps_cache_rows_frac",
                 sum(plan) / cfg.total_embedding_rows,
                 "cached fraction of pooled rows"))
    from repro.sharding.policy import uniform_vocab_ranges
    n_ps = 4
    uniform = uniform_vocab_ranges(cfg.total_embedding_rows, n_ps)
    rows.append(("hotps_imbalance_uniform_striping",
                 placement_imbalance(counts, uniform),
                 "max/mean PS load, uniform vocab split"))
    rows.append(("hotps_imbalance_balanced_ranges", svc.imbalance(n_ps),
                 "max/mean PS load, frequency-balanced ranges"))

    # --- padded physical placement: the plan is what GSPMD places -----------
    # Until now the balanced ranges were advisory (GSPMD NamedShardings only
    # materialize equal splits). The padded (n_ps, max_range, D) layout makes
    # them physical: these rows measure the MATERIALIZED store — real rows
    # per shard from the padding mask of an actually-padded parameter array,
    # and lookup imbalance over those physical shards — plus bit-exactness
    # of the padded fused engine against the flat XLA reference.
    import jax.numpy as jnp

    from repro.kernels.fused_embedding import fused_embedding_bag
    from repro.sharding.policy import EmbeddingPlan, padded_layout_for_ranges

    balanced = svc.ps_ranges(n_ps)
    layout = padded_layout_for_ranges(balanced)
    rng = np.random.default_rng(0)
    D = cfg.embed_dim
    pool = jnp.asarray(rng.standard_normal(
        (cfg.total_embedding_rows, D)).astype(np.float32))
    ppool = layout.pad_rows(pool)                 # the (n_ps, max_range, D) store
    mask = layout.padding_mask()
    materialized = mask.sum(axis=1)               # real rows per physical shard
    plan_sizes = np.array([e - s for s, e in balanced])
    rows.append(("padded_shard_rows_match_plan",
                 float(np.array_equal(materialized, plan_sizes)),
                 f"materialized rows/shard {materialized.tolist()} == plan"))
    rows.append(("padded_materialized_imbalance",
                 placement_imbalance(counts, layout.ranges),
                 "max/mean lookup load over the PHYSICAL shards (<=1.05)"))
    rows.append(("padded_equal_split_imbalance",
                 placement_imbalance(counts, uniform),
                 "what the old equal-split materialization suffered"))
    rows.append(("padded_overhead_rows_frac",
                 (layout.padded_rows - cfg.total_embedding_rows)
                 / cfg.total_embedding_rows,
                 f"padding cost of max_range={layout.max_range}"))

    batch = criteo_batch(cfg, 11, np.arange(0, 256))
    idx = jnp.asarray(batch["sparse"])
    flat_plan = EmbeddingPlan(offsets=cfg.table_offsets, combiner="sum")
    pad_plan = flat_plan.with_replan(None, layout)
    out_flat = fused_embedding_bag(pool, idx, plan=flat_plan)
    out_pad = fused_embedding_bag(ppool.reshape(-1, D), idx, plan=pad_plan)
    rows.append(("padded_fwd_bitexact_err",
                 float(jnp.abs(out_pad - out_flat).max()),
                 "padded forward vs flat XLA reference (0 = bit-exact)"))
    import jax as _jax
    g_flat = _jax.grad(lambda p: jnp.sum(
        fused_embedding_bag(p, idx, plan=flat_plan) * 1.3))(pool)
    g_pad = _jax.grad(lambda p3: jnp.sum(fused_embedding_bag(
        p3.reshape(-1, D), idx, plan=pad_plan) * 1.3))(ppool)
    rows.append(("padded_bwd_bitexact_err",
                 float(jnp.abs(layout.unpad_rows(g_pad) - g_flat).max()),
                 "padded backward vs flat XLA reference (0 = bit-exact)"))
    rows.append(("padded_pad_rows_grad_abs_max",
                 float(jnp.abs(jnp.where(jnp.asarray(mask)[..., None],
                                         0.0, g_pad)).max()),
                 "gradient mass on padding slots (must be 0)"))

    # --- live re-planning under DRIFTING skew --------------------------------
    # A plan frozen at compile time re-creates the hot-PS problem the moment
    # row popularity drifts. The HotTableTracker's decayed rolling counts
    # watch the live stream; when the applied plan's imbalance crosses the
    # trigger it emits a ReplanDecision (frequency permutation + balanced
    # ranges + measured cache prefixes) that repro.train.replan applies
    # bit-exactly. Here: plan once, rotate the hot head by 157 ids per table,
    # and show imbalance re-converging to ~1.0 after the second re-plan.
    from repro.core.sharding_service import HotTableTracker
    from repro.train.replan import EmbeddingRemapper

    rows_per_table = cfg.table_rows[0]
    tracker = HotTableTracker(cfg.table_rows, n_ps=n_ps,
                              hot_budget=cfg.hot_rows_k, decay=0.8,
                              trigger=1.2, cooldown=4, min_lookups=512)
    remap = EmbeddingRemapper(cfg.table_rows)

    def feed(lo, shift):
        batch = criteo_batch(cfg, 11, np.arange(lo, lo + 256))
        sparse = ((batch["sparse"].astype(np.int64) + shift) % rows_per_table
                  ).astype(np.int32)
        tracker.observe(remap.remap(sparse))

    for lo in range(0, 1536, 256):              # phase A: stationary skew
        feed(lo, shift=0)
    d1 = tracker.maybe_replan()                 # uniform striping has gone hot
    assert d1 is not None
    tracker.mark_applied(d1)
    remap.compose(d1.permutation)
    rows.append(("replan_initial_imbalance_before", d1.imbalance_before,
                 "uniform striping under stationary skew"))
    rows.append(("replan_initial_imbalance_after", d1.imbalance_after,
                 "first re-plan: balanced ranges"))

    for lo in range(2048, 4096, 256):           # phase B: hot head rotates
        feed(lo, shift=157)
    d2 = tracker.maybe_replan()                 # drift re-arms the trigger
    assert d2 is not None
    tracker.mark_applied(d2)
    remap.compose(d2.permutation)
    rows.append(("replan_drift_imbalance_before", d2.imbalance_before,
                 "stale plan under drifted skew (trigger: 1.2)"))
    rows.append(("replan_drift_imbalance_after", d2.imbalance_after,
                 "second re-plan re-converges (target: <=1.05)"))
    rows.append(("replan_drift_cache_rows", sum(d2.table_hot),
                 f"measured table_hot rows at K={cfg.hot_rows_k}"))
    rows.append(("replan_count", tracker.n_replans, "re-plans applied"))
    return rows
