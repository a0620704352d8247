"""Training launcher: train any --arch with the full DLRover-RM substrate.

It runs a reduced config by default and the full published config with
``--full``; on a TPU backend the fused embedding engine runs its Pallas
kernels, on any other backend the XLA path (``repro.kernels.ops``).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --steps 100 --batch 8 --seq 64 [--reduced/--full] [--ckpt-dir DIR]

The paper's own DLRM workloads run the same way (``--arch wide_deep``,
``xdeepfm`` or ``dcn``) with the live re-planning loop wired in: a
``HotTableTracker`` folds every batch's sparse ids into decayed rolling
counts, and every ``--replan-every`` steps the launcher asks it whether the
placement drifted past ``--imbalance-threshold`` — if so, it snapshots,
permutes the pooled rows, recompiles the step with the measured ``table_hot``
plan, and keeps training on remapped ids (bit-exact across the cut).

    PYTHONPATH=src python -m repro.launch.train --arch wide_deep \
        --steps 200 --zipf-alpha 1.05 --replan-every 20

``--padded-shards`` additionally materializes the plan physically: the
pooled rows are stored padded as (n_ps, max_range, D) so an equal GSPMD
split of the leading axis IS the balanced plan (see
docs/EMBEDDING_LAYOUT.md); re-plans re-pad onto each new plan and
checkpoints stay flat-canonical, so --resume works across layout changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import reduce_config
from repro.configs.registry import DLRMS, get_arch, get_dlrm
from repro.core.flash_checkpoint import FlashCheckpoint
from repro.core.sharding_service import HotTableTracker, ShardingService
from repro.data.pipeline import ShardDataLoader
from repro.data.synthetic import criteo_batch, lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.sharding.policy import padded_layout_for_ranges, uniform_vocab_ranges
from repro.train import optim, replan, trainer


def main(argv=None):
    """Parse ``argv`` (default: the command line) and train; returns what
    the DLRM trainer returns (None for the other entry paths)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 8 for LMs, the config's batch for DLRMs")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default=None,
                    choices=["adam", "adamw", "adagrad", "sgd"],
                    help="default: adamw for LMs, adagrad for DLRMs")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (sized for one TPU "
                         "chip; the default is a reduced config)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--resume", action="store_true")
    # --- DLRM / live re-planning knobs (--arch wide_deep|xdeepfm|dcn) ------
    ap.add_argument("--zipf-alpha", type=float, default=1.05,
                    help="power-law skew of the sparse-feature stream (DLRM)")
    ap.add_argument("--hot-rows", type=int, default=64,
                    help="VMEM hot-row cache budget in pooled rows (DLRM)")
    ap.add_argument("--n-ps", type=int, default=4,
                    help="PS shard count the placement plan targets (DLRM)")
    ap.add_argument("--padded-shards", action="store_true",
                    help="materialize physically-unequal PS shards: store the "
                         "pooled rows as a padded (n_ps, max_range, D) array "
                         "so an equal GSPMD split of the leading axis places "
                         "exactly the balanced range plan (DLRM)")
    ap.add_argument("--fused-update", action="store_true",
                    help="fuse the sparse embedding backward + row-wise "
                         "optimizer update into the train step: deduped COO "
                         "row grads feed Optimizer.update_rows, touching "
                         "only looked-up rows (DLRM; adagrad/adam)")
    ap.add_argument("--replan-every", type=int, default=0, metavar="N",
                    help="poll the hot tracker for a re-plan every N steps "
                         "(0 disables live re-planning)")
    ap.add_argument("--imbalance-threshold", type=float, default=1.2,
                    help="max/mean PS load that arms a re-plan")
    # --- chaos / self-healing knobs (DLRM archs only) ----------------------
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="scripted fault plan, e.g. 'ps_loss@10,hang@20:0.5' "
                         "(see repro.core.faults); implies --supervise")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the corruption-byte RNG (determinism)")
    ap.add_argument("--supervise", action="store_true",
                    help="run DLRM training under the recovery supervisor "
                         "(watchdog + restore-with-backoff) even without "
                         "injected faults")
    ap.add_argument("--chaos-proc", default=None, metavar="SPEC",
                    help="process-level fault plan, e.g. 'kill@5' or "
                         "'kill_loop@3x2,stop@7': train in a REAL worker "
                         "subprocess under the job-master daemon, which "
                         "SIGKILLs/SIGSTOPs it per the plan and re-execs it "
                         "from the newest valid checkpoint (see docs/CHAOS.md)")
    ap.add_argument("--workdir", default=None,
                    help="job-master working directory (heartbeats, loss "
                         "logs, per-incarnation worker logs); default: "
                         "a fresh temp dir")
    ap.add_argument("--heartbeat-deadline", type=float, default=30.0,
                    help="job-master staleness deadline in seconds after a "
                         "worker's first 'ready' heartbeat (SIGSTOP/hang "
                         "detection)")
    ap.add_argument("--step-deadline", type=float, default=None,
                    help="watchdog per-step deadline in seconds (hang "
                         "detection; None disables)")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="capped restart budget of the supervisor")
    ap.add_argument("--event-log", default=None, metavar="PATH",
                    help="write the supervisor's structured event log (JSONL)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.arch in DLRMS:
        if args.chaos_proc is not None:
            train_dlrm_chaos_proc(args)
        elif args.chaos or args.supervise:
            train_dlrm_supervised(args)
        else:
            return train_dlrm(args)
        return None
    if args.batch is None:
        args.batch = 8

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduce_config(cfg)
    api = build_model(cfg)
    opt = optim.make(args.optimizer or "adamw", args.lr)
    print(f"arch={cfg.name} family={cfg.family} params={cfg.param_count():,} "
          f"({'full' if args.full else 'reduced'})")

    ckpt = FlashCheckpoint(args.ckpt_dir) if args.ckpt_dir else None
    state = None
    if args.resume and ckpt is not None and ckpt.latest_step() is not None:
        like = jax.eval_shape(lambda k: trainer.make_train_state(api, opt, k),
                              jax.random.PRNGKey(0))
        state, step0 = ckpt.restore(like)
        print(f"resumed from step {step0}")
    if state is None:
        state = trainer.make_train_state(api, opt, jax.random.PRNGKey(0))

    step_fn = jax.jit(trainer.make_train_step(
        api, opt, remat=True, grad_compress=args.grad_compress))

    total = args.steps * args.batch
    svc = ShardingService(total, shard_size=max(args.batch * 8, 64))
    loader = ShardDataLoader(
        svc, "worker0",
        lambda idx: lm_batch(0, idx, args.seq, cfg.vocab_size),
        batch_size=args.batch)

    t0 = time.time()
    n = 0
    for batch in loader:
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.family == "encdec":
            b["frames"] = jnp.zeros((args.batch, cfg.n_frames, cfg.d_model),
                                    jnp.float32)
        state, m = step_fn(state, b)
        n += 1
        if n % 20 == 0 or n == 1:
            print(f"step {n:5d} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"({n*args.batch/(time.time()-t0):.1f} samples/s)")
        if ckpt is not None and n % args.ckpt_every == 0:
            ckpt.save(state, n)
    ok, covered, dup = svc.coverage(0)
    print(f"done: {n} steps, exactly-once={ok} (covered={covered} dup={dup})")
    if ckpt is not None:
        ckpt.save(state, n)
        ckpt.wait()
        print(f"checkpointed at step {n} -> {args.ckpt_dir}")


def train_dlrm(args) -> dict:
    """DLRM training with the live embedding re-planning loop wired in.

    Checkpoints are layout-stamped (``replan.save_with_layout``): each blob
    carries the composed raw-id → layout map and the active cache plan, so
    ``--resume`` in a fresh process keeps training correctly no matter how
    many re-plans the previous run applied. A resumed run reads the sample
    stream from the restored global step on, and trains ``--steps`` more.

    Returns ``{"start_step", "losses" {global step: loss}, "replans",
    "first_step_s"}``; ``first_step_s`` is the wall time to the first
    step's loss, compile included.
    """
    from repro.configs.dlrm_models import reduced_dlrm

    cfg = get_dlrm(args.arch)
    if not args.full:
        cfg = reduced_dlrm(cfg)
    cfg = dataclasses.replace(cfg, zipf_alpha=args.zipf_alpha,
                              hot_rows_k=args.hot_rows,
                              batch_size=args.batch or cfg.batch_size)
    opt_name = args.optimizer or "adagrad"       # the classic DLRM optimizer
    opt = optim.make(opt_name, args.lr)
    print(f"arch={cfg.name} kind={cfg.kind} params={cfg.param_count():,} "
          f"rows={cfg.total_embedding_rows:,} zipf_alpha={cfg.zipf_alpha} "
          f"({'full' if args.full else 'reduced'})")

    ckpt = FlashCheckpoint(args.ckpt_dir)
    remapper = replan.EmbeddingRemapper(cfg.table_rows)
    table_hot = None                             # None = cfg default plan
    vocab_ranges = None                          # None = uniform striping
    layout = None                                # None = flat pooled store
    state = None
    step0 = 0
    if args.resume and ckpt.latest_step() is not None:
        state, step0, remapper, table_hot, vocab_ranges, layout = \
            replan.restore_with_layout(cfg, opt, ckpt)
        print(f"resumed from step {step0} "
              f"(layout-stamped; cache plan {'measured' if table_hot else 'default'}; "
              f"{'padded ' + str(layout.n_ps) + '-shard' if layout else 'flat'} pool)")
    if args.padded_shards and layout is None:
        # fresh padded job (or a flat-era checkpoint upgraded in place):
        # physical shards follow the applied plan, uniform until one exists
        layout = padded_layout_for_ranges(
            vocab_ranges if vocab_ranges is not None
            else uniform_vocab_ranges(cfg.total_embedding_rows, args.n_ps))
        if state is not None:
            state = replan.pad_train_state(
                state, cfg.total_embedding_rows, layout)
    if state is None:
        state = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0),
                                              layout=layout)
    if layout is not None:
        print(f"padded PS shards: n_ps={layout.n_ps} "
              f"max_range={layout.max_range} physical rows/shard="
              f"{list(layout.shard_sizes)} "
              f"(+{layout.padded_rows - cfg.total_embedding_rows} pad rows)")
    plan = cfg.embedding_plan(table_hot=table_hot, layout=layout,
                              sparse_update=args.fused_update)
    if args.fused_update and opt.update_rows is None:
        raise SystemExit(f"--fused-update: optimizer {opt_name!r} has no "
                         "row-update seam (use adagrad or adam)")
    if args.fused_update:
        print("fused sparse update: backward dedupe + row-wise "
              f"{opt_name} on looked-up rows only")
    step_fn = jax.jit(trainer.make_dlrm_train_step(
        cfg, opt, grad_compress=args.grad_compress, plan=plan))

    tracker = HotTableTracker(
        cfg.table_rows, n_ps=args.n_ps, hot_budget=cfg.hot_rows_k,
        trigger=args.imbalance_threshold,
        cooldown=max(args.replan_every, 1),
        min_lookups=4 * cfg.batch_size * cfg.n_tables * cfg.multi_hot,
        initial_ranges=vocab_ranges, initial_hot=table_hot)

    total = args.steps * cfg.batch_size
    svc = ShardingService(total, shard_size=max(cfg.batch_size * 8, 64))
    first = step0 * cfg.batch_size               # sample stream resumes here
    loader = ShardDataLoader(
        svc, "worker0", lambda idx: criteo_batch(cfg, 11, first + idx),
        batch_size=cfg.batch_size)

    t0 = time.time()
    n = 0
    losses = {}                                  # device scalars, read at end
    first_step_s = None
    for raw in loader:
        batch = remapper.remap_batch(raw)
        tracker.observe(batch["sparse"])        # worker-side heartbeat payload
        state, m = step_fn(state, {k: jnp.asarray(v) for k, v in batch.items()})
        losses[step0 + n] = m["loss"]
        n += 1
        replanned = False
        if n == 1:
            m["loss"].block_until_ready()
            first_step_s = time.time() - t0
        if n % 20 == 0 or n == 1:
            print(f"step {n:5d} loss={float(m['loss']):.4f} "
                  f"imbalance={tracker.imbalance():.3f} "
                  f"({n*cfg.batch_size/(time.time()-t0):.1f} samples/s)")
        if args.replan_every and n % args.replan_every == 0:
            decision = tracker.maybe_replan()
            if decision is not None:
                # old-layout snapshot (with its own layout stamp) first, so a
                # crash mid-replan loses nothing; apply_replan itself then
                # permutes, re-plans placement, and recompiles
                replan.save_with_layout(ckpt, state, int(state["step"]),
                                        remapper, table_hot, vocab_ranges,
                                        layout=layout)
                res = replan.apply_replan(state, cfg, opt, decision,
                                          remapper=remapper, opt_name=opt_name,
                                          grad_compress=args.grad_compress,
                                          layout=layout, plan=plan)
                tracker.mark_applied(decision)
                state, step_fn, layout = res.state, res.step_fn, res.layout
                plan = res.plan
                table_hot = decision.table_hot
                vocab_ranges = decision.vocab_ranges
                replanned = True
                print(f"step {n:5d} RE-PLAN: imbalance "
                      f"{decision.imbalance_before:.3f} -> "
                      f"{decision.imbalance_after:.3f}, "
                      f"cache rows {sum(decision.table_hot)}"
                      + (f", physical rows/shard {list(layout.shard_sizes)}"
                         if layout is not None else ""))
        if args.ckpt_dir and n % args.ckpt_every == 0 and not replanned:
            # key by the GLOBAL step so resumed runs sort above their
            # pre-resume checkpoints (n restarts at 0 on every process)
            replan.save_with_layout(ckpt, state, int(state["step"]),
                                    remapper, table_hot, vocab_ranges,
                                    layout=layout)
    ok, covered, dup = svc.coverage(0)
    print(f"done: {n} steps, exactly-once={ok} (covered={covered} dup={dup}), "
          f"{tracker.n_replans} re-plan(s), final imbalance "
          f"{tracker.imbalance():.3f}")
    if args.ckpt_dir:
        replan.save_with_layout(ckpt, state, int(state["step"]),
                                remapper, table_hot, vocab_ranges,
                                layout=layout)
        ckpt.wait()
        print(f"checkpointed at step {n} -> {args.ckpt_dir}")
    return {"start_step": step0,
            "losses": {k: float(v) for k, v in losses.items()},
            "replans": tracker.n_replans, "first_step_s": first_step_s}


def train_dlrm_supervised(args) -> None:
    """DLRM training under the self-healing supervisor (``--chaos`` /
    ``--supervise``).

    The scripted fault plan fires through the trainer/data/checkpoint hooks;
    the supervisor detects each abnormality (watchdog deadline, typed fault,
    EWMA outlier) and recovers from layout-stamped flash checkpoints —
    the end-to-end §5 reliability loop on the real training path.
    """
    import tempfile

    from repro.configs.dlrm_models import reduced_dlrm
    from repro.core.faults import FaultInjector, parse_chaos_spec
    from repro.train.supervisor import DLRMJob, Supervisor, SupervisorConfig

    cfg = get_dlrm(args.arch)
    if not args.full:
        cfg = reduced_dlrm(cfg)
    cfg = dataclasses.replace(cfg, zipf_alpha=args.zipf_alpha,
                              hot_rows_k=args.hot_rows,
                              batch_size=args.batch or cfg.batch_size)
    opt_name = args.optimizer or "adagrad"
    plan = parse_chaos_spec(args.chaos or "")
    injector = FaultInjector(plan, seed=args.chaos_seed) if plan.specs else None
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="chaos_ckpt_")
    ckpt = FlashCheckpoint(
        ckpt_dir, async_persist=False,      # sync: every blob restorable
        fault_hook=injector.on_persist if injector else None)
    if injector is not None:
        injector.bind_checkpoint(ckpt)
    print(f"arch={cfg.name} kind={cfg.kind} params={cfg.param_count():,} "
          f"supervised (chaos plan: {plan if plan.specs else 'none'}; "
          f"ckpt -> {ckpt_dir})")

    job = DLRMJob(cfg, ckpt, opt_name=opt_name, lr=args.lr,
                  ckpt_every=args.ckpt_every, n_ps=args.n_ps,
                  padded=args.padded_shards,
                  sparse_update=args.fused_update, injector=injector)
    sup = Supervisor(job, SupervisorConfig(
        step_deadline_s=args.step_deadline, max_restarts=args.max_restarts,
        seed=args.chaos_seed))
    try:
        report = sup.run(args.steps, resume=args.resume)
    finally:
        if args.event_log:                  # log survives a failed run too
            sup.write_event_log(args.event_log)
    for ev in report.events:
        print(f"  event step={ev.step:5d} {ev.kind} {ev.detail}")
    lat = report.recovery_latencies_s
    mean_lat = sum(lat) / len(lat) if lat else 0.0
    print(f"CHAOS completed={report.completed} final_step={report.final_step} "
          f"final_loss={report.final_loss:.6f} restarts={report.restarts} "
          f"steps_lost={report.steps_lost} "
          f"goodput={report.goodput_fraction:.3f} "
          f"recovery_latency_mean_s={mean_lat:.4f}")
    if args.event_log:
        sup.write_event_log(args.event_log, report)
        print(f"event log -> {args.event_log}")


def train_dlrm_chaos_proc(args) -> None:
    """DLRM training in a real worker subprocess under the job-master daemon
    (``--chaos-proc``).

    Unlike ``--chaos`` (in-process fault hooks under the supervisor), the
    worker here is an actual OS process the plan SIGKILLs/SIGSTOPs; the
    master detects the death via exit code or stale heartbeat and re-execs
    a fresh incarnation that resumes from the newest valid layout-stamped
    checkpoint — same process tree as a production pod restart.
    """
    import os
    import tempfile

    from repro.train.job_master import JobMaster, JobMasterConfig, WorkerSpec

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_proc_")
    spec = WorkerSpec(
        name="worker0", workdir=workdir,
        ckpt_dir=args.ckpt_dir or os.path.join(workdir, "ckpt"),
        arch=args.arch, steps=args.steps, ckpt_every=args.ckpt_every,
        n_ps=args.n_ps, padded=args.padded_shards,
        chaos_proc=args.chaos_proc,
        opt_name=args.optimizer or "adagrad", lr=args.lr, full=args.full,
        zipf_alpha=args.zipf_alpha, hot_rows=args.hot_rows)
    master = JobMaster([spec], JobMasterConfig(
        heartbeat_deadline_s=args.heartbeat_deadline,
        max_reexecs=args.max_restarts, seed=args.chaos_seed))
    print(f"arch={args.arch} chaos-proc plan: {args.chaos_proc or 'none'} "
          f"(workdir -> {workdir}, ckpt -> {spec.ckpt_dir})")
    try:
        report = master.run()
    finally:
        if args.event_log:                  # log survives a failed run too
            master.write_event_log(args.event_log)
    for ev in report.events:
        print(f"  event {ev.kind} worker={ev.worker} {ev.detail}")
    t = report.measured_timings()
    losses = spec.read_losses()
    final_loss = losses[-1]["loss"] if losses else float("nan")
    print(f"CHAOS-PROC completed={report.completed} "
          f"final_steps={report.final_steps} reexecs={report.reexecs} "
          f"exit_history={report.exit_history} final_loss={final_loss:.6f} "
          f"reexec_mean_s={t.reexec_s():.3f} "
          f"restore_mean_s={t.flash_ckpt_load_s:.3f} "
          f"wall_s={report.wall_seconds:.1f}")
    if args.event_log:
        master.write_event_log(args.event_log, report)
        print(f"event log -> {args.event_log}")


if __name__ == "__main__":
    main()
