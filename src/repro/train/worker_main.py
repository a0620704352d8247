"""Worker-process entrypoint spawned (and re-exec'd) by the job master.

One incarnation of one worker: build the DLRM job (reduced, or the full
published config with ``--full``), resume from the
newest valid layout-stamped checkpoint in ``--ckpt-dir`` (fresh init when
none), then train to ``--steps`` global steps, publishing a heartbeat file
after every step and appending each step's loss to a shared JSONL log.

Bit-exactness across kills is inherited, not re-implemented: batches are a
pure function of the global step (``DLRMJob``), checkpoints are layout-
stamped and checksum-verified (``FlashCheckpoint`` + ``resume_dlrm_stamped``),
so incarnation *k* replaying steps the dead incarnation already ran recomputes
byte-identical losses — the kill-matrix suite (``tests/test_chaos_proc.py``)
asserts the merged loss log equals a never-killed run's to the ulp.

``--chaos-proc`` scripts this process's own death
(``repro.core.faults.ProcessFaultInjector``): SIGKILL before a scheduled
step, SIGSTOP (the master's heartbeat deadline must catch it), or SIGKILL
inside the checkpoint pre-commit window. ``--incarnation`` (supplied by the
master) gates which specs fire, so a re-exec'd worker does not re-die
unless the plan says so (``kill_loop``).

Invoked as ``python -m repro.train.worker_main`` — heavy imports happen
*after* the first "boot" heartbeat so the master can tell "booting" from
"dead" immediately.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="repro.train.worker_main")
    ap.add_argument("--arch", default="wide_deep")
    ap.add_argument("--steps", type=int, required=True,
                    help="train until this many global steps completed")
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--ckpt-every", type=int, default=3)
    ap.add_argument("--n-ps", type=int, default=4)
    ap.add_argument("--padded", action="store_true")
    ap.add_argument("--optimizer", default="adagrad")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--init-seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=11)
    ap.add_argument("--full", action="store_true",
                    help="the full published config instead of the reduced one")
    ap.add_argument("--zipf-alpha", type=float, default=0.0,
                    help="power-law skew of the sparse-feature stream")
    ap.add_argument("--hot-rows", type=int, default=0,
                    help="hot-row cache budget in pooled rows (0 = off)")
    ap.add_argument("--heartbeat", required=True,
                    help="heartbeat JSON path (atomically replaced per step)")
    ap.add_argument("--losses", required=True,
                    help="append-only JSONL of {incarnation, step, loss}")
    ap.add_argument("--fault-log", default=None,
                    help="append-only JSONL of fired process faults")
    ap.add_argument("--chaos-proc", default="",
                    help="process-level fault plan (kill/stop/kill_ckpt/"
                         "kill_loop specs; see repro.core.faults)")
    ap.add_argument("--incarnation", type=int, default=0,
                    help="0 for the first exec; +1 per job-master re-exec")
    args = ap.parse_args(argv)

    # publish liveness before the heavy imports/JIT: the master's spawn
    # grace (not its per-step deadline) covers everything until "ready"
    from repro.train.job_master import write_heartbeat
    pid = os.getpid()

    def beat(step: int, phase: str, restore_s: float = 0.0) -> None:
        write_heartbeat(args.heartbeat, pid=pid,
                        incarnation=args.incarnation, step=step,
                        phase=phase, restore_s=restore_s)

    beat(-1, "boot")

    import dataclasses

    from repro.configs.dlrm_models import reduced_dlrm
    from repro.configs.registry import get_dlrm
    from repro.core.faults import ProcessFaultInjector, parse_chaos_spec
    from repro.core.flash_checkpoint import FlashCheckpoint
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.supervisor import DLRMJob

    enable_compile_cache()       # a re-exec'd incarnation reuses the compile
    cfg = get_dlrm(args.arch)
    if not args.full:
        cfg = reduced_dlrm(cfg)
    cfg = dataclasses.replace(cfg, zipf_alpha=args.zipf_alpha,
                              hot_rows_k=args.hot_rows)
    injector = ProcessFaultInjector(
        parse_chaos_spec(args.chaos_proc), incarnation=args.incarnation,
        log_path=args.fault_log)
    ckpt = FlashCheckpoint(
        args.ckpt_dir, async_persist=False,  # sync: every blob restorable
        pre_commit_hook=injector.on_pre_commit)
    job = DLRMJob(cfg, ckpt, opt_name=args.optimizer, lr=args.lr,
                  init_seed=args.init_seed, data_seed=args.data_seed,
                  ckpt_every=args.ckpt_every, n_ps=args.n_ps,
                  padded=args.padded)
    t0 = time.perf_counter()
    start_step = job.start(resume=True)      # newest valid stamped blob
    # every later beat re-publishes restore_s: steps can outpace the master's
    # poll, so the "ready" beat alone would often be replaced before it's read
    restore_s = time.perf_counter() - t0
    beat(start_step, "ready", restore_s=restore_s)

    with open(args.losses, "a") as losses:
        while job.global_step < args.steps:
            injector.before_step(job.global_step)   # may SIGKILL/SIGSTOP here
            m = job.run_step()
            losses.write(json.dumps({
                "incarnation": args.incarnation, "step": m["step"],
                "loss": m["loss"]}) + "\n")
            losses.flush()
            beat(job.global_step, "step", restore_s=restore_s)
    job.save()                               # final blob on the way out
    ckpt.wait()
    beat(job.global_step, "done", restore_s=restore_s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
