#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, at its size.

    python3 benchmarks/chip/calibrate.py --workload wide_deep.zipf \
        --seeds 101-112

For each seed, in one process: the program's first three steps through the
cell's own loop (as a run's set-up drives them), the reference (float32 at
the configuration's matmul precision), the control (the reference in
bfloat16, in the program's place), and the half-batch fault (the
reference with the loss's mean taken over the first half of each batch).
Each side's compared numbers against the reference
go to standard output, one JSON line per seed and side, then a summary: the
largest reading of the program (the lower reading of each limit) and the
smallest of the control and of the fault (the upper readings). A step that
leaves the state unchanged reads 1 on ``grad_gap`` and ``change_gap`` and
needs no run. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SIDES = ("program", "control", "half_batch")


def readings(manifest, cell, seeds, *, conf=None, traffic=None, log=print):
    """Yields ``(seed, side, gaps)`` for every seed and side."""
    import jax
    import jax.numpy as jnp
    import compare
    import harness
    import ringgen
    from repro.train import trainer
    entry = manifest.workload(cell)
    conf = conf or manifest.config(entry["config"])
    traffic = traffic or manifest.traffic(entry["traffic"])
    job = harness.build_job(manifest, conf, traffic)
    make_state = harness.state_fn(job)
    step_fn = jax.jit(trainer.make_dlrm_train_step(job.cfg, job.opt,
                                                   plan=job.plan))
    for seed in seeds:
        t0 = time.perf_counter()
        ring = ringgen.make_ring(seed, conf["sizes"], traffic,
                                 n=harness.CHECK_STEPS)
        loop = harness.make_loop(job, make_state(harness.seed_key(seed)),
                                 ring, step_fn=step_fn)
        prog = harness.program_readings(loop)
        del loop
        ref = harness.reference_readings(job, seed, ring)
        sides = {"program": prog,
                 "control": harness.reference_readings(
                     job, seed, ring, dtype=jnp.bfloat16),
                 "half_batch": harness.reference_readings(
                     job, seed, ring, half=True)}
        for side, got in sides.items():
            yield seed, side, dict(compare.gaps(got, ref),
                                   losses=got["losses"])
        log(f"seed {seed}: reference losses {ref['losses']} "
            f"({time.perf_counter() - t0:.1f} s)")


def summary(rows):
    """Per number: the program's largest reading, the others' smallest."""
    out = {}
    for n in ("loss_gap", "grad_gap", "change_gap"):
        out[n] = {"program_max": max(r[n] for s, side, r in rows
                                     if side == "program")}
        for side in SIDES[1:]:
            out[n][f"{side}_min"] = min(r[n] for s, sd, r in rows
                                        if sd == side)
    return out


def _seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, e.g. 101-112")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import jax
    from manifest import Manifest
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate needs a TPU", file=sys.stderr)
        return 2
    rows = []
    log = lambda s: print(s, file=sys.stderr, flush=True)
    for seed, side, gaps in readings(Manifest(ROOT, HERE), args.workload,
                                     _seeds(args.seeds), log=log):
        rows.append((seed, side, gaps))
        print(json.dumps({"seed": seed, "side": side, **gaps}), flush=True)
    print(json.dumps({"summary": summary(rows),
                      "device_kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
