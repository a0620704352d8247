#!/usr/bin/env python3
"""Bring-up check: the full-width DLRM training path on a TPU.

    python3 chip_smoke.py               # one chip: the four phases below
    python3 chip_smoke.py --four-chips  # the sharded pooled store on a 2x2 host

The model is ``wide_deep`` at its published width (26 tables, 3,294,238
pooled rows, D=16 plus the D=1 wide pool, batch 512, 4 lookups per bag)
under zipf-1.05 sparse ids, with the hot-row cache on (64 rows) and the
pooled rows padded onto 4 parameter-server shards. Weights and data are
made from fixed seeds. Every phase goes through the entry points users run:

1. device    - JAX sees a TPU (anything else fails the run).
2. train     - ``repro.launch.train`` for 20 steps with a live re-plan every
               5 steps, then a fresh ``--resume`` from the layout-stamped
               checkpoint: losses finite and falling, at least one re-plan,
               and the resumed first loss equal to the original run's loss
               at that step.
3. reference - the Pallas kernels against ``impl="xla"`` (the plain float32
               reference): loss and pooled-store gradients on one batch;
               5 adagrad steps run on both, per-step losses and the state
               they end in; then a re-plan of that state (new hot-row cache
               plan, permuted and re-padded rows) and one more step on
               both. Last, the ``--fused-update`` sparse step against the
               dense step.
4. reexec    - ``--chaos-proc kill@5`` with one worker at full width: the
               job master re-execs the SIGKILLed worker, which resumes from
               the newest valid checkpoint, and the merged loss log must
               equal a run without faults.

``--four-chips`` runs only the sharded path: a few steps on a (data=1,
model=4) mesh with the pooled store split one PS shard per chip, against
the same steps on one chip, and reports the per-chip store bytes and the
collectives in the compiled step.

A chip belongs to one process at a time, so this process never imports
JAX: each phase runs in a child (this file with ``--phase``), one after the
other, and the re-exec phase's workers are the job master's children. The
last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1140            # whole run, compiles included
RESULT = "RESULT "           # prefix of a child's result line

# the job every phase trains: full published width, zipf traffic, cache on,
# padded onto 4 PS shards
ZIPF_ALPHA, HOT_ROWS, N_PS, LR = 1.05, 64, 4, 0.05
JOB_ARGS = ["--arch", "wide_deep", "--full", "--zipf-alpha", str(ZIPF_ALPHA),
            "--hot-rows", str(HOT_ROWS), "--padded-shards", "--n-ps",
            str(N_PS), "--lr", str(LR)]
TRAIN_STEPS, REPLAN_EVERY = 20, 5     # launcher steps; re-plan poll period
REF_STEPS = 5                         # steps compared against the reference
REEXEC_STEPS, KILL_AT = 8, 5          # worker steps; SIGKILL before this one
FOUR_CHIP_STEPS = 3
# f32 sums taken in another order (kernel vs XLA reduce, Mosaic vs XLA
# sqrt/divide): max |a - b| / max |b| over each compared array, and
# ||a - b|| / ||b|| over adagrad-trained params (see _state_diffs)
REL_BOUND = 1e-5


class PhaseError(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseError(what)


# --------------------------------------------------------------- child side
def _device() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    _check(dev["platform"] == "tpu", f"no TPU: JAX runs on {dev['platform']}")
    return dev


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_train() -> dict:
    """Launcher steps with live re-plans, then a fresh resumed call."""
    from repro.launch import train
    steps, every = TRAIN_STEPS, REPLAN_EVERY
    ckpt_dir = tempfile.mkdtemp(prefix="smoke_ckpt_")
    try:
        args = JOB_ARGS + ["--ckpt-dir", ckpt_dir, "--ckpt-every", str(every)]
        run = train.main(args + ["--steps", str(steps),
                                 "--replan-every", str(every)])
        losses = [run["losses"][s] for s in sorted(run["losses"])]
        _check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
        tail = losses[-every:]
        _check(max(tail) < losses[0],
               f"loss not falling: first {losses[0]}, last {tail}")
        _check(run["replans"] >= 1, "no live re-plan fired")
        # the newest blob is lost: resume from the newest remaining one,
        # whose step the first run trained through
        newest = os.path.join(ckpt_dir, f"ckpt_{steps:012d}")
        _check(os.path.isdir(newest), f"no final checkpoint at {newest}")
        shutil.rmtree(newest)
        resumed = train.main(args + ["--steps", "2", "--resume"])
        s0 = resumed["start_step"]
        _check(0 < s0 < steps, f"resumed from step {s0}")
        first = resumed["losses"][s0]
        _check(first == run["losses"][s0],
               f"resumed loss at step {s0} {first!r} != {run['losses'][s0]!r}")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    out = {"losses": losses, "replans": run["replans"],
           "first_step_s": run["first_step_s"], "resumed_from": s0,
           "resumed_loss": first, "resume_first_step_s":
           resumed["first_step_s"]}
    print(f"phase train: {steps} steps loss {losses[0]:.6f} -> "
          f"{losses[-1]:.6f}, {run['replans']} re-plan(s), first step "
          f"(compile included) {run['first_step_s']:.2f}s; resumed from step "
          f"{s0}: loss {first!r} equals the saved run's", flush=True)
    return out


def _job():
    """The smoke's job at full width, its padded layout and fresh state."""
    import dataclasses

    import jax
    from repro.configs.dlrm_models import WIDE_DEEP
    from repro.sharding.policy import (padded_layout_for_ranges,
                                       uniform_vocab_ranges)
    from repro.train import optim, trainer
    cfg = dataclasses.replace(WIDE_DEEP, zipf_alpha=ZIPF_ALPHA,
                              hot_rows_k=HOT_ROWS)
    opt = optim.make("adagrad", LR)
    layout = padded_layout_for_ranges(
        uniform_vocab_ranges(cfg.total_embedding_rows, N_PS))
    state = trainer.make_dlrm_train_state(cfg, opt, jax.random.PRNGKey(0),
                                          layout=layout)
    return cfg, opt, layout, state


def _raw_batches(cfg, n: int):
    """The launcher's first ``n`` batches, as host arrays."""
    import numpy as np
    from repro.data.synthetic import criteo_batch
    B = cfg.batch_size
    return [criteo_batch(cfg, 11, np.arange(s * B, (s + 1) * B))
            for s in range(n)]


def _on_device(batch: dict) -> dict:
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _compiled(fn, impl: str, *args):
    """``fn`` traced and compiled with the embedding kernels pinned to
    ``impl``; the Pallas program must hold a kernel and the reference none.

    A new function per call: jit caches traces by function, and the impl is
    read when the function is traced.
    """
    import jax
    from repro.kernels import ops
    ops.set_default_impl(impl)
    try:
        compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    finally:
        ops.set_default_impl(None)
    kernel = "tpu_custom_call" in compiled.as_text()
    _check(kernel == (impl == "pallas"),
           f"{impl} program holds a Pallas kernel: {kernel}")
    return compiled


def _run_steps(step, state, batches):
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses, state


def _state_diffs(diffs: dict, tag: str, got, ref) -> None:
    """Pooled params and adagrad accumulators of two train states.

    Params are held in the L2 norm: adagrad scales each element's step by
    1 / (sqrt(acc) + eps), so an element whose gradient is near eps turns
    an f32 rounding difference into a step difference up to lr. The
    largest such element is printed, not bounded.
    """
    import numpy as np
    for k in ("tables", "wide"):
        a, b = np.asarray(got["params"][k]), np.asarray(ref["params"][k])
        diffs[f"{tag}_params_{k}"] = _rel_l2(a, b)
        big = np.abs(a - b) > REL_BOUND * np.max(np.abs(b))
        print(f"phase reference: {tag}_params_{k} max|diff|/max|ref| = "
              f"{_rel(a, b):.3e} in {int(big.sum())} of {b.size} elements "
              "over the bound (not bounded)", flush=True)
        diffs[f"{tag}_acc_{k}"] = _rel(got["opt"]["acc"][k],
                                       ref["opt"]["acc"][k])


def phase_reference() -> dict:
    """Pallas vs the float32 XLA reference over steps and a re-plan; sparse
    vs dense step."""
    import jax
    import numpy as np
    from repro.core.sharding_service import HotTableTracker
    from repro.kernels import ops
    from repro.models.dlrm import dlrm_loss
    from repro.train import replan, trainer
    cfg, opt, layout, state0 = _job()
    plan = cfg.embedding_plan(layout=layout)
    raw = _raw_batches(cfg, REF_STEPS + 1)
    batches = [_on_device(b) for b in raw[:REF_STEPS]]
    _check(ops.get_default_impl() == "pallas",
           f"kernel impl {ops.get_default_impl()!r} on the TPU")
    diffs, losses = {}, {}
    # float32 matmuls on both sides: only the embedding path differs
    with jax.default_matmul_precision("float32"):
        # loss and pooled-store gradients on one batch
        grads = {}
        for impl in ("pallas", "xla"):
            fn = _compiled(jax.value_and_grad(lambda q: dlrm_loss(
                q, batches[0], cfg, plan=plan)), impl, state0["params"])
            grads[impl] = fn(state0["params"])
        (l_k, g_k), (l_x, g_x) = grads["pallas"], grads["xla"]
        diffs["loss"] = _rel(l_k, l_x)
        for k in ("tables", "wide"):
            diffs[f"grad_{k}"] = _rel(g_k[k], g_x[k])
            _check(float(np.max(np.abs(np.asarray(g_x[k])))) > 0,
                   f"zero reference gradient for {k}")
        del grads, g_k, g_x

        # REF_STEPS adagrad steps on each side, from the same state
        steps, ends = {}, {}
        for impl in ("pallas", "xla"):
            steps[impl] = _compiled(trainer.make_dlrm_train_step(
                cfg, opt, plan=plan), impl, state0, batches[0])
            losses[impl], ends[impl] = _run_steps(steps[impl], state0,
                                                  batches)
        for s, (a, b) in enumerate(zip(losses["pallas"], losses["xla"])):
            diffs[f"step{s}_loss"] = _rel(a, b)
        _state_diffs(diffs, f"step{REF_STEPS - 1}", ends["pallas"],
                     ends["xla"])

        # a re-plan of the reference's state, as the launcher applies one:
        # rows permuted and re-padded, a measured hot-row cache plan
        tracker = HotTableTracker(
            cfg.table_rows, n_ps=N_PS, hot_budget=cfg.hot_rows_k,
            cooldown=REF_STEPS,
            min_lookups=4 * cfg.batch_size * cfg.n_tables * cfg.multi_hot)
        for b in raw[:REF_STEPS]:
            tracker.observe(b["sparse"])
        decision = tracker.maybe_replan()
        _check(decision is not None, "the tracker planned no re-plan")
        remapper = replan.EmbeddingRemapper(cfg.table_rows)
        res = replan.apply_replan(ends["xla"], cfg, opt, decision,
                                  remapper=remapper, layout=layout,
                                  plan=plan)
        del ends
        hot_after = res.plan.table_hot
        _check(hot_after != plan.table_hot,
               f"the re-plan kept the cache plan {plan.table_hot}")
        nxt = _on_device(remapper.remap_batch(raw[REF_STEPS]))
        after = {}
        for impl in ("pallas", "xla"):
            fn = _compiled(trainer.make_dlrm_train_step(
                cfg, opt, plan=res.plan), impl, res.state, nxt)
            (loss,), after[impl] = _run_steps(fn, res.state, [nxt])
            losses[f"replan_{impl}"] = loss
        diffs["replan_loss"] = _rel(losses["replan_pallas"],
                                    losses["replan_xla"])
        _state_diffs(diffs, "replan", after["pallas"], after["xla"])
        del after, res

        # the --fused-update sparse step against the dense step (Pallas)
        sparse = _compiled(trainer.make_dlrm_train_step(
            cfg, opt, plan=cfg.embedding_plan(layout=layout,
                                              sparse_update=True)),
            "pallas", state0, batches[0])
        s_d, m_d = steps["pallas"](state0, batches[0])
        s_s, m_s = sparse(state0, batches[0])
        diffs["sparse_loss"] = _rel(m_s["loss"], m_d["loss"])
        _state_diffs(diffs, "sparse", s_s, s_d)

    print(f"phase reference: per-step losses pallas {losses['pallas']} "
          f"xla {losses['xla']}", flush=True)
    print(f"phase reference: re-plan at step {REF_STEPS} (imbalance "
          f"{decision.imbalance_before:.3f} -> "
          f"{decision.imbalance_after:.3f}, cache plan {plan.table_hot} -> "
          f"{hot_after}); next-step loss pallas "
          f"{losses['replan_pallas']!r} xla {losses['replan_xla']!r}",
          flush=True)
    for name, d in diffs.items():
        metric = "||diff||/||ref||" if "_params_" in name else \
            "max|diff|/max|ref|"
        print(f"phase reference: {name} {metric} = {d:.3e} "
              f"(bound {REL_BOUND:.0e})", flush=True)
    bad = {k: d for k, d in diffs.items() if not d <= REL_BOUND}
    _check(not bad, f"over the bound {REL_BOUND}: {bad}")
    return {"diffs": diffs, "bound": REL_BOUND, "losses": losses,
            "loss_kernel": float(l_k), "loss_xla": float(l_x)}


def _collectives(text: str) -> dict:
    import re
    ops_ = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")
    out = {op: len(re.findall(rf"= [^=\n]*\b{op}(?:-start)?\(", text))
           for op in ops_}
    out["all_gather_shapes"] = sorted(set(re.findall(
        r"= (\S+) all-gather(?:-start)?\(", text)))
    return out


def phase_four_chips() -> dict:
    """Sharded pooled store on (data=1, model=4) vs the same steps on one
    device, in one process."""
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.train import elastic, trainer
    cfg, opt, layout, state0 = _job()
    plan = cfg.embedding_plan(layout=layout)
    batches = [_on_device(b) for b in _raw_batches(cfg, FOUR_CHIP_STEPS)]

    one = jax.jit(trainer.make_dlrm_train_step(cfg, opt, plan=plan))
    state = jax.device_put(state0, jax.devices()[0])
    ref = []
    for b in batches:
        state, m = one(state, b)
        ref.append(float(m["loss"]))
    del state

    mesh = make_local_mesh(data=1, model=4)
    step, shardings, batch_sh = elastic.make_dlrm_mesh_step(
        cfg, opt, "adagrad", mesh, plan)
    state = jax.device_put(state0, shardings)
    del state0
    pool_bytes = {k: state["params"][k].addressable_shards[0].data.nbytes
                  for k in ("tables", "wide")}
    placed = [jax.device_put(b, batch_sh) for b in batches]
    compiled = step.lower(state, placed[0]).compile()
    text = compiled.as_text()
    coll = _collectives(text)
    padded = f"{layout.n_ps},{layout.max_range},"
    gathered = any(s.split("[")[1].startswith(padded) or
                   s.split("[")[1].startswith(f"{layout.padded_rows},")
                   for s in coll["all_gather_shapes"] if "[" in s)
    got = []
    for b in placed:
        state, m = step(state, b)
        got.append(float(m["loss"]))
    rel = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(got, ref))
    print(f"phase four_chips: pooled store per device "
          f"tables={pool_bytes['tables']} B wide={pool_bytes['wide']} B "
          f"(n_ps={layout.n_ps}, max_range={layout.max_range})", flush=True)
    print(f"phase four_chips: collectives {coll}; pool all-gathered: "
          f"{gathered}; kernel in step: {'tpu_custom_call' in text}",
          flush=True)
    print(f"phase four_chips: losses mesh {got} one-device {ref} "
          f"max rel diff {rel:.3e} (bound {REL_BOUND:.0e})", flush=True)
    _check(all(math.isfinite(x) for x in got), f"loss not finite: {got}")
    _check(rel <= REL_BOUND, f"mesh losses {got} != one-device {ref}")
    return {"pool_bytes_per_device": pool_bytes, "collectives": coll,
            "pool_all_gathered": gathered, "losses": got, "ref": ref,
            "max_rel_diff": rel}


def _child(phases) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = {"device": _device()}
    for name in phases:
        t0 = time.time()
        result[name] = {"train": phase_train, "reference": phase_reference,
                        "four_chips": phase_four_chips}[name]()
        result[name]["seconds"] = time.time() - t0
    print(RESULT + json.dumps(result), flush=True)
    return 0


# -------------------------------------------------------------- parent side
def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # a worker that cannot get the chip fails instead of falling back to CPU
    env["JAX_PLATFORMS"] = "tpu"
    return env


def _run(cmd, deadline: float) -> str:
    """Run ``cmd`` in its own process group, echo its output, return it."""
    left = deadline - time.time()
    if left <= 0:
        raise PhaseError("out of time before " + " ".join(cmd[:4]))
    proc = subprocess.Popen(cmd, cwd=ROOT, env=_env(), text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=left)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        print(out, end="", flush=True)
        raise PhaseError(f"timed out: {' '.join(cmd[:6])}")
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        else:                    # reap anything the child left in its group
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    print(out, end="", flush=True)
    if proc.returncode != 0:
        raise PhaseError(f"exit {proc.returncode}: {' '.join(cmd[:6])}")
    return out


def _child_result(out: str) -> dict:
    lines = [ln for ln in out.splitlines() if ln.startswith(RESULT)]
    if not lines:
        raise PhaseError("child printed no result")
    return json.loads(lines[-1][len(RESULT):])


def _merged_losses(workdir: str) -> dict:
    """Per global step, the loss of the newest incarnation that ran it."""
    merged = {}
    with open(os.path.join(workdir, "losses_worker0.jsonl")) as f:
        for line in f:
            if line.strip():
                rec = json.loads(line)
                merged[rec["step"]] = (rec["incarnation"], rec["loss"])
    return merged


def phase_reexec(deadline: float) -> dict:
    """The job master SIGKILLs the worker before step ``KILL_AT``; the
    re-exec'd worker must resume and reproduce the no-fault loss log."""
    steps, kill_at = REEXEC_STEPS, KILL_AT
    logs = {}
    summary = ""
    root = tempfile.mkdtemp(prefix="smoke_reexec_")
    try:
        for plan in ("", f"kill@{kill_at}"):
            workdir = os.path.join(root, plan or "nofault")
            out = _run([sys.executable, "-m", "repro.launch.train", *JOB_ARGS,
                        "--steps", str(steps), "--ckpt-every", "2",
                        "--heartbeat-deadline", "120",
                        "--chaos-proc", plan, "--workdir", workdir], deadline)
            logs[plan] = _merged_losses(workdir)
            summary = [ln for ln in out.splitlines()
                       if ln.startswith("CHAOS-PROC")][-1]
        ref, got = logs[""], logs[f"kill@{kill_at}"]
        _check("completed=True" in summary and "reexecs=1" in summary,
               f"re-exec run: {summary}")
        _check(any(inc > 0 for inc, _ in got.values()),
               "no step ran in a re-exec'd incarnation")
        same = {s: v for s, (_, v) in ref.items()} == \
            {s: v for s, (_, v) in got.items()}
        _check(same and len(ref) == steps,
               f"merged loss log {got} != no-fault {ref}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(f"phase reexec: {summary}", flush=True)
    print(f"phase reexec: merged loss log of {len(got)} steps equals the "
          "no-fault run", flush=True)
    return {"summary": summary}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pooled-store path on 4 chips")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return _child(args.phase.split(","))

    deadline = time.time() + DEADLINE_S
    me = [sys.executable, os.path.abspath(__file__)]
    try:
        if args.four_chips:
            res = _child_result(_run(me + ["--phase", "four_chips"], deadline))
            if res["device"]["count"] != 4:
                raise PhaseError(f"--four-chips needs 4 chips, JAX sees "
                                 f"{res['device']['count']}")
        else:
            res = _child_result(_run(me + ["--phase", "train,reference"],
                                     deadline))
            phase_reexec(deadline)
    except (PhaseError, OSError, ValueError, KeyError) as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
