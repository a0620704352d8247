"""One run of one cell: set-up, the measured window, the comparison.

Set-up builds one job as the launcher does (``repro.launch.train.
train_dlrm``: the registry's config at the configuration file's sizes, the
program's optimizer, the padded layout over ``n_ps`` parameter-server
ranges, the jitted train step), makes its initial state on the device in
one jitted call from ``--seed``, makes the ring of batches, and drives the
loop through its first steps: the first is the one the reference follows. The window then runs the same loop object for ``seconds`` and ends
when the device is done. After the window the device's memory peak is read,
the program's state is freed, and the reference runs.

``run`` is everything but the look for a chip, which ``run.py`` makes, so a
test can drive a whole run on the CPU at a small size.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import tempfile
import time
from types import ModuleType
from typing import Callable, Dict, List, Mapping, Optional

import numpy as np

import compare
import refcore
import ringgen
import tracecut
from loop import LauncherLoop
from manifest import Manifest

#: the steps the reference follows from the initial state: the first
#: gives the gradient; the later two give adagrad's updates lr*g/sqrt(sum
#: g^2), which, unlike the first one's lr*sign(g), the arithmetic moves
CHECK_STEPS = 3
#: further set-up steps, so that the host pieces' first calls are over
WARM_STEPS = 5
#: the pooled stores: leaves whose rows are the pooled embedding rows
POOLS = ("tables", "wide")
_SIZE_TUPLES = ("table_rows", "mlp_dims", "cin_layers")
#: the check on the newest checkpoint is exact
CKPT_LIMIT = 0.0


@dataclasses.dataclass
class Job:
    """The program's job for one cell, and the benchmark's files for it."""
    conf: Dict
    traffic: Dict
    cfg: object            # repro DLRMConfig
    opt: object            # repro Optimizer
    layout: object         # repro PaddedLayout or None
    plan: object           # repro EmbeddingPlan
    ref: ModuleType        # reference/<kind>.py
    work: ModuleType       # work/<kind>.py


def build_job(manifest: Manifest, conf: Mapping, traffic: Mapping) -> Job:
    """The launcher's job at the configuration file's sizes and options."""
    from repro.configs.registry import get_dlrm
    from repro.sharding.policy import (padded_layout_for_ranges,
                                       uniform_vocab_ranges)
    from repro.train import optim
    ringgen.check_traffic(traffic)
    sizes = {k: tuple(v) if k in _SIZE_TUPLES else v
             for k, v in conf["sizes"].items()}
    cfg = dataclasses.replace(get_dlrm(conf["registry"]), **sizes,
                              zipf_alpha=float(traffic["zipf_alpha"]),
                              hot_rows_k=int(traffic["hot_rows"]))
    if cfg.kind != conf["kind"]:
        raise ValueError(f"registry {conf['registry']!r} is {cfg.kind!r}, "
                         f"the file says {conf['kind']!r}")
    job = conf["job"]
    opt = optim.make(job["optimizer"], job["lr"], eps=job["eps"])
    layout = None
    if job["padded_shards"]:
        layout = padded_layout_for_ranges(
            uniform_vocab_ranges(cfg.total_embedding_rows, job["n_ps"]))
    plan = cfg.embedding_plan(layout=layout,
                              sparse_update=job["fused_update"])
    return Job(dict(conf), dict(traffic), cfg, opt, layout, plan,
               manifest.reference(conf["kind"]), manifest.work(conf["kind"]))


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    import jax
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def pad_pool(flat, ranges, max_range: int):
    """Flat pooled rows -> (n_ps, max_range, ...): each range's rows at the
    head of its shard, zero rows after."""
    import jax.numpy as jnp
    tail = [(0, 0)] * (flat.ndim - 1)
    return jnp.stack([jnp.pad(flat[s:e], [(0, max_range - (e - s))] + tail)
                      for s, e in ranges])


def state_fn(job: Job) -> Callable:
    """key -> the program's initial train state, in one jitted call: the
    reference's weights, the pools padded onto the job's layout."""
    import jax
    import jax.numpy as jnp
    sizes, layout = job.conf["sizes"], job.layout

    def make(key):
        params = job.ref.init(key, sizes)
        if layout is not None:
            params = {k: pad_pool(v, layout.ranges, layout.max_range)
                      if k in POOLS else v for k, v in params.items()}
        return {"params": params, "opt": job.opt.init(params),
                "step": jnp.zeros((), jnp.int32)}
    return jax.jit(make)


def weights_fn(job: Job) -> Callable:
    import jax
    return jax.jit(lambda key: job.ref.init(key, job.conf["sizes"]))


def make_loop(job: Job, state, ring, ckpt=None, step_fn=None) -> LauncherLoop:
    return LauncherLoop(job.cfg, job.opt, job.plan, job.layout, state, ring,
                        n_ps=job.conf["job"]["n_ps"],
                        imbalance_threshold=job.conf["job"]["imbalance_threshold"],
                        ckpt=ckpt, ckpt_every=int(job.traffic["ckpt_every"]),
                        step_fn=step_fn)


def program_readings(loop: LauncherLoop) -> Dict:
    """The loop's first ``CHECK_STEPS`` steps, read as the reference is:
    losses, the first gradient from the accumulator, the change."""
    p0 = refcore.named_leaves(loop.state["params"])
    loop.step()
    grad_sq = refcore.sum_sq(loop.state["opt"]["acc"])
    for _ in range(CHECK_STEPS - 1):
        loop.step()
    change = refcore.change_norms(loop.state["params"], p0)
    del p0
    return {"losses": [float(x) for x in loop.losses[:CHECK_STEPS]],
            "grad_sq": {k: float(v) for k, v in grad_sq.items()},
            "change": {k: float(v) for k, v in change.items()}}


def reference_readings(job: Job, seed: int, ring, **kw) -> Dict:
    """The reference's readings over the ring's first ``CHECK_STEPS``
    batches; ``kw`` selects the control (``dtype``) or a fault (``half``)."""
    sizes, opt = job.conf["sizes"], job.conf["job"]
    weights = weights_fn(job)(seed_key(seed))
    return refcore.train(job.ref.logit, weights, ring[:CHECK_STEPS],
                         table_rows=sizes["table_rows"], lr=opt["lr"],
                         eps=opt["eps"],
                         matmul_precision=job.conf["precision"]["matmul"],
                         **kw)


class CompileCounter:
    """Counts the compiles JAX reports. JAX keeps its listeners for the
    life of the process, so there is one counter."""
    _one: Optional["CompileCounter"] = None

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.count = 0
        return cls._one

    def _event(self, name: str, secs: float, **kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.count += 1


def _max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    diffs = jax.tree.map(lambda x, y: jnp.max(jnp.abs(x - y)), a, b)
    return max(float(d) for d in jax.tree.leaves(diffs))


def check_checkpoint(job: Job, loop: LauncherLoop, ckpt_dir: str) -> float:
    """Restore the newest blob in a fresh store, as ``--resume`` would, and
    return its largest difference from the state that was saved (exact: 0).
    """
    from repro.core.flash_checkpoint import FlashCheckpoint
    from repro.train import replan
    loop.ckpt.wait()
    step, saved = loop.saved
    state, got, _, _, _, layout = replan.restore_with_layout(
        job.cfg, job.opt, FlashCheckpoint(ckpt_dir))
    if got != step or (layout is None) != (job.layout is None):
        return math.inf
    return _max_abs_diff(state, saved)


def device_info(chips: Optional[int] = None) -> Dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:chips or len(devs)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


E2E: Dict[str, Callable[[Dict], Optional[float]]] = {
    "samples_per_s": lambda c: c["steps"] * c["batch"] / c["window_s"],
    "ckpt_stall_ms": lambda c: (1e3 * float(np.mean(c["save_stall_s"]))
                                if c["save_stall_s"] else None),
    "setup_s": lambda c: c["setup_s"],
}


def run(manifest: Manifest, cell: str, seed: int, seconds: float, *,
        trace: bool = False, t0: Optional[float] = None,
        conf: Optional[Mapping] = None, traffic: Optional[Mapping] = None,
        log: Callable[[str], None] = print) -> Dict:
    """One run of ``cell``; returns the result line's object.

    ``conf``/``traffic`` replace the files the manifest names (a test runs
    a cell at a small size so); ``t0`` is the process's start.
    """
    import jax
    t0 = time.perf_counter() if t0 is None else t0
    entry = manifest.workload(cell)
    conf = conf or manifest.config(entry["config"])
    traffic = traffic or manifest.traffic(entry["traffic"])
    job = build_job(manifest, conf, traffic)
    peaks = manifest.peaks(jax.devices()[0].device_kind) if trace else None
    counter = CompileCounter.get()

    marks = [("start", time.perf_counter())]
    ring = ringgen.make_ring(seed, conf["sizes"], traffic)
    marks.append(("ring", time.perf_counter()))
    state = jax.block_until_ready(state_fn(job)(seed_key(seed)))
    marks.append(("state", time.perf_counter()))
    ckpt_dir = ckpt = trace_dir = None
    if int(traffic["ckpt_every"]):
        from repro.core.flash_checkpoint import FlashCheckpoint
        ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
        ckpt = FlashCheckpoint(ckpt_dir)
    try:
        loop = make_loop(job, state, ring, ckpt)
        del state
        prog = program_readings(loop)
        marks.append(("first steps", time.perf_counter()))
        for _ in range(WARM_STEPS):
            loop.step()
        if ckpt is not None:
            _warm_save(loop)
        jax.block_until_ready(loop.state)
        marks.append(("warm steps", time.perf_counter()))
        n0, saves0 = loop.n, len(loop.save_stall_s)
        setup_s = time.perf_counter() - t0
        log("set-up s: before the harness %.2f, " % (marks[0][1] - t0)
            + ", ".join(f"{n} {b - a:.2f}" for (_, a), (n, b)
                        in zip(marks, marks[1:]))
            + f"; compiles {counter.count}")
        counter.count = 0

        if trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(trace_dir)
        try:
            window_s = loop.run_for(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        compiles = counter.count
        steps = loop.n - n0
        log(f"window: {steps} steps in {window_s:.4f} s; saves "
            f"{len(loop.save_stall_s) - saves0}, blocked ms "
            f"{[round(1e3 * s, 1) for s in loop.save_stall_s[saves0:]]}")
        device = device_info(entry["chips"])
        losses = np.asarray(jax.device_get(loop.losses[n0:]))
        ctx = {"steps": steps, "batch": job.cfg.batch_size,
               "window_s": window_s, "setup_s": setup_s,
               "save_stall_s": loop.save_stall_s[saves0:],
               "save_flatten_s": loop.save_flatten_s[saves0:],
               "sizes": conf["sizes"], "work": job.work, "peaks": peaks}
        if trace:
            ctx["trace"] = tracecut.reduce(**tracecut.load(trace_dir))
        found = {}
        if ckpt is not None:
            found["ckpt_restore_diff"] = check_checkpoint(job, loop, ckpt_dir)
        del loop
    finally:
        for d in (ckpt_dir, trace_dir):
            if d is not None:
                shutil.rmtree(d, ignore_errors=True)

    ref = reference_readings(job, seed, ring)
    found.update(compare.gaps(prog, ref))
    limits = dict(conf["limits"])
    if "ckpt_restore_diff" in found:
        limits["ckpt_restore_diff"] = CKPT_LIMIT
    correct, checks = compare.judge(found, limits)
    log(f"program losses {prog['losses']} reference {ref['losses']}")
    log(f"worst leaves: grad {found['grad_leaf']}, change "
        f"{found['change_leaf']}; still leaves {found['still_leaves']}; "
        f"compiles in the window {compiles}")

    metrics = {}
    for m in manifest.metrics(cell, trace):
        value = (manifest.reader(m["name"]).read(ctx) if trace
                 else E2E[m["name"]](ctx))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace:
        device["busy_s"] = ctx["trace"]["busy_ns"] / 1e9
        device["window_s"] = ctx["trace"]["window_ns"] / 1e9
    result = {"correct": correct, "attempted": steps,
              "failed": int(np.sum(~np.isfinite(losses))),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = tracecut.breakdown(ctx["trace"])
    result["checks"] = checks
    return result


def _warm_save(loop: LauncherLoop) -> None:
    """Compile the save path (the unpad of the pools) into a store that
    keeps memory only, so that the window's first save compiles nothing."""
    from repro.core.flash_checkpoint import FlashCheckpoint
    from repro.train import replan
    replan.save_with_layout(FlashCheckpoint(None), loop.state, 0,
                            loop.remapper, None, None, layout=loop.layout)


def checks_lines(checks: Mapping) -> List[str]:
    return [f"check {k} = {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
