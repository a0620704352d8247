"""From a profiler trace to the program's own layer split.

``tracecut`` reads the benchmark's ``bench/`` spans and cuts each device
operation to its HLO name. This module reads the names the program gives
itself (``repro.spans``) from the same ``.xplane.pb``:

* each device operation's ``jax.named_scope`` path, the ``op_name`` of its
  HLO instruction's metadata. A v5e trace's operation events carry only
  their HLO text and times; the op names sit in the HLO proto of each
  program, which the trace keeps in its ``/host:metadata`` plane (stat
  "Hlo Proto"), named as the device's "XLA Modules" events name the runs of
  that program. ``ProfileData`` does not show that plane's metadata, so
  ``hlo_programs`` reads it from the file's protobuf fields. The operation
  goes to the innermost scope of ``SCOPES`` in its path, or to ``unscoped``:
  so do the copies and loops the compiler adds, which carry no metadata,
  and each unscoped operation is labelled with the scopes it feeds;
* the program's ``repro/`` host spans, each with its thread (the line of the
  host plane it sits on); the loop's thread is the line that holds the
  benchmark's ``bench/window`` span.

``reduce`` works on plain lists, so a test can build a trace by hand:

* each instant of a chip's busy time in the window goes to the operation
  running then that started last (a loop's body operations take their part
  of the loop's interval), so the scopes and ``unscoped`` sum to the busy
  time; averaged over chips;
* each idle gap of the first chip is split among the loop thread's innermost
  ``repro/`` spans over it, or ``none``; spans on other threads (the
  checkpoint's persist thread) are timed but never take idle.

``METRICS`` are the per-layer numbers these give, each ``None`` where the
trace or the run holds nothing to read. ``python3 benchmarks/chip/scopecut.py
--workload <cell> --seed <n> --seconds <s>`` makes one traced run of a cell
on the chip, as ``run.py --trace 1`` does, and prints them with the split.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import sys
from collections import defaultdict
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from repro.spans import PREFIX, SCOPES  # noqa: E402
from tracecut import DEVICE_PLANE, OPS_LINE, op_name, union  # noqa: E402

UNSCOPED = "unscoped"
#: the device line whose events are the runs of each program
PROGRAMS_LINE = "XLA Modules"
#: the benchmark's window span, which marks the loop's thread
WINDOW = "bench/window"
TOP = 10
_TOKEN = re.compile(r"[^/()]+")

Op = Tuple[str, str, float, float]        # (op, scope, start_ns, end_ns)
Span = Tuple[int, str, float, float]      # (thread, name, start_ns, end_ns)


def innermost_scope(path: str) -> str:
    """``jit(step)/transpose(jvp(emb_fwd))/emb_relayout/reshape`` ->
    ``emb_relayout``: the last name of ``SCOPES`` in the path."""
    found = [t for t in _TOKEN.findall(path) if t in SCOPES]
    return found[-1] if found else UNSCOPED


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes (a memoryview) for the other wire types."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _sub(buf, number: int) -> Iterator[Any]:
    return (v for f, v in _fields(buf) if f == number)


def _ints(value) -> List[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        n, i = _varint(value, i)
        out.append(n)
    return out


def _text(buf, number: int) -> str:
    return bytes(next(_sub(buf, number), b"")).decode()


def program_scopes(hlo) -> Dict[str, Tuple[str, str]]:
    """``{instruction: (scope, feeds)}`` of one serialized ``HloProto``.

    Its fields: 1 the module, whose 6 is the entry computation's id and 3
    the computations; a computation's 5 is its id and 2 its instructions;
    an instruction's 1 is its name, 7 its ``OpMetadata`` (whose 2 is the op
    name), 35 its id, 36 its operands' ids, 38 the ids of the computations
    it calls. ``scope`` is the innermost of ``SCOPES`` in the op name. The
    compiler's own instructions (layout copies, the loops that move large
    arrays) have no op name and are unscoped; for an unscoped instruction
    ``feeds`` names the scopes fed by the entry instruction it belongs to
    (itself, or the loop whose body holds it), through unscoped ones,
    joined by "+"."""
    module = next(_sub(hlo, 1), memoryview(b""))
    entry = next(_sub(module, 6), None)
    instrs: Dict[int, Tuple[str, str, List[int], Optional[int]]] = {}
    called_by: Dict[int, int] = {}
    for comp in _sub(module, 3):
        cid = next(_sub(comp, 5), None)
        for ins in _sub(comp, 2):
            iid = next(_sub(ins, 35), None)
            op = next((_text(md, 2) for md in _sub(ins, 7)), "")
            for c in (c for v in _sub(ins, 38) for c in _ints(v)):
                called_by.setdefault(c, iid)
            instrs[iid] = (_text(ins, 1), op, [o for v in _sub(ins, 36) for o in _ints(v)],
                           cid)
    users: Dict[int, List[int]] = defaultdict(list)
    for iid, (_, _, operands, cid) in instrs.items():
        if cid == entry:
            for o in operands:
                users[o].append(iid)

    def owner(iid: int) -> int:
        for _ in range(len(instrs)):
            cid = instrs[iid][3]
            if cid == entry or cid not in called_by:
                break
            iid = called_by[cid]
        return iid

    fed: Dict[int, str] = {}

    def feeds(iid: int) -> str:
        if iid not in fed:
            found, todo, seen = set(), [iid], {iid}
            while todo:
                for u in users[todo.pop()]:
                    if u not in seen:
                        seen.add(u)
                        scope = innermost_scope(instrs[u][1])
                        if scope == UNSCOPED:
                            todo.append(u)
                        else:
                            found.add(scope)
            fed[iid] = "+".join(sorted(found))
        return fed[iid]

    out = {}
    for iid, (name, op, _, _) in instrs.items():
        scope = innermost_scope(op)
        out[name] = (scope, feeds(owner(iid)) if scope == UNSCOPED else "")
    return out


def hlo_programs(xspace: bytes) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """``{program: program_scopes(its HLO)}`` from a serialized ``XSpace``:
    its ``/host:metadata`` plane (``XPlane`` field 2 its name, 4 its event
    metadata by id) holds one ``XEventMetadata`` per program (2 its name, 5
    its stats), whose bytes stat (``XStat`` field 6) is the ``HloProto``."""
    out: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for plane in _sub(memoryview(xspace), 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                for stat in _sub(meta, 5):
                    for hlo in _sub(stat, 6):
                        out[_text(meta, 2)] = program_scopes(hlo)
    return out


def load(trace_dir: str) -> Dict:
    """``{"chips": [[Op]], "spans": [Span], "windows": [Span], "feeds":
    {op: scopes}}`` from the newest trace under ``trace_dir``: a thread is
    a line's index on the host planes, and ``feeds`` gives what each
    unscoped operation feeds (``program_scopes``)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    programs = hlo_programs(raw)
    chips, spans, windows, feeds = [], [], [], {}
    thread = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line.events for line in plane.lines}
            runs = sorted((e.start_ns, e.end_ns, e.name) for e in lines.get(PROGRAMS_LINE, ()))
            ops, r = [], 0
            for e in sorted(lines.get(OPS_LINE, ()), key=lambda e: e.start_ns):
                while r < len(runs) and runs[r][1] < e.start_ns:
                    r += 1
                scopes = programs.get(runs[r][2], {}) if r < len(runs) else {}
                instr = e.name.partition(" = ")[0].lstrip("%")
                scope, fed = scopes.get(instr, (UNSCOPED, ""))
                ops.append((op_name(e.name), scope, e.start_ns, e.end_ns))
                if scope == UNSCOPED:
                    feeds[ops[-1][0]] = fed
            chips.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((thread, e.name[len(PREFIX):], e.start_ns, e.end_ns))
                    elif e.name == WINDOW:
                        windows.append((thread, e.name, e.start_ns, e.end_ns))
                thread += 1
    chips.sort()
    return {"chips": [ops for _, ops in chips], "spans": spans, "windows": windows,
            "feeds": feeds}


def pieces(intervals: Sequence[Tuple[Any, float, float]]) -> List[Tuple[float, float, Any]]:
    """``(key, start, end)`` intervals -> ``(start, end, key)`` pieces that
    cover their union once: each instant goes to the interval that started
    last (of two that start together, the one that ends first)."""
    evs = sorted((s, e, k) for k, s, e in intervals if e > s)
    points = sorted({p for s, e, _ in evs for p in (s, e)})
    out: List[Tuple[float, float, Any]] = []
    heap: List[Tuple[float, float, Any]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            s, e, k = evs[i]
            heapq.heappush(heap, (-s, e, k))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            key = heap[0][2]
            if out and out[-1][2] == key and out[-1][1] == a:
                out[-1] = (out[-1][0], b, key)
            else:
                out.append((a, b, key))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


def reduce(chips: Sequence[Sequence[Op]], spans: Sequence[Span],
           windows: Sequence[Span], feeds: Optional[Dict[str, str]] = None) -> Dict:
    """Device time by scope, the operations under no scope and by the
    scopes they feed (``feeds``, else none), host span time by name, and
    idle time by the loop thread's innermost span, over the last window."""
    if not windows or not chips:
        raise ValueError("the trace holds no window span or no device")
    loop, _, lo, hi = windows[-1]
    scope_ns: Dict[str, float] = defaultdict(float)
    unscoped_ns: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for ops in chips:
        inside = [((scope, op),) + _clip(s, e, lo, hi)
                  for op, scope, s, e in ops if e > lo and s < hi]
        for a, b, (scope, op) in pieces(inside):
            scope_ns[scope] += (b - a) / len(chips)
            if scope == UNSCOPED:
                unscoped_ns[op] += (b - a) / len(chips)
            busy += (b - a) / len(chips)
    span_ns: Dict[str, List[float]] = defaultdict(list)
    for _, name, s, e in spans:
        if s >= lo and e <= hi:
            span_ns[name].append(e - s)
    # idle gaps of the first chip, split among the loop thread's spans
    merged = union([_clip(s, e, lo, hi) for _, _, s, e in chips[0] if e > lo and s < hi])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    owned = pieces([(name,) + _clip(s, e, lo, hi) for t, name, s, e in spans
                    if t == loop and e > lo and s < hi])
    idle_ns: Dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(owned) and owned[j][1] <= g0:
            j += 1
        k = j
        while k < len(owned) and owned[k][0] < g1:
            a, b, name = owned[k]
            ov = max(0.0, min(b, g1) - max(a, g0))
            idle_ns[name] += ov
            covered += ov
            k += 1
        idle_ns["none"] += (g1 - g0) - covered
    unscoped_feeds: Dict[str, float] = defaultdict(float)
    for op, ns in unscoped_ns.items():
        unscoped_feeds[(feeds or {}).get(op) or "none"] += ns
    return {"window_ns": hi - lo, "busy_ns": busy, "scope_ns": dict(scope_ns),
            "unscoped_ns": dict(unscoped_ns), "unscoped_feeds": dict(unscoped_feeds),
            "span_ns": dict(span_ns), "idle_ns": dict(idle_ns)}


def breakdown(summary: Dict) -> Dict:
    """The split in seconds: device time by scope, the costliest unscoped
    operations, unscoped time by the scopes it feeds, idle time by the loop
    thread's span."""
    def top(d, n=None):
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    return {"scopes": top(summary["scope_ns"]),
            "unscoped_ops": top(summary["unscoped_ns"], TOP),
            "unscoped_feeds": top(summary["unscoped_feeds"]),
            "idle_by_span": top(summary["idle_ns"])}


# ---------------------------------------------------------------- metrics
# Each reads ``ctx``: ``program`` (``reduce``'s output), ``steps`` in the
# window, ``save_bytes`` (``FlashCheckpoint.last_save_bytes``, the same for
# every save) and ``save_flatten_s`` (``last_save_seconds`` per save).

#: the scope whose metric also counts the unscoped operations that feed it
#: alone: the compiler's moves of a pool into the layout of the program's
#: ``_pool2d`` and ``lane_pack`` reshapes carry no op name, and are the
#: greater part of the relayout
RELAYOUT = "emb_relayout"


def _scope_ms(scope: str) -> Callable[[Dict], Optional[float]]:
    def read(ctx):
        prog = ctx.get("program")
        if not prog or not ctx["steps"]:
            return None
        scope_ns = prog["scope_ns"]
        if not any(s in scope_ns for s in SCOPES):
            return None             # a program that names no scope
        if scope != UNSCOPED and scope not in scope_ns:
            return None
        fed = prog["unscoped_feeds"].get(RELAYOUT, 0.0)
        ns = scope_ns.get(scope, 0.0) + {RELAYOUT: fed, UNSCOPED: -fed}.get(scope, 0.0)
        return ns / 1e6 / ctx["steps"]
    return read


def _wait_ms(*names: str) -> Callable[[Dict], Optional[float]]:
    def read(ctx):
        prog = ctx.get("program")
        if not prog or not ctx["steps"] or not any(n in prog["span_ns"] for n in names):
            return None
        return sum(prog["idle_ns"].get(n, 0.0) for n in names) / 1e6 / ctx["steps"]
    return read


def _ckpt_stamp_ms(ctx):
    prog = ctx.get("program")
    stamps = prog and prog["span_ns"].get("ckpt_stamp")
    if not stamps:
        return None
    return sum(stamps) / len(stamps) / 1e6


def _ckpt_d2h_gbps(ctx):
    saves = ctx.get("save_flatten_s")
    if not ctx.get("save_bytes") or not saves or not sum(saves):
        return None
    return ctx["save_bytes"] * len(saves) / sum(saves) / 1e9


METRICS: Dict[str, Callable[[Dict], Optional[float]]] = {
    "relayout_ms": _scope_ms(RELAYOUT),
    "emb_fwd_ms": _scope_ms("emb_fwd"),
    "emb_bwd_ms": _scope_ms("emb_bwd"),
    "dense_ms": _scope_ms("dense"),
    "opt_update_ms": _scope_ms("opt_update"),
    "unscoped_ms": _scope_ms(UNSCOPED),
    "tracker_wait_ms": _wait_ms("observe"),
    "input_wait_ms": _wait_ms("loader", "remap"),
    "ckpt_stamp_ms": _ckpt_stamp_ms,
    "ckpt_d2h_gbps": _ckpt_d2h_gbps,
}


def traced_run(manifest, cell: str, seed: int, seconds: float, **kw) -> Dict:
    """One traced run of ``cell`` through ``harness.run`` (``kw`` passed
    on), with ``harness``'s trace loader and loop factory wrapped for the
    run, to keep the trace for this reduction and the loop for its
    checkpoint's counters; the line ``main`` prints."""
    import harness
    kept: Dict = {}
    load_bench, make_loop = harness.tracecut.load, harness.make_loop

    def load_both(trace_dir):
        kept["program"] = reduce(**load(trace_dir))
        return load_bench(trace_dir)

    def keep_loop(*a, **k):
        kept["loop"] = make_loop(*a, **k)
        return kept["loop"]

    harness.tracecut.load, harness.make_loop = load_both, keep_loop
    try:
        result = harness.run(manifest, cell, seed, seconds, trace=True, **kw)
    finally:
        harness.tracecut.load, harness.make_loop = load_bench, make_loop
    loop, prog = kept["loop"], kept["program"]
    saves = len(prog["span_ns"].get("ckpt_d2h", ()))     # the window's saves
    ctx = {"program": prog, "steps": result["attempted"],
           "save_bytes": loop.ckpt.last_save_bytes if loop.ckpt else None,
           "save_flatten_s": loop.save_flatten_s[-saves:] if saves else []}
    window_s = prog["window_ns"] / 1e9
    return {"workload": cell, "seed": seed, "correct": result["correct"],
            "steps": ctx["steps"], "save_bytes": ctx["save_bytes"],
            "samples_per_s": ctx["steps"] * loop.cfg.batch_size / window_s,
            "busy_s": prog["busy_ns"] / 1e9, "window_s": window_s,
            "metrics": {k: f(ctx) for k, f in METRICS.items()},
            "bench_metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "bench_idle_gaps": result["breakdown"]["idle_gaps"],
            "split": breakdown(prog),
            "span_ms": {k: [len(v), sum(v) / 1e6] for k, v in prog["span_ns"].items()}}


def main(argv=None) -> int:
    """One traced run of a cell on the chip: prints ``traced_run``'s line."""
    import argparse
    import json
    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    from manifest import Manifest
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 2
    line = traced_run(Manifest(), args.workload, args.seed, args.seconds,
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
