"""From a profiler trace to the numbers the per-layer metrics read.

The traced run writes one ``.xplane.pb``. ``load`` reads it with JAX's own
``ProfileData`` into plain lists: each chip's device operations, and the
benchmark's host spans (``bench/<layer>``, see ``loop.py``). ``reduce``
works on those lists alone, so a test can build a small trace by hand:

* busy time is the union of a chip's operation intervals inside the window
  (the ``bench/window`` span), averaged over chips; idle share is 1 minus
  busy over the window;
* operation time is summed by name: the trace names an operation by its
  whole HLO text, which ``op_name`` cuts to the instruction's name, its
  opcode, its result type and the custom call's target;
* each idle gap of the first chip is split among the host spans that
  overlap it, so that idle time is labelled by what the host was doing;
  time under no span is ``none``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from loop import SPAN

Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

#: device planes are named "/device:TPU:<n>"; their operations sit on this line
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
_OPCODE = re.compile(r"[\]\})] ([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """``%jvp__.2 = f32[26,512,128]{...} custom-call(...),
    custom_call_target="tpu_custom_call", ...`` ->
    ``%jvp__.2 custom-call f32[26,512,128] tpu_custom_call``."""
    name, sep, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    if m is None:
        return hlo[:120]
    result = _LAYOUT.sub("", rest[:m.start() + 1])
    target = _TARGET.search(hlo)
    return " ".join([name, m.group(1), result[:60]]
                    + ([target.group(1)] if target else []))


def load(trace_dir: str) -> Dict:
    """``{"chips": [[Event]], "spans": [Event]}`` from the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    chips, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = [(op_name(e.name), e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            chips.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            spans += [(e.name[len(SPAN):], e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN)]
    chips.sort()
    return {"chips": [ops for _, ops in chips], "spans": spans}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(chips: Sequence[Sequence[Event]], spans: Sequence[Event]) -> Dict:
    """Busy and idle time, operation time by name, span time by name, and
    idle time by host span, over the ``window`` span."""
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows or not chips:
        raise ValueError("the trace holds no window span or no device")
    lo, hi = windows[-1]
    busy = []
    op_ns: Dict[str, float] = defaultdict(float)
    for ops in chips:
        merged = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            if e > lo and s < hi:
                op_ns[name] += e - s
    span_ns: Dict[str, List[float]] = defaultdict(list)
    leaves = []
    for name, s, e in spans:
        if name != "window" and s >= lo and e <= hi:
            span_ns[name].append(e - s)
            leaves.append((s, e, name))
    leaves.sort()
    # idle gaps of the first chip, split among the host spans over them
    merged = union(_clip([(s, e) for _, s, e in chips[0]], lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    idle_ns: Dict[str, float] = defaultdict(float)
    first = 0                   # the step's spans follow one another
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        while first < len(leaves) and leaves[first][1] <= g0:
            first += 1
        covered = 0.0
        for j in range(first, len(leaves)):
            s, e, name = leaves[j]
            if s >= g1:
                break
            ov = _overlap(g0, g1, s, e)
            idle_ns[name] += ov
            covered += ov
        idle_ns["none"] += max(0.0, (g1 - g0) - covered)
    window_ns = hi - lo
    busy_ns = sum(busy) / len(busy)
    return {"window_ns": window_ns, "busy_ns": busy_ns,
            "idle_share": 1.0 - busy_ns / window_ns,
            "op_ns": dict(op_ns), "span_ns": dict(span_ns),
            "idle_ns": dict(idle_ns)}


def breakdown(summary: Dict) -> Dict:
    """The ``breakdown`` of a traced run's result line, in seconds."""
    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(summary["op_ns"]),
            "idle_gaps": top(summary["idle_ns"])}
