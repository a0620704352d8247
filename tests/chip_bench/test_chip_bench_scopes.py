"""The program's layer split (``scopecut``): scope paths, the reduction on
traces built by hand, its readers, and the ``repro/`` spans a traced loop
run of the checkpoint cell leaves on the host plane."""
import pytest

from conftest import tiny_cell

MS = 1e6   # ns
LOOP, PERSIST = 3, 7    # thread ids


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/jvp(emb_fwd)/emb_relayout/reshape", "emb_relayout"),
    ("jit(train_step)/transpose(jvp(emb_relayout))/reshape", "emb_relayout"),
    ("jit(train_step)/transpose(jvp(dense))/dot_general", "dense"),
    ("jit(train_step)/emb_bwd/scatter-add", "emb_bwd"),
    ("jit(train_step)/opt_update/emb_relayout/reshape", "emb_relayout"),
    ("jit(train_step)/jit(_take)/gather", "unscoped"),
    ("", "unscoped")])
def test_an_op_goes_to_its_innermost_scope(path, scope):
    import scopecut
    assert scopecut.innermost_scope(path) == scope


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _pb(*fields) -> bytes:
    """A protobuf message of ``(number, int, bytes or str)`` fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return out


def _hlo(comps) -> bytes:
    """An ``HloProto`` whose module's entry is the first of ``comps``:
    ``{computation id: [(id, name, op_name, operand ids, called ids)]}``."""
    def instr(iid, name, op, operands, calls):
        return _pb((1, name), (35, iid), *([(7, _pb((2, op)))] if op else []),
                   *[(36, o) for o in operands], *[(38, c) for c in calls])
    module = _pb((6, next(iter(comps))), *[
        (3, _pb((5, cid), *[(2, instr(*i)) for i in instrs])) for cid, instrs in comps.items()])
    return _pb((1, module))


def _event(meta_id, start_us, end_us):
    return (f"events {{ metadata_id: {meta_id} offset_ps: {start_us * 1000000} "
            f"duration_ps: {(end_us - start_us) * 1000000} }}")


def test_load_joins_each_op_to_its_programs_scope(tmp_path):
    """Two programs share an instruction name, and each run of an op takes
    the op name of the program running then. The compiler's loop and its
    body op have none: unscoped, feeding the dense op that reads the loop."""
    import scopecut
    from jax.profiler import ProfileData
    programs = {
        "jit_train_step(7)": {1: [(1, "fusion.1", "jit(train_step)/jvp(dense)/mul", [2], []),
                                  (2, "while.2", "", [], [20])],
                              20: [(3, "dynamic-update-slice.3", "", [], [])]},
        "jit_save(9)": {1: [(1, "fusion.1", "jit(save)/emb_fwd/take", [], [])]}}
    names = list(programs) + ["%fusion.1 = f32[8] fusion(%p)",
                              "%while.2 = (u32[]) while(%t)",
                              "%dynamic-update-slice.3 = f32[8] dynamic-update-slice(%a)",
                              "bench/window", "repro/observe", "repro/ckpt_persist"]
    meta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
                     for i, n in enumerate(names))
    escaped = ["".join("\\%03o" % b for b in _hlo(comps)) for comps in programs.values()]
    hlo = "\n".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" stats {{ '
        f'metadata_id: 1 bytes_value: "{esc}" }} }} }}'
        for i, (n, esc) in enumerate(zip(programs, escaped)))
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0" {meta}
      lines {{ id: 1 name: "XLA Modules" {_event(1, 0, 10)} {_event(2, 20, 30)} }}
      lines {{ id: 2 name: "XLA Ops" {_event(3, 1, 3)} {_event(4, 4, 8)} {_event(5, 5, 6)}
                                     {_event(3, 21, 23)} }} }}
    planes {{ id: 2 name: "/host:metadata" {hlo}
      stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
    planes {{ id: 3 name: "/host:CPU" {meta}
      lines {{ id: 1 name: "python3" {_event(6, 0, 40)} {_event(7, 0, 1)} }}
      lines {{ id: 2 name: "python3" {_event(8, 25, 40)} }} }}"""
    (tmp_path / "t.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    got = scopecut.load(str(tmp_path))
    assert [(op.split()[0], scope) for op, scope, _, _ in got["chips"][0]] == [
        ("%fusion.1", "dense"), ("%while.2", "unscoped"),
        ("%dynamic-update-slice.3", "unscoped"), ("%fusion.1", "emb_fwd")]
    assert {op.split()[0]: fed for op, fed in got["feeds"].items()} == {
        "%while.2": "dense", "%dynamic-update-slice.3": "dense"}
    assert got["windows"] == [(0, "bench/window", 0, 40000)]
    assert got["spans"] == [(0, "observe", 0, 1000), (1, "ckpt_persist", 25000, 40000)]
    split = scopecut.reduce(**got)
    assert split["unscoped_feeds"] == pytest.approx({"dense": 4000})


def test_scopes_are_read_from_a_real_profile(tmp_path):
    """The profiler's own file (here the CPU's) holds each program's HLO
    with the program's scopes in its op names."""
    import glob

    import jax
    import jax.numpy as jnp
    import scopecut
    from repro.spans import scope

    @jax.jit
    def f(x):
        with scope("dense"):
            return jnp.tanh(x @ x)
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    raw = open(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0], "rb").read()
    (scopes,) = [v for k, v in scopecut.hlo_programs(raw).items() if k.startswith("jit_f(")]
    assert "dense" in {scope for scope, _ in scopes.values()}


def _trace():
    """A window of 100 ms. The device: a loop (unscoped) whose body runs a
    relayout op, then the forward and an op that starts before the window.
    The loop thread: observe with loader nested in a step span, then
    dispatch; the persist thread: a save over the whole second gap."""
    chip = [("%while.1", "unscoped", 10 * MS, 30 * MS),
            ("%fusion.2", "emb_relayout", 12 * MS, 20 * MS),
            ("%custom.3", "emb_fwd", 40 * MS, 50 * MS),
            ("%fusion.4", "dense", 45 * MS, 48 * MS),
            ("%fusion.5", "opt_update", -5 * MS, 5 * MS)]
    spans = [(LOOP, "step", 0, 60 * MS),
             (LOOP, "observe", 5 * MS, 35 * MS),
             (LOOP, "loader", 32 * MS, 34 * MS),
             (LOOP, "dispatch", 35 * MS, 37 * MS),
             (PERSIST, "ckpt_persist", 30 * MS, 100 * MS),
             (LOOP, "observe", 150 * MS, 160 * MS)]       # after the window
    windows = [(LOOP, "bench/window", 0, 100 * MS)]
    return [chip], spans, windows


def test_scopes_and_unscoped_sum_to_the_busy_time():
    import scopecut
    import tracecut
    chips, spans, windows = _trace()
    s = scopecut.reduce(chips, spans, windows)
    assert s["scope_ns"] == pytest.approx({"opt_update": 5 * MS, "unscoped": 12 * MS,
                                           "emb_relayout": 8 * MS, "emb_fwd": 7 * MS,
                                           "dense": 3 * MS})
    assert s["unscoped_ns"] == pytest.approx({"%while.1": 12 * MS})
    busy = tracecut.reduce([[(n, a, b) for n, _, a, b in chips[0]]],
                           [("window", 0, 100 * MS)])["busy_ns"]
    assert sum(s["scope_ns"].values()) == pytest.approx(busy) == pytest.approx(s["busy_ns"])


def test_idle_goes_to_the_loop_threads_innermost_span_only():
    import scopecut
    s = scopecut.reduce(*_trace())
    # gaps [5,10], [30,40], [50,100]: observe holds 5 + 5 of them, loader 2
    # inside observe, observe again 1, dispatch 2, the step span alone 3 + 10;
    # the persist thread's span over [30,100] takes none
    assert s["idle_ns"] == pytest.approx({"observe": 5 * MS + 3 * MS, "loader": 2 * MS,
                                          "dispatch": 2 * MS, "step": 13 * MS,
                                          "none": 40 * MS})
    assert "ckpt_persist" not in s["idle_ns"]
    assert s["span_ns"]["ckpt_persist"] == [70 * MS]
    assert s["span_ns"]["observe"] == [30 * MS]


def test_a_trace_without_window_or_device_is_refused():
    import scopecut
    chips, spans, _ = _trace()
    with pytest.raises(ValueError):
        scopecut.reduce(chips, spans, [])
    with pytest.raises(ValueError):
        scopecut.reduce([], spans, _trace()[2])


def _ctx():
    import scopecut
    return {"program": scopecut.reduce(*_trace()), "steps": 2,
            "save_bytes": 600e6, "save_flatten_s": [0.3, 0.3]}


def test_readers_read_the_split():
    import scopecut
    got = {k: f(_ctx()) for k, f in scopecut.METRICS.items()}
    assert got == pytest.approx({
        "relayout_ms": 4.0, "emb_fwd_ms": 3.5, "emb_bwd_ms": None, "dense_ms": 1.5,
        "opt_update_ms": 2.5, "unscoped_ms": 6.0, "tracker_wait_ms": 4.0,
        "input_wait_ms": 1.0, "ckpt_stamp_ms": None, "ckpt_d2h_gbps": 2.0})


@pytest.mark.parametrize("fed,relayout,unscoped", [
    ("emb_relayout", 10.0, 0.0),                 # the loop feeds the reshape alone
    ("emb_relayout+opt_update", 4.0, 6.0),
    ("opt_update", 4.0, 6.0)])
def test_relayout_counts_the_unscoped_ops_that_feed_it_alone(fed, relayout, unscoped):
    """The compiler's pool moves carry no op name; those that feed only the
    program's relayout reshapes count in ``relayout_ms``, the rest stays in
    ``unscoped_ms``, and the six scope metrics still sum to the busy time."""
    import scopecut
    ctx = _ctx()
    ctx["program"] = scopecut.reduce(*_trace(), feeds={"%while.1": fed})
    got = {k: f(ctx) for k, f in scopecut.METRICS.items()}
    assert (got["relayout_ms"], got["unscoped_ms"]) == pytest.approx((relayout, unscoped))
    six = ["relayout_ms", "emb_fwd_ms", "emb_bwd_ms", "dense_ms", "opt_update_ms", "unscoped_ms"]
    assert sum(got[k] or 0.0 for k in six) * ctx["steps"] == pytest.approx(
        ctx["program"]["busy_ns"] / 1e6)


@pytest.mark.parametrize("gone", ["program", "steps", "save_bytes", "save_flatten_s",
                                  "scopes", "spans"])
def test_readers_give_nothing_where_their_input_is_missing(gone):
    import scopecut
    ctx = _ctx()
    if gone == "scopes":        # a program that names no scope, as before
        ns = ctx["program"]["scope_ns"]
        ctx["program"]["scope_ns"] = {"unscoped": sum(ns.values())}
    elif gone == "spans":       # nor spans
        ctx["program"]["span_ns"], ctx["program"]["idle_ns"] = {}, {"none": 1.0}
    elif gone == "steps":
        ctx["steps"] = 0
    else:
        ctx.pop(gone)
    reads = {"program": [k for k in scopecut.METRICS if k != "ckpt_d2h_gbps"],
             "steps": [k for k in scopecut.METRICS
                       if k.endswith("_ms") and k != "ckpt_stamp_ms"],
             "save_bytes": ["ckpt_d2h_gbps"], "save_flatten_s": ["ckpt_d2h_gbps"],
             "scopes": ["relayout_ms", "emb_fwd_ms", "emb_bwd_ms", "dense_ms",
                        "opt_update_ms", "unscoped_ms"],
             "spans": ["tracker_wait_ms", "input_wait_ms", "ckpt_stamp_ms"]}[gone]
    for name in reads:
        assert scopecut.METRICS[name](ctx) is None, name


def test_a_traced_loop_run_leaves_the_programs_spans(manifest, tmp_path):
    """The checkpoint cell's loop at a tiny size, under the profiler: every
    host span of the input, the tracker and the checkpoint is on the host
    plane, the persist thread's on a thread of its own."""
    import jax

    import harness
    import ringgen
    import scopecut
    from repro.core.flash_checkpoint import FlashCheckpoint
    conf, traffic = tiny_cell(manifest, "wide_deep.zipf.ckpt")
    job = harness.build_job(manifest, conf, traffic)
    ring = ringgen.make_ring(5, conf["sizes"], traffic)
    ckpt = FlashCheckpoint(str(tmp_path / "ckpt"))
    loop = harness.make_loop(job, harness.state_fn(job)(harness.seed_key(5)), ring, ckpt)
    loop.step()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        loop.run_for(0.0)                        # to the end of the first save
        ckpt.wait()
    finally:
        jax.profiler.stop_trace()
    got = scopecut.load(str(tmp_path / "trace"))
    threads = {name: t for t, name, _, _ in got["spans"]}
    assert {"loader", "remap", "observe", "ckpt_stamp", "ckpt_d2h",
            "ckpt_persist"} <= set(threads)
    (loop_thread, *_), = got["windows"]
    assert threads["observe"] == threads["ckpt_d2h"] == loop_thread
    assert threads["ckpt_persist"] != loop_thread


def test_a_traced_run_reports_the_program_metrics(manifest, monkeypatch):
    """``traced_run``, the wiring ``main`` prints, on the CPU at a tiny size:
    the harness's trace loader and loop factory are wrapped for the run and
    put back, and the line holds the checkpoint cell's program metrics. A
    CPU trace has no device plane, so both loaders add one chip, busy in
    ``emb_fwd`` over the window's first half; the host spans are the run's."""
    import harness
    import scopecut
    import tracecut
    real_bench, real_prog, make_loop = tracecut.load, scopecut.load, harness.make_loop

    def bench_load(trace_dir):
        got = real_bench(trace_dir)
        (lo, hi), = [(s, e) for n, s, e in got["spans"] if n == "window"][-1:]
        return dict(got, chips=[[("%fusion.1", lo, (lo + hi) / 2)]])

    def prog_load(trace_dir):
        got = real_prog(trace_dir)
        _, _, lo, hi = got["windows"][-1]
        return dict(got, chips=[[("%fusion.1", "emb_fwd", lo, (lo + hi) / 2)]])

    monkeypatch.setattr(tracecut, "load", bench_load)
    monkeypatch.setattr(scopecut, "load", prog_load)
    monkeypatch.setattr(manifest, "peaks", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})
    conf, traffic = tiny_cell(manifest, "wide_deep.zipf.ckpt")
    line = scopecut.traced_run(manifest, "wide_deep.zipf.ckpt", 2**41 + 17, 0.3,
                               conf=conf, traffic=traffic, log=lambda s: None)
    assert tracecut.load is bench_load and harness.make_loop is make_loop
    assert line["correct"] and line["steps"] > 0
    got = line["metrics"]
    assert got["emb_fwd_ms"] * line["steps"] == pytest.approx(line["busy_s"] * 1e3)
    assert got["unscoped_ms"] == 0.0 and got["relayout_ms"] is None
    for name in ("tracker_wait_ms", "input_wait_ms", "ckpt_stamp_ms", "ckpt_d2h_gbps"):
        assert got[name] is not None and got[name] >= 0, name
    saves, _ = line["span_ms"]["ckpt_d2h"]
    assert saves >= 1 and line["span_ms"]["ckpt_stamp"][0] == saves
    assert line["save_bytes"] > 0 and "ckpt_flatten_ms" in line["bench_metrics"]
