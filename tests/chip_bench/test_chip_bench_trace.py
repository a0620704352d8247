"""The reduction from a trace to the per-layer numbers, on traces built by
hand: union of intervals, time by name, idle gaps by host span."""
import pytest

MS = 1e6   # ns


def _trace():
    """A window of 100 ms: two steps, each host span before its device work."""
    spans = [("window", 0, 100 * MS),
             ("observe", 0, 20 * MS), ("dispatch", 20 * MS, 22 * MS),
             ("observe", 50 * MS, 70 * MS), ("dispatch", 70 * MS, 72 * MS),
             ("observe", 200 * MS, 210 * MS)]          # after the window
    chip = [("fusion.1", 22 * MS, 30 * MS), ("kernel", 25 * MS, 27 * MS),
            ("fusion.1", 72 * MS, 80 * MS), ("copy.2", 79 * MS, 90 * MS),
            ("fusion.1", -10 * MS, 5 * MS)]             # starts before the window
    return [chip], spans


def test_busy_is_the_union_of_intervals_in_the_window():
    import tracecut
    s = tracecut.reduce(*_trace())
    # [0,5] + [22,30] + [72,90] = 31 ms of 100
    assert s["window_ns"] == 100 * MS
    assert s["busy_ns"] == pytest.approx(31 * MS)
    assert s["idle_share"] == pytest.approx(0.69)


def test_time_by_name_and_spans_in_the_window():
    import tracecut
    s = tracecut.reduce(*_trace())
    assert s["op_ns"]["fusion.1"] == pytest.approx(31 * MS)
    assert s["op_ns"]["kernel"] == pytest.approx(2 * MS)
    assert s["span_ns"]["observe"] == [20 * MS, 20 * MS]
    bd = tracecut.breakdown(s)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.031)]


def test_idle_gaps_are_split_among_host_spans():
    import tracecut
    s = tracecut.reduce(*_trace())
    # gaps [5,22], [30,72], [90,100]: observe covers 15 + 20, dispatch 2 + 2
    assert s["idle_ns"]["observe"] == pytest.approx(35 * MS)
    assert s["idle_ns"]["dispatch"] == pytest.approx(4 * MS)
    assert s["idle_ns"]["none"] == pytest.approx(30 * MS)
    assert sum(s["idle_ns"].values()) == pytest.approx(69 * MS)


def test_busy_is_averaged_over_chips():
    import tracecut
    chips, spans = _trace()
    s = tracecut.reduce(chips + [[("fusion.9", 0, 100 * MS)]], spans)
    assert s["busy_ns"] == pytest.approx((31 + 100) / 2 * MS)


def test_a_trace_without_window_or_device_is_refused():
    import tracecut
    chips, spans = _trace()
    with pytest.raises(ValueError):
        tracecut.reduce(chips, [x for x in spans if x[0] != "window"])
    with pytest.raises(ValueError):
        tracecut.reduce([], spans)


@pytest.mark.parametrize("hlo,name", [
    ('%jvp__.2 = f32[26,512,128]{2,1,0:T(8,128)S(1)} custom-call(s32[1664,1,32]{2,1,0} '
     '%bitcast.69), custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "%jvp__.2 custom-call f32[26,512,128] tpu_custom_call"),
    ("%while.35 = (u32[]{:T(128)}, f32[52707840]{0:T(1024)}) while((u32[]{:T(128)}, "
     "f32[52707840]{0}) %tuple.143), condition=%wide.cond.1",
     "%while.35 while (u32[], f32[52707840])"),
    ("%fusion.9 = f32[3294240,16]{0,1:T(8,128)} fusion(f32[3294240,16]{0,1:T(8,128)} %b), "
     "kind=kCustom", "%fusion.9 fusion f32[3294240,16]"),
    ("not hlo at all", "not hlo at all")])
def test_op_names_are_cut_from_the_hlo_text(hlo, name):
    import tracecut
    assert tracecut.op_name(hlo) == name


def test_union_merges_touching_and_nested():
    import tracecut
    assert tracecut.union([(5, 6), (0, 2), (2, 3), (1, 1.5)]) == [(0, 3), (5, 6)]


def _ctx(op_ns, steps=10, save_s=()):
    from manifest import Manifest
    from conftest import BENCH, ROOT
    m = Manifest(ROOT, BENCH)
    sizes = m.config("wide_deep")["sizes"]
    return m, {"trace": {"op_ns": op_ns, "span_ns": {"observe": [2e6] * steps,
                                                      "loader": [1e6] * steps},
                         "window_ns": 1e9, "busy_ns": 2e8, "idle_share": 0.8},
               "steps": steps, "sizes": sizes, "work": m.work("wide_deep"),
               "peaks": m.peaks("TPU v5 lite"), "save_flatten_s": list(save_s)}


def test_readers_give_nothing_where_there_is_nothing_to_read():
    m, ctx = _ctx({"fusion.1": 5e6})
    assert m.reader("emb_fwd_roofline").read(ctx) is None
    assert m.reader("ckpt_flatten_ms").read(ctx) is None
    assert m.reader("tracker_observe_ms").read(ctx) == pytest.approx(2.0)
    assert m.reader("host_input_ms").read(ctx) == pytest.approx(1.0)
    assert m.reader("device_idle_share").read(ctx) == pytest.approx(80.0)


def test_roofline_and_mfu_from_the_work_files():
    import workcore
    m, ctx = _ctx({"%jvp__.2 custom-call f32[26,512,128] tpu_custom_call": 15e6,
                   "%jvp__.3 custom-call f32[26,512,128] tpu_custom_call": 5e6,
                   "%fusion.9 fusion f32[3294240,16]": 30e6}, save_s=(0.2, 0.4))
    sizes, peaks = ctx["sizes"], ctx["peaks"]
    least = sum(workcore.emb_fwd_bytes(sizes, d) for d in (16, 1)) / peaks["hbm_bytes_per_s"]
    assert m.reader("emb_fwd_roofline").read(ctx) == pytest.approx(100 * least * 10 / 0.02)
    flops = ctx["work"].step_flops(sizes) * 10
    assert m.reader("step_mfu").read(ctx) == pytest.approx(100 * flops / 1.0 / 197e12)
    assert m.reader("ckpt_flatten_ms").read(ctx) == pytest.approx(300.0)
