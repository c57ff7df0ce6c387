"""The trace reduction: on hand-built traces, on a small trace recorded
on a TPU v5e through the harness's traced path (two spans, each a
``KMeansModel.predict`` call of 16384 rows on a model fitted at n=16384,
d=128, k=64, kn=16), and on the benchmark's own program groups."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
GROUPS = {"init": ["^jit_gdi"], "iterate": ["^jit_step"]}


def _trace():
    ops = {"/device:TPU:0": [
        tr.Op("sort.1", "jit_gdi_round_step", 100, 200),
        tr.Op("fusion.2", "jit_gdi_round_step", 150, 260),   # overlaps
        tr.Op("kernel", "jit_step", 400, 500),
        tr.Op("fusion.9", "jit_other", 900, 1200),           # outside
    ]}
    host = [("fit", 50, 600), ("PjitFunction(step)", 300, 420),
            ("$python frame", 270, 380)]
    return tr.Trace(ops, sorted(host, key=lambda h: h[1]))


def test_busy_is_the_union_inside_the_span():
    s = tr.reduce(_trace(), ["fit"], GROUPS)
    assert s.window_s == pytest.approx(550e-9)
    assert s.busy_s == pytest.approx((160 + 100) * 1e-9)
    assert s.idle_share == pytest.approx(1 - 260 / 550)


def test_group_time_counts_each_op_clipped():
    s = tr.reduce(_trace(), ["fit"], GROUPS)
    assert s.group_s["init"] == pytest.approx(160e-9)   # overlap once
    assert s.group_s["iterate"] == pytest.approx(100e-9)
    assert "other" not in s.group_s           # its op lies outside


def test_group_times_sum_to_busy_where_groups_overlap():
    ops = {"/device:TPU:0": [
        tr.Op("a", "jit_step", 100, 300),
        tr.Op("b", "jit_gdi_round_step", 200, 400),   # init wins 200..300
        tr.Op("c", "jit_other", 250, 500),
        tr.Op("d", "jit_step", 450, 480),             # iterate over other
    ]}
    s = tr.reduce(tr.Trace(ops, [("fit", 0, 1000)]), ["fit"], GROUPS)
    assert s.group_s == pytest.approx({"iterate": 130e-9, "init": 200e-9,
                                       "other": 70e-9})
    assert sum(s.group_s.values()) == pytest.approx(s.busy_s)


def test_gaps_are_named_by_the_innermost_host_event():
    s = tr.reduce(_trace(), ["fit"], GROUPS)
    gaps = dict((round(sec * 1e9), name) for name, sec in s.gaps)
    assert gaps[140] == "PjitFunction(step)"  # 260..400, $-frames skipped
    assert gaps[100] == "fit"                 # 500..600
    assert gaps[50] == "fit"                  # 50..100
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["jit_gdi_round_step:fusion.2",
                                   pytest.approx(110e-9)]
    assert len(bd["idle_gaps"]) == 3


def test_no_span_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="no span"):
        tr.reduce(_trace(), ["predict_call"], GROUPS)
    with pytest.raises(ValueError, match="no device"):
        tr.reduce(tr.Trace({}, [("fit", 0, 10)]), ["fit"], GROUPS)


def test_group_of_first_match_wins():
    assert tr.group_of("jit_gdi_round_step", GROUPS) == "init"
    assert tr.group_of("jit_foo", GROUPS) == "other"


def test_recorded_tpu_trace():
    path = os.path.join(DATA, "tiny_predict.xplane.pb.gz")
    trace = tr.load(path, ["predict_call"])
    assert list(trace.ops) == ["/device:TPU:0"]
    groups = {"predict": ["^jit__route", "^jit_bounded_predict_assign"]}
    s = tr.reduce(trace, ["predict_call"], groups)
    assert len(s.spans) == 2
    assert 0 < s.busy_s <= s.window_s
    assert s.group_s["predict"] > 0.9 * s.busy_s
    assert sum(s.group_s.values()) == pytest.approx(s.busy_s)
    bd = s.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    name, sec = bd["device_ops"][0]
    assert name == "jit__route:fusion.7 f32[393216]" and sec > 0
    assert bd["idle_gaps"][0][1] >= bd["idle_gaps"][-1][1]


def test_op_name_keeps_name_and_type():
    assert tr.op_name("%fusion.7 = f32[13631488]{0:T(1024)} fusion(%a)") \
        == "fusion.7 f32[13631488]"
    assert tr.op_name("%t = (s32[8]{0}, f32[8]{0}) custom-call(%x)") \
        == "t (s32[8], f32[8])"
    assert tr.op_name("copy-start") == "copy-start"


def test_program_groups_load_from_their_files():
    groups = tr.load_groups(os.path.join(os.path.dirname(DATA), "..",
                                         "program_groups"))
    assert list(groups) == sorted(groups)
    assert {"init", "iterate"} <= set(groups)
    assert tr.group_of("jit_gdi_round_step", groups) == "init"
    assert tr.group_of("jit__resident_single_step", groups) == "iterate"
