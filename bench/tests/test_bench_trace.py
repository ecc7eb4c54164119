"""The trace reducer on hand-built events (bench/lib/trace.py)."""
import os
import sys

from pytest import approx

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import trace as tr  # noqa: E402
from bench.lib.trace import Event, Trace  # noqa: E402


def _trace():
    ops = [Event("%while.1 = (...) while(...)", 0, 100),
           Event("%fusion.2 = f32[8] fusion(...)", 10, 30),
           Event("%weighted_agg.7 = f32[1,128] custom-call(...)", 50, 20),
           Event("%copy.3 = f32[8] copy(...)", 150, 50),
           Event("%fusion.2 = f32[8] fusion(...)", 260, 40)]
    host = [Event("bench.window", 0, 400), Event("bench.call", 0, 220),
            Event("bench.call", 220, 170), Event("bench.wait", 390, 10)]
    return Trace(devices={"/device:TPU:0": ops}, host=host)


def test_merge_and_busy_union():
    assert tr.merge([(5, 10), (0, 3), (2, 6), (20, 30)]) == [(0, 10),
                                                             (20, 30)]
    t = _trace()
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 400)
    # union: [0,100] + [150,200] + [260,300] = 190 ns
    assert tr.busy_ns(t.devices["/device:TPU:0"], lo, hi) == 190
    assert tr.mean_busy_s(t, lo, hi) == approx(190e-9)
    # clipping to a narrower window
    assert tr.busy_ns(t.devices["/device:TPU:0"], 50, 170) == 70


def test_idle_gaps_are_labelled_by_host_activity():
    t = _trace()
    gaps = tr.idle_gaps(t.devices["/device:TPU:0"], 0, 400)
    assert gaps == [(100, 150), (200, 260), (300, 400)]
    assert tr.label(t.host, 125) == "bench.call"
    assert tr.label(t.host, 395) == "bench.wait"
    assert tr.label(t.host, 1000) == "none"
    bd = tr.breakdown(t, 0, 400)
    assert bd["idle_gaps"][0][0] == "bench.call"
    assert [g[1] for g in bd["idle_gaps"]] == approx([100e-9, 60e-9, 50e-9])


def test_kernel_time_by_name_and_self_time():
    t = _trace()
    assert tr.op_seconds(t, ["%weighted_agg."], 0, 400) == approx(20e-9)
    assert tr.op_seconds(t, ["%fusion.2"], 0, 400) == approx(70e-9)
    assert tr.op_seconds(t, ["%fusion.2"], 0, 200) == approx(30e-9)
    assert tr.op_seconds(t, ["%nothing"], 0, 400) == 0
    selfs = {e.name.split(" = ")[0]: s
             for e, s in tr.self_ns(t.devices["/device:TPU:0"])
             if e.start_ns == 0}
    # the while loop holds the fusion and the kernel: 100 - 30 - 20
    assert selfs == {"%while.1": 50}
    ops = dict(tr.breakdown(t, 0, 400)["device_ops"])
    assert ops == approx({"%fusion.2": 70e-9, "%while.1": 50e-9,
                          "%copy.3": 50e-9, "%weighted_agg.7": 20e-9})
