"""The trace reduction against hand-computed values."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def test_busy_union_op_sums_and_gap_attribution():
    # window [0, 100] ns; device busy [0, 15] (two overlapping ops),
    # [40, 55], [95, 100] (clipped): 35 ns busy, gaps [15, 40], [55, 95]
    spans = [Event("bench.window", 0, 100), Event("bench.step", 10, 20),
             Event("bench.sync", 15, 5), Event("bench.eval", 60, 30),
             Event("not.ours", 0, 100)]
    ops = [Event("%fusion.1 = f32[8] fusion(...)", 0, 10),
           Event("%fusion.2 = f32[8] fusion(...)", 5, 10),
           Event('%custom-call.3 = f32[8] custom-call(...), metadata={op_name="eigh"}', 40, 15),
           Event("%all-gather.1 = f32[8] all-gather(...)", 95, 20),
           Event("%copy.9 = f32[8] copy(...)", 120, 5)]
    s = tr.reduce(tr.from_events([ops], spans), 1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(35e-9)
    # fusion.2 starts inside fusion.1: their overlap [5, 10] counts once
    assert s.op_s == pytest.approx({"fusion.1": 5e-9, "fusion.2": 10e-9,
                                    "custom-call.3": 15e-9,
                                    "all-gather.1": 5e-9})
    # gap [15,40]: sync 15-20, step 20-30, nothing 30-40;
    # gap [55,95]: nothing 55-60, eval 60-90, nothing 90-95
    assert s.idle_by_host == pytest.approx({"bench.sync": 5e-9,
                                            "bench.step": 10e-9,
                                            "bench.eval": 30e-9,
                                            "host:other": 20e-9})
    assert s.time_matching(r"eigh") == pytest.approx(15e-9)
    b = s.breakdown()
    assert b["device_ops"][0] == ["custom-call.3", pytest.approx(15e-9)]
    assert b["idle_gaps"][0] == ["bench.eval", pytest.approx(30e-9)]


def test_nested_operations_count_their_self_time():
    # a loop [0, 50] holding two ops [10, 20] and [30, 45]: the loop's
    # self time is 25, busy time 50
    spans = [Event("bench.window", 0, 100)]
    ops = [Event("%while.3 = (s32[]) while(...)", 0, 50),
           Event("%fusion.1 = f32[8] fusion(...)", 10, 10),
           Event("%eigh.2 = f32[8] custom-call(...)", 30, 15)]
    s = tr.reduce(tr.from_events([ops], spans), 1)
    assert s.busy_s == pytest.approx(50e-9)
    assert s.op_s == pytest.approx({"while.3": 25e-9, "fusion.1": 10e-9,
                                    "eigh.2": 15e-9})
    # the loop's time with its body, as a rule on its HLO text reads it
    assert s.time_matching(r"\(s32\[\]\) while") == pytest.approx(50e-9)
    assert s.time_matching(r"fusion|eigh") == pytest.approx(25e-9)


def test_devices_are_averaged():
    spans = [Event("bench.window", 0, 100)]
    s = tr.reduce(tr.from_events([[Event("a", 0, 50)], [Event("a", 0, 100)]],
                                 spans), 2)
    assert s.busy_s == pytest.approx(75e-9)
    assert s.op_s == pytest.approx({"a": 75e-9})
    assert s.idle_by_host == pytest.approx({"host:other": 25e-9})


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.from_events([[]], []), 1)
    with pytest.raises(ValueError):
        tr.reduce(tr.from_events([[]], [Event("bench.window", 0, 1)]), 2)


def test_recorded_trace():
    """A trace recorded on a TPU v5 lite: a jitted 512x512 matmul run
    three times, each call in a ``bench.step`` span followed by a 2 ms
    ``bench.sleep``, all in ``bench.window`` [45333691, 55508801] ns.
    Each call is three device operations: copy-start (13 ns), copy-done
    (2 ns) and the fusion (1824 or 1825 ns). The device clock sits
    0.2-0.4 ms before the host's in this trace, so the first call's
    operations (at 45014021 ns) fall before the window and the other
    two fall inside the sleeps that follow their steps."""
    s = tr.reduce(tr.load(str(DATA / "tiny.xplane.pb")), 1)
    assert s.window_s == pytest.approx(10175110e-9)
    assert s.busy_s == pytest.approx(2 * (13 + 2 + 1825) * 1e-9)
    assert s.op_s == pytest.approx({"copy-start": 26e-9, "copy-done": 4e-9,
                                    "fusion": 3650e-9})
    steps = (880110 + 840040 + 1314620) * 1e-9
    sleeps = (2507370 + 2536320 + 2059620) * 1e-9
    assert s.idle_by_host == pytest.approx(
        {"bench.step": steps, "bench.sleep": sleeps - s.busy_s,
         "host:other": s.window_s - steps - sleeps})
    assert s.texts["fusion"].startswith("%fusion = f32[]")
