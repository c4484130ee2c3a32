"""The program-span reduction (``chipbench/program.py``) against hand
values, its per-layer readings on hand-built summaries, and the spans
runner (``chipbench/spans.py``) on the CPU."""
import shutil
from pathlib import Path

import pytest

from chipbench import harness, program, spans
from chipbench import trace_reduce as tr
from chipbench.tests import tiny
from chipbench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def from_events(spans_by_thread, launches_by_device, enqueued,
                completed) -> program.ProgramTrace:
    """A ``ProgramTrace`` from event lists, as ``program.load`` makes one
    from a trace file."""
    spans_ = [p for evs in spans_by_thread for p in program.span_paths(
        [e for e in evs if e.name.startswith(program.PREFIX)])]
    return program.ProgramTrace(spans_, dict(enumerate(launches_by_device)),
                                dict(enqueued), dict(completed))


def _synthetic():
    """Window [0, 1000] ns; the device clock sits 100 ns before the
    host's. Launches (device clock) and their host enqueue / completion:
    round [150, 250] (230 / 370), evaluate [500, 600] (590 / 710), round
    [800, 850] (880 / 960); a launch enqueued after the window (whose
    bounds would not hold) and one never completed bound nothing."""
    launches = [("jit__server_round(12)", 150, 250, 1),
                ("jit_evaluate(7)", 500, 600, 2),
                ("jit__server_round(12)", 800, 850, 3),
                ("jit_late(1)", 1300, 1310, 4),
                ("jit_lost(1)", 10, 20, 5)]
    enq = {1: 230, 2: 590, 3: 880, 4: 1500, 5: 0}
    done = {1: 370, 2: 710, 3: 960, 4: 1320}
    thread = [Event("qfed.step", 200, 200), Event("qfed.round_key", 200, 40),
              Event("qfed.round", 240, 140), Event("qfed.eval", 550, 250),
              Event("qfed.fetch", 600, 190), Event("bench.step", 0, 500)]
    pt = from_events([thread, []], [launches], enq, done)
    ops = [Event(f"%op.{k} = f32[] op()", s, e - s)
           for k, (_, s, e, _) in enumerate(launches[:3])]
    trace = tr.from_events([ops], [Event("bench.window", 0, 1000)])
    return pt, trace


def test_span_paths_follow_nesting():
    pt, _ = _synthetic()
    assert [s.name for s in pt.spans] == [
        "qfed.step", "qfed.step/qfed.round_key", "qfed.step/qfed.round",
        "qfed.eval", "qfed.eval/qfed.fetch"]


def test_clock_offset_bracket_and_midpoint():
    pt, _ = _synthetic()
    # lower bounds 80, 90, 80; upper bounds 120, 110, 110
    assert program.clock_segments(pt, 0, 1000) == [(150, 90, 110, 3)]
    assert program.clock_segments(pt, 2000, 3000) == []


def test_a_cut_moves_to_where_the_brackets_are_widest():
    """Offset 100 until 500, 40 after. The launch at 600 still fits the
    first bracket (it narrows it to [99, 105]); the one at 800 does
    not. Cut before 600, the brackets are [99, 120] and [39, 60]."""
    launches = [("a(1)", 100, 110, 1), ("b(1)", 300, 310, 2),
                ("c(1)", 600, 610, 3), ("d(1)", 800, 810, 4)]
    bounds = {1: (98, 130), 2: (99, 120), 3: (30, 105), 4: (39, 60)}
    enq = {k: s + bounds[k][0] for _, s, _, k in launches}
    done = {k: e + bounds[k][1] for _, _, e, k in launches}
    pt = from_events([], [launches], enq, done)
    assert program.clock_segments(pt, 0, 1000) == [(100, 99, 120, 2),
                                                   (600, 39, 60, 2)]


def test_a_clock_step_starts_a_segment():
    """The device clock sits 100 ns early until 500, 40 ns after: the
    launch at 600 leaves no offset common with those before it."""
    launches = [("a(1)", 100, 150, 1), ("b(1)", 300, 350, 2),
                ("c(1)", 600, 700, 3), ("d(1)", 800, 820, 4)]
    enq = {1: 190, 2: 395, 3: 635, 4: 830}
    done = {1: 262, 2: 455, 3: 750, 4: 870}
    pt = from_events([[Event("qfed.tick", 640, 120)]], [launches],
                             enq, done)
    assert program.clock_segments(pt, 0, 1000) == [(100, 95, 105, 2),
                                                   (600, 35, 50, 2)]
    ops = [Event(f"%op.{k} = f32[] op()", s, e - s)
           for k, (_, s, e, _) in enumerate(launches)]
    out = program.device_split(
        pt, tr.from_events([ops], [Event("bench.window", 0, 1000)]), 1)
    # shifted by 100 and 42.5: busy [200, 250], [400, 450],
    # [642.5, 742.5], [842.5, 862.5]
    assert out["idle_s"] == pytest.approx(780e-9)
    # the tick [640, 760] covers idle [640, 642.5] and [742.5, 760]
    assert out["idle_by_span"] == pytest.approx(
        {"qfed.tick": 20e-9, "host:other": 760e-9})
    assert out["clock_offset_us"] == [pytest.approx([0.095, 0.105]),
                                      pytest.approx([0.035, 0.05])]
    assert out["clock_segments"] == [[pytest.approx(200e-6), 2],
                                     [pytest.approx(642.5e-6), 2]]


def test_idle_split_over_nested_spans_after_the_shift():
    pt, trace = _synthetic()
    out = program.device_split(pt, trace, 1)
    assert out["clock_offset_us"] == [pytest.approx([0.09, 0.11])]
    assert out["clock_segments"] == [[pytest.approx(250e-6), 3]]
    # on the host's clock (+100 ns) the device is busy [250, 350],
    # [600, 700], [900, 950]; idle [0, 250], [350, 600], [700, 900],
    # [950, 1000]
    assert out["idle_s"] == pytest.approx(750e-9)
    assert out["idle_by_span"] == pytest.approx({
        "host:other": 500e-9, "qfed.step/qfed.round_key": 40e-9,
        "qfed.step/qfed.round": 40e-9, "qfed.step": 20e-9,
        "qfed.eval": 60e-9, "qfed.eval/qfed.fetch": 90e-9})
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert program.subtree(out["idle_by_span"], "qfed.step") == \
        pytest.approx(100e-9)


def test_module_time_and_launch_counts():
    pt, trace = _synthetic()
    mods = program.device_split(pt, trace, 1)["modules"]
    # jit_late starts after the window; jit_lost starts in it (at 110)
    assert mods == {
        "jit__server_round": {"n": 2, "seconds": pytest.approx(150e-9)},
        "jit_evaluate": {"n": 1, "seconds": pytest.approx(100e-9)},
        "jit_lost": {"n": 1, "seconds": pytest.approx(10e-9)}}


def test_summary_longest_and_counters():
    agg = {"qfed.tick": {"n": 2, "seconds": 1.5, "self_s": 0.7,
                         "longest_s": 1.0},
           "qfed.tick/qfed.dispatch/qfed.fetch": {
               "n": 1, "seconds": 0.3, "self_s": 0.3, "longest_s": 0.3}}
    snap = {"counters": {"qfed.park": 2}, "aggregates": agg, "spans": [
        {"name": "qfed.tick", "id": 1, "parent": None, "t0": 0.0, "t1": 1.0},
        {"name": "qfed.dispatch", "id": 2, "parent": 1, "t0": 0.1, "t1": 0.9},
        {"name": "qfed.fetch", "id": 3, "parent": 2, "t0": 0.5, "t1": 0.8},
        {"name": "qfed.tick", "id": 4, "parent": None, "t0": 2.0, "t1": 2.5},
        {"name": "qfed.queue", "id": 5, "parent": None, "t0": 0.0, "t1": 3.0,
         "rid": "j1"}]}
    out = program.summary(snap)
    assert out["counters"] == {"qfed.park": 2}
    assert out["spans"] is agg
    assert out["longest"] == {"qfed.tick": {
        "seconds": 1.0, "children": {"qfed.dispatch": pytest.approx(0.8)}}}
    # untraced: no idle split, so only the host-clock readings
    assert "idle_by_span" not in out
    assert out["per_layer"] == {"tick_wait_ms.serve": pytest.approx(150.0)}


READINGS = [
    ("idle_step_ms.to-target",
     {"spans": {"qfed.step": {"n": 2}},
      "idle_by_span": {"qfed.step": 0.001, "qfed.step/qfed.round": 0.002,
                       "qfed.eval": 0.5, "host:other": 1.0}}, 1.5),
    ("idle_eval_ms.to-target",
     {"spans": {"qfed.eval": {"n": 4}},
      "idle_by_span": {"qfed.eval": 0.004, "qfed.eval/qfed.fetch": 0.004,
                       "qfed.step": 1.0}}, 2.0),
    ("idle_submit_ms.serve",
     {"spans": {"qfed.submit": {"n": 2}},
      "idle_by_span": {"qfed.create": 0.002, "qfed.submit": 0.001,
                       "qfed.submit/qfed.create": 0.001,
                       "qfed.tick": 1.0}}, 2.0),
    ("queue_ms.serve",
     {"spans": {"qfed.queue": {"n": 4, "seconds": 3.2}},
      "idle_by_span": {}}, 800.0),
    ("idle_tick_ms.serve",
     {"spans": {"qfed.tick": {"n": 2, "seconds": 0.1}},
      "idle_by_span": {"qfed.tick": 0.002,
                       "qfed.tick/qfed.dispatch/qfed.fetch": 0.006,
                       "qfed.submit": 1.0}}, 4.0),
    ("tick_wait_ms.serve",
     {"spans": {"qfed.tick": {"n": 2, "seconds": 0.1},
                "qfed.tick/qfed.dispatch/qfed.fetch": {"n": 4,
                                                       "seconds": 0.05},
                "qfed.eval/qfed.fetch": {"n": 1, "seconds": 1.0}},
      "idle_by_span": {}}, 25.0),
]


@pytest.mark.parametrize("name,prog,want", READINGS,
                         ids=[r[0] for r in READINGS])
def test_per_layer_reading_on_a_hand_built_summary(name, prog, want):
    assert program.per_layer(prog)[name] == pytest.approx(want)
    # a run without the spans it reads reads nothing
    assert name not in program.per_layer({"spans": {}, "idle_by_span": {}})
    assert name not in program.per_layer({"spans": {}})


def test_recorded_trace_reduces_as_before_with_the_program_beside(tmp_path):
    """While the spans runner records, ``reduce_dir`` gives what it
    gives without it, and the program's part finds the three launches of
    ``tiny.xplane.pb`` (see ``test_trace_reduce.test_recorded_trace``);
    afterwards the harness is as it was."""
    src = DATA / "tiny.xplane.pb"
    ref = tr.reduce(tr.load(str(src)), 1)
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(src, d / "tiny.xplane.pb")
    base = tr.reduce_dir, harness.Spans
    with spans.recording() as got:
        out = tr.reduce_dir(tmp_path, n_devices=1,
                            window_span="bench.window")
    assert (tr.reduce_dir, harness.Spans) == base
    for field in ("busy_s", "window_s", "op_s", "idle_by_host", "texts",
                  "names"):
        assert getattr(out, field) == getattr(ref, field), field
    assert out.breakdown() == ref.breakdown()
    prog = got["device"]
    # lower bounds: enqueue - device start of run_id 4, 5, 6; upper
    # bounds: completion - device end
    lo = max(45551845 - 45014018, 48879505 - 48329018, 52529795 - 51923416)
    hi = min(46066825 - 45015863, 49474225 - 48330865, 53394265 - 51925263)
    assert (lo, hi) == (606379, 1050962)
    assert prog["clock_offset_us"] == [pytest.approx([lo / 1e3, hi / 1e3])]
    assert [n for _, n in prog["clock_segments"]] == [3]
    assert prog["modules"] == {"jit__lambda": {
        "n": 3, "seconds": pytest.approx((1845 + 1847 + 1847) * 1e-9)}}
    # no qfed.* spans in this trace: all idle is host:other
    assert prog["idle_by_span"] == pytest.approx(
        {"host:other": prog["idle_s"]})
    assert prog["trace_counts"] == {"bench.step": 3, "bench.sleep": 3}


@pytest.mark.parametrize("workload", ["qnn232-to-target", "qnn232-serve"])
def test_spans_runner_reports_the_program(tmp_path, workload):
    from repro import obs
    root = tiny.make_root(tmp_path)
    base = tr.reduce_dir, harness.Spans
    res = spans.run(root, workload, 2 ** 33 + 5, 1.0, False,
                    require_chip=False)
    assert (tr.reduce_dir, harness.Spans) == base
    assert res["correct"], res["check"]
    assert list(res)[-2:] == ["program", "check"]
    got = res["program"]["spans"]
    if workload == "qnn232-serve":
        for path in ("qfed.tick", "qfed.tick/qfed.admit",
                     "qfed.tick/qfed.seat", "qfed.tick/qfed.retire",
                     "qfed.tick/qfed.dispatch/qfed.fetch",
                     "qfed.submit", "qfed.create", "qfed.queue"):
            assert got[path]["n"] > 0, path
        # the harness's ``tick`` spans also count the drain after the
        # window, where the program's spans are off
        assert got["qfed.tick"]["n"] <= res["host_spans"]["tick"]["n"]
        assert "qfed.tick" in res["program"]["longest"]
        assert sorted(res["program"]["per_layer"]) == [
            "queue_ms.serve", "tick_wait_ms.serve"]
    else:
        for path in ("qfed.step", "qfed.step/qfed.round_key",
                     "qfed.step/qfed.round", "qfed.eval/qfed.fetch"):
            assert got[path]["n"] > 0, path
        # the harness's ``step`` span also runs from each episode's last
        # round to its end (no step in it): one more per episode, the
        # unfinished last one included
        extra = res["host_spans"]["step"]["n"] - got["qfed.step"]["n"]
        assert extra - res["attempted"] in (0, 1)
        assert res["program"]["per_layer"] == {}
    assert not obs.ON
    # a run of the harness itself records nothing
    obs.reset()
    harness.run(root, workload, 3, 0.5, False, require_chip=False)
    assert obs.snapshot()["spans"] == []
