"""The program's own spans (``repro.obs``) in a benchmark run, on the
device trace's clock: the ``program`` summary that ``chipbench/spans.py``
adds to a run's result line.

* Recording is on for exactly the harness's ``window`` span, after
  ``obs.reset()``; the summary is made after the run.
* Host and device clocks of a trace disagree by a fraction of a
  millisecond, and the device's steps when the profiler syncs it again
  (on a v5e about once a second, by 0.2 ms). Each device ``XLA Modules``
  event (a launch of one compiled program) carries an id ``_c`` that the
  host's ``DoEnqueueProgram`` carries as ``_p`` and its
  ``CompleteCallbacks`` as ``_c``. A launch cannot start on the device
  before its enqueue began, nor be reported complete before it ended:
  each launch bounds the offset to add to device times from below and
  from above. The launches enqueued in the window are cut, in time
  order, into the fewest segments whose launches leave an offset common
  to them all, each cut placed where the brackets on its two sides are
  widest; each segment's device operations are shifted by the midpoint
  of its bracket.
* The shifted device idle time is then split over the ``qfed.*`` spans
  as ``trace_reduce`` splits it over the harness's ``bench.*`` spans:
  each piece goes to the innermost span covering it, or ``host:other``.
  A span is named by its path (``qfed.tick/qfed.dispatch/qfed.fetch``),
  so a subtree's idle is the sum over the paths under it.

``per_layer`` gives the readings a per-layer metric would report.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import trace_reduce
from chipbench.trace_reduce import DEVICE_PLANE, HOST_PLANE, Event

PREFIX = "qfed."
WINDOW = "window"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
ROOTS = ("qfed.step", "qfed.eval", "qfed.submit", "qfed.tick")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class ProgramTrace:
    """``spans``: the host's ``qfed.*`` events, each named by its path;
    ``launches[i]``: device i's program launches ``(name, start_ns,
    end_ns, id)``; ``enqueued`` / ``completed``: host start of the
    enqueue and of the completion callbacks by launch id."""
    spans: List[Event]
    launches: Dict[int, List[Tuple[str, float, float, int]]]
    enqueued: Dict[int, float]
    completed: Dict[int, float]


def span_paths(events: List[Event]) -> List[Event]:
    """Events of one host thread renamed by their nesting path."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            stack.pop()
        path = f"{stack[-1][1]}/{e.name}" if stack else e.name
        stack.append((e.end_ns, path))
        out.append(Event(path, e.start_ns, e.dur_ns))
    return out


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(path: str) -> ProgramTrace:
    """The ``qfed.*`` host spans, the device launches and their host
    enqueue and completion of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Event] = []
    launches: Dict[int, list] = {}
    enqueued: Dict[int, float] = {}
    completed: Dict[int, float] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    launches[int(m.group(1))] = [
                        (e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns),
                         _stat(e, "_c")) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine = []
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        mine.append(Event(name, float(e.start_ns),
                                          float(e.duration_ns)))
                    elif name == ENQUEUE:
                        enqueued.setdefault(_stat(e, "_p"),
                                            float(e.start_ns))
                    elif name == COMPLETE:
                        completed.setdefault(_stat(e, "_c"),
                                             float(e.start_ns))
                spans += span_paths(mine)
    return ProgramTrace(spans, launches, enqueued, completed)


def clock_segments(pt: ProgramTrace, lo: float, hi: float):
    """``[(start_ns, low, high, n)]``: from device time ``start_ns`` on,
    the bracket (ns) of the offset to add to device times, bounded by
    ``n`` launches whose enqueue began in ``[lo, hi]``; empty where no
    launch matches.

    The fewest segments, found in one pass; then each cut moves to
    where the two segments beside it leave the widest brackets in sum.
    A launch on the wrong side of a clock step can fit the bracket
    there, but it narrows it."""
    rows = []
    for evs in pt.launches.values():
        for _, s, e, key in evs:
            q, c = pt.enqueued.get(key), pt.completed.get(key)
            if q is not None and c is not None and lo <= q <= hi:
                rows.append((s, q - s, c - e))
    rows.sort()
    if not rows:
        return []
    t, low, high = (np.array(x, np.float64) for x in zip(*rows))
    cuts = [0]
    top, bot = low[0], high[0]
    for i in range(1, len(t)):
        top, bot = max(top, low[i]), min(bot, high[i])
        if top > bot:
            cuts.append(i)
            top, bot = low[i], high[i]
    cuts.append(len(t))
    for j in range(1, len(cuts) - 1):
        i0, i2 = cuts[j - 1], cuts[j + 1]
        w_left = (np.minimum.accumulate(high[i0:i2])
                  - np.maximum.accumulate(low[i0:i2]))[:-1]
        w_right = (np.minimum.accumulate(high[i0:i2][::-1])
                   - np.maximum.accumulate(low[i0:i2][::-1]))[::-1][1:]
        ok = (w_left >= 0) & (w_right >= 0)
        cuts[j] = i0 + 1 + int(np.argmax(np.where(ok, w_left + w_right,
                                                  -np.inf)))
    return [(t[i], low[i:k].max(), high[i:k].min(), k - i)
            for i, k in zip(cuts, cuts[1:])]


def _shifts(segs, t: np.ndarray) -> np.ndarray:
    """The offset (ns) to add at device times ``t``: the midpoint of the
    bracket of the segment each falls in."""
    if not segs:
        return np.zeros_like(t)
    mids = np.array([(a + b) / 2 for _, a, b, _ in segs])
    starts = np.array([s for s, _, _, _ in segs])
    return mids[np.maximum(np.searchsorted(starts, t, "right") - 1, 0)]


def _window(trace: trace_reduce.Trace, window_span: str):
    (win,) = [e for e in trace.spans if e.name == window_span]
    return win.start_ns, win.end_ns


def device_split(pt: ProgramTrace, trace: trace_reduce.Trace,
                 n_devices: int, window_span: str = "bench.window") -> dict:
    """The device side of the window on the host's clock: the offset
    brackets (``clock_segments``: each segment's start, ms into the
    window, and its launches), idle seconds by ``qfed.*`` span path,
    and device seconds and launch counts by compiled program, each
    averaged over the devices; and the ``qfed.*`` and ``bench.*`` spans
    that start in the window, counted by path."""
    lo, hi = _window(trace, window_span)
    segs = clock_segments(pt, lo, hi)
    # the innermost span is the latest to start; of two starting
    # together, the shorter (``_attribute`` keeps the first listed)
    spans = sorted((s for s in pt.spans
                    if s.end_ns > lo and s.start_ns < hi),
                   key=lambda s: (s.start_ns, s.dur_ns))
    idle: Dict[str, float] = defaultdict(float)
    modules: Dict[str, dict] = {}
    busy = 0.0
    for i in range(n_devices):
        starts, ends, _ = trace.ops[i]
        shift = _shifts(segs, starts)
        s = np.maximum(starts + shift, lo)
        e = np.minimum(ends + shift, hi)
        us, ue = trace_reduce._union(s[e > s], e[e > s])
        busy += float(np.sum(ue - us)) * 1e-9 / n_devices
        gaps = [(a, b) for a, b in zip([lo] + ue.tolist(),
                                       us.tolist() + [hi]) if b > a]
        for name, v in trace_reduce._attribute(gaps, spans).items():
            idle[name] += v / n_devices
        launches = pt.launches.get(i, [])
        t0 = np.array([x[1] for x in launches], np.float64)
        t1 = np.array([x[2] for x in launches], np.float64)
        shift = _shifts(segs, t0)
        t0, t1 = t0 + shift, np.minimum(t1 + shift, hi)
        for (name, *_), a, b in zip(launches, t0.tolist(), t1.tolist()):
            if lo <= a < hi:
                m = modules.setdefault(_SUFFIX.sub("", name),
                                       {"n": 0.0, "seconds": 0.0})
                m["n"] += 1 / n_devices
                m["seconds"] += (b - a) * 1e-9 / n_devices
    counts: Dict[str, int] = defaultdict(int)
    for s in pt.spans + trace.spans:
        if lo <= s.start_ns < hi and s.name != window_span:
            counts[s.name] += 1
    return {
        "clock_offset_us": [[a * 1e-3, b * 1e-3] for _, a, b, _ in segs],
        "clock_segments": [[(t + (a + b) / 2 - lo) * 1e-6, n]
                           for t, a, b, n in segs],
        "idle_s": (hi - lo) * 1e-9 - busy,
        "idle_by_span": dict(idle),
        "modules": modules,
        "trace_counts": dict(counts),
    }


def longest(snapshot: dict) -> dict:
    """From an ``obs.snapshot()``: for each of ``ROOTS``, its longest
    instance and the seconds of its children by name."""
    recs = snapshot["spans"]
    kids: Dict[int, list] = defaultdict(list)
    for r in recs:
        if r["parent"] is not None:
            kids[r["parent"]].append(r)
    out: Dict[str, dict] = {}
    for r in recs:
        d = r["t1"] - r["t0"]
        if r["name"] in ROOTS and r["parent"] is None and \
                d > out.get(r["name"], {}).get("seconds", -1.0):
            ch: Dict[str, float] = defaultdict(float)
            for k in kids[r["id"]]:
                ch[k["name"]] += k["t1"] - k["t0"]
            out[r["name"]] = {"seconds": d, "children": dict(ch)}
    return out


def subtree(by_path: Dict[str, float], root: str, leaf: str = "") -> float:
    """Sum over the paths at or under ``root`` (ending in ``leaf``)."""
    return sum(v for p, v in by_path.items()
               if (p == root or p.startswith(root + "/"))
               and p.endswith(leaf))


# device idle (ms) under the subtrees of ``roots``, per ``per`` span
IDLE_PER = {
    "idle_step_ms.to-target": (("qfed.step",), "qfed.step"),
    "idle_eval_ms.to-target": (("qfed.eval",), "qfed.eval"),
    "idle_submit_ms.serve": (("qfed.create", "qfed.submit"), "qfed.submit"),
    "idle_tick_ms.serve": (("qfed.tick",), "qfed.tick"),
}


def per_layer(prog: dict) -> Dict[str, float]:
    """The per-layer readings (ms) of a ``program`` summary: ``IDLE_PER``
    (traced runs only); ``queue_ms.serve``, the mean ``qfed.queue``
    interval; ``tick_wait_ms.serve``, the ``qfed.fetch`` time under
    ``qfed.tick`` per tick. Each only where the run recorded what it
    reads."""
    spans = prog["spans"]

    def n(path):
        return spans.get(path, {}).get("n", 0)

    out = {}
    idle = prog.get("idle_by_span")
    for name, (roots, per) in IDLE_PER.items():
        if idle is not None and n(per):
            out[name] = 1000.0 * sum(subtree(idle, r) for r in roots) / n(per)
    if n("qfed.queue"):
        out["queue_ms.serve"] = (1000.0 * spans["qfed.queue"]["seconds"]
                                 / n("qfed.queue"))
    if n("qfed.tick"):
        waits = {p: a["seconds"] for p, a in spans.items()}
        out["tick_wait_ms.serve"] = (1000.0 * subtree(
            waits, "qfed.tick", "/qfed.fetch") / n("qfed.tick"))
    return out


def summary(snapshot: dict, device: Optional[dict] = None) -> dict:
    """The result line's ``program``: ``spans`` (``obs``'s aggregates by
    path), ``longest``, ``counters``; from a traced run's ``device_split``
    ``idle_by_span``, ``modules``, ``clock_offset_us`` (and ``idle_s``,
    ``clock_segments``, ``trace_counts``); and ``per_layer``."""
    out = {"spans": snapshot["aggregates"], "longest": longest(snapshot),
           "counters": dict(snapshot["counters"])}
    out.update(device or {})
    out["per_layer"] = per_layer(out)
    return out
