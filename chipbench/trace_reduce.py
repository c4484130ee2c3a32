"""Reduce a ``jax.profiler`` trace to the device's busy time, the time
of each device operation, and the idle gaps by what the host was doing.

* Device operations are the events on the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane. An event's name is its HLO instruction
  (``%fusion.12 = f32[...] fusion(...), ...``); an operation is named by
  the instruction name alone (``fusion.12``) and its full text is kept
  once. Busy time is the union of the operations' intervals inside the
  traced window (a loop and the operations nested in it count once);
  idle time is the rest of the window. An operation's time is its self
  time: its interval less the operations nested in it, so a ``while``
  loop is not counted again over its body.
* The window is the host span ``bench.window`` that the harness puts
  around its traced window. Host spans are the harness's ``bench.<name>``
  annotations on the host plane.
* Each idle stretch of a device is split over the host spans covering
  it: a piece goes to the innermost span (the latest to start) covering
  it, and to ``host:other`` where none does.

Times are seconds, averaged over the devices the cell uses.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10
OTHER = "host:other"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads. ``ops[i]``: device i's
    operations as arrays ``(start_ns, end_ns, name_id)``; ``names``: the
    operation names by id; ``texts``: each operation's full HLO text;
    ``spans``: the host's ``bench.*`` spans."""
    ops: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]]
    names: List[str]
    texts: Dict[str, str]
    spans: List[Event]


@dataclasses.dataclass
class TraceSummary:
    """``busy_s``/``window_s``: mean busy seconds of a device and the
    window's length. ``op_s``: mean device self seconds by operation.
    ``texts``: each operation's HLO text. ``idle_by_host``: mean idle
    seconds by the host span that covered them. ``ops``: each device's
    operations clipped to the window, ``(start_ns, end_ns, name_id)``
    with ``names`` by id."""
    busy_s: float
    window_s: float
    op_s: Dict[str, float]
    texts: Dict[str, str]
    idle_by_host: Dict[str, float]
    names: List[str] = dataclasses.field(default_factory=list)
    ops: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=list)

    def time_matching(self, pattern: str) -> float:
        """Mean device seconds in which an operation whose name or HLO
        text matches ``pattern`` (a regular expression) was running, the
        operations nested in it included (a ``while`` loop's body)."""
        rx = re.compile(pattern)
        ids = [i for i, n in enumerate(self.names)
               if rx.search(n) or rx.search(self.texts.get(n, ""))]
        total = 0.0
        for s, e, k in self.ops:
            hit = np.isin(k, ids)
            us, ue = _union(s[hit], e[hit])
            total += float(np.sum(ue - us))
        return total * 1e-9 / max(1, len(self.ops))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def short_name(text: str) -> str:
    """``%while.93 = (s32[], ...) while(...)`` -> ``while.93``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


class _Names:
    """Operation ids by name, and each operation's first full text."""

    def __init__(self):
        self.names: List[str] = []
        self.texts: Dict[str, str] = {}
        self._by_text: Dict[str, int] = {}
        self._by_short: Dict[str, int] = {}

    def id(self, text: str) -> int:
        i = self._by_text.get(text)
        if i is None:
            short = short_name(text)
            i = self._by_short.get(short)
            if i is None:
                i = self._by_short[short] = len(self.names)
                self.names.append(short)
                self.texts[short] = text
            self._by_text[text] = i
        return i


def from_events(ops_by_device: Sequence[Sequence[Event]],
                spans: Sequence[Event]) -> Trace:
    """A ``Trace`` from event lists (device i's operations, host spans)."""
    names = _Names()
    ops = {}
    for i, evs in enumerate(ops_by_device):
        ops[i] = (np.array([e.start_ns for e in evs], np.float64),
                  np.array([e.end_ns for e in evs], np.float64),
                  np.array([names.id(e.name) for e in evs], np.int64))
    return Trace(ops, names.names, names.texts,
                 [s for s in spans if s.name.startswith(SPAN_PREFIX)])


def load(path: str) -> Trace:
    """The device operations and host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = _Names()
    ops = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                starts, ends, nid = [], [], []
                for e in line.events:
                    s = float(e.start_ns)
                    starts.append(s)
                    ends.append(s + float(e.duration_ns))
                    nid.append(names.id(e.name))
                ops[int(m.group(1))] = (np.array(starts, np.float64),
                                        np.array(ends, np.float64),
                                        np.array(nid, np.int64))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Event(name, float(e.start_ns),
                                           float(e.duration_ns)))
    return Trace(ops, names.names, names.texts, spans)


def _union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint (start, end) arrays covering the given intervals."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def _self_times(starts, ends, nid, n_names) -> np.ndarray:
    """Self nanoseconds by name id of possibly nested intervals: each
    interval less the parts of it that later-starting ones cover."""
    order = np.lexsort((-ends, starts))
    s, e = starts[order].tolist(), ends[order].tolist()
    child = [0.0] * len(s)
    stack: List[int] = []
    for i in range(len(s)):
        while stack and e[stack[-1]] <= s[i]:
            stack.pop()
        if stack:
            top = stack[-1]
            child[top] += min(e[i], e[top]) - s[i]
        stack.append(i)
    own = np.maximum(0.0, np.array(e) - np.array(s) - np.array(child))
    out = np.zeros(n_names)
    np.add.at(out, nid[order], own)
    return out


def _segments(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """The host timeline cut at every span boundary, each piece named
    by the innermost span (the latest to start) covering it."""
    pts = sorted({b for sp in spans for b in (sp.start_ns, sp.end_ns)})
    order = sorted(spans, key=lambda sp: sp.start_ns)
    heap: list = []
    segs, i = [], 0
    for a, b in zip(pts, pts[1:]):
        while i < len(order) and order[i].start_ns <= a:
            heapq.heappush(heap, (-order[i].start_ns, i, order[i]))
            i += 1
        while heap and heap[0][2].end_ns <= a:
            heapq.heappop(heap)
        segs.append((a, b, heap[0][2].name if heap else OTHER))
    return segs


def _attribute(gaps, spans: List[Event]) -> Dict[str, float]:
    """Split each gap over the host spans covering it, innermost first."""
    out: Dict[str, float] = defaultdict(float)
    segs = _segments(spans)
    j = 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            ov = min(b, g1) - max(a, g0)
            if ov > 0:
                out[name] += ov * 1e-9
                covered += ov
            k += 1
        if g1 - g0 - covered > 0:
            out[OTHER] += (g1 - g0 - covered) * 1e-9
    return out


def reduce(trace: Trace, n_devices: int,
           window_span: str = "bench.window") -> TraceSummary:
    win = [e for e in trace.spans if e.name == window_span]
    if len(win) != 1:
        raise ValueError(f"trace holds {len(win)} {window_span!r} spans, "
                         "want exactly 1")
    lo, hi = win[0].start_ns, win[0].end_ns
    inner = [e for e in trace.spans if e.name != window_span
             and e.end_ns > lo and e.start_ns < hi]
    if not all(i in trace.ops for i in range(n_devices)):
        raise ValueError(f"trace holds device planes {sorted(trace.ops)}, "
                         f"want TPU:0..{n_devices - 1}")
    busy_s = 0.0
    op_ns = np.zeros(len(trace.names))
    idle: Dict[str, float] = defaultdict(float)
    clipped = []
    for i in range(n_devices):
        starts, ends, nid = trace.ops[i]
        s, e = np.maximum(starts, lo), np.minimum(ends, hi)
        keep = e > s
        s, e, k = s[keep], e[keep], nid[keep]
        clipped.append((s, e, k))
        op_ns += _self_times(s, e, k, len(trace.names))
        us, ue = _union(s, e)
        busy_s += float(np.sum(ue - us)) * 1e-9 / n_devices
        gaps = [(a, b) for a, b in zip([lo] + ue.tolist(), us.tolist() + [hi])
                if b > a]
        for name, v in _attribute(gaps, inner).items():
            idle[name] += v / n_devices
    op_s = {trace.names[j]: float(v) * 1e-9 / n_devices
            for j, v in enumerate(op_ns) if v > 0}
    return TraceSummary(busy_s=busy_s, window_s=(hi - lo) * 1e-9, op_s=op_s,
                        texts=trace.texts, idle_by_host=dict(idle),
                        names=trace.names, ops=clipped)


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def reduce_dir(trace_dir, n_devices: int,
               window_span: str = "bench.window") -> TraceSummary:
    return reduce(load(find_xplane(trace_dir)), n_devices, window_span)
