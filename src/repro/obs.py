"""Spans and counters inside the federation program.

Off by default. While off, ``span`` returns one shared no-op context
manager after a single flag check: no allocation, no clock read. An
operator (or a profiling run) turns recording on for a stretch of
time::

    from repro import obs
    obs.reset()
    obs.enable()
    ...                      # serve, train
    obs.disable()
    snap = obs.snapshot()    # spans, aggregates by path, counters

While on, each ``span(name)`` records ``(name, id, parent id, start,
end)`` in memory (``time.perf_counter`` seconds; the parent is the
innermost span open on the same thread) and opens a
``jax.profiler.TraceAnnotation`` of the same name, so that a running
profiler trace shows the span on the host plane beside the device
operations it enqueued. ``interval`` records an interval timed by the
caller (a serving job's wait in the queue, which crosses ticks) with a
request id. Records accumulate until ``reset``.

Counters (``count``) are always on. While recording, every XLA compile
is counted under the innermost open span, as ``qfed.compile[<span>]``
(``qfed.compile[]`` outside any span).

Every name starts with ``qfed.``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional

import jax

ON = False
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    name: str
    id: int
    parent: Optional[int]
    t0: float
    t1: float
    rid: Optional[str] = None


_records: List[Record] = []
_counts: Dict[str, int] = defaultdict(int)
_ids = itertools.count(1)
_local = threading.local()
_count_lock = threading.Lock()
_listening = False

clock = time.perf_counter


class _Off:
    """The span handed out while recording is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Span:
    __slots__ = ("name", "id", "parent", "t0", "ann", "stack")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.stack = _stack()
        self.parent = self.stack[-1].id if self.stack else None
        self.id = next(_ids)
        self.stack.append(self)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        t1 = clock()
        self.ann.__exit__(*exc)
        self.stack.pop()
        _records.append(Record(self.name, self.id, self.parent, self.t0, t1))
        return False


def span(name: str):
    """A context manager timing ``name`` while recording is on."""
    if not ON:
        return _OFF
    return _Span(name)


def interval(name: str, t0: float, t1: float, rid: str) -> None:
    """Record an interval the caller timed (``clock()`` seconds) for
    request ``rid``; nothing while recording is off."""
    if ON:
        _records.append(Record(name, next(_ids), None, t0, t1, rid))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (always on)."""
    with _count_lock:
        _counts[name] += n


def _on_event(event: str, duration_secs: float, **_) -> None:
    if ON and event == COMPILE_EVENT:
        st = _stack()
        count(f"qfed.compile[{st[-1].name if st else ''}]")


def enable() -> None:
    """Start recording spans and intervals (and counting compiles)."""
    global ON, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    ON = True


def disable() -> None:
    global ON
    ON = False


def reset() -> None:
    """Drop every record and counter."""
    _records.clear()
    _counts.clear()


def snapshot() -> dict:
    """``spans``: every record as a dict (``name, id, parent, t0, t1``,
    and ``rid`` for intervals); ``aggregates``: by path (the names from
    the outermost span in, ``qfed.tick/qfed.dispatch/qfed.fetch``),
    ``n``, ``seconds``, ``self_s`` (less the time of child spans) and
    ``longest_s``; ``counters``: by name."""
    records = list(_records)
    by_id = {r.id: r for r in records}
    paths: Dict[int, str] = {}

    def path(r: Record) -> str:
        p = paths.get(r.id)
        if p is None:
            up = by_id.get(r.parent)
            p = paths[r.id] = f"{path(up)}/{r.name}" if up else r.name
        return p

    child_s: Dict[int, float] = defaultdict(float)
    for r in records:
        if r.parent is not None:
            child_s[r.parent] += r.t1 - r.t0
    agg: Dict[str, dict] = {}
    for r in records:
        d = r.t1 - r.t0
        a = agg.setdefault(path(r), {"n": 0, "seconds": 0.0, "self_s": 0.0,
                                     "longest_s": 0.0})
        a["n"] += 1
        a["seconds"] += d
        a["self_s"] += d - child_s.get(r.id, 0.0)
        a["longest_s"] = max(a["longest_s"], d)
    spans = [{k: v for k, v in r._asdict().items()
              if k != "rid" or v is not None} for r in records]
    return {"spans": spans, "aggregates": agg, "counters": dict(_counts)}
