#!/usr/bin/env python3
"""Run one benchmark cell once with the program's spans (``repro.obs``)
recorded over the window, and print its result line with a ``program``
key before ``check``:

    python3 chipbench/spans.py --workload qnn232-serve --seed 7 \
        --seconds 30 --trace 0

With ``--trace 0`` the profiler stays off, so the run measures what
recording spans costs and which span held the longest calls
(``program.longest``); with ``--trace 1`` it is a traced run whose
``program`` also holds the device idle split over the spans. See
``chipbench/program.py``. ``chipbench/run.py`` never records the spans.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness, program, trace_reduce  # noqa: E402
from repro import obs  # noqa: E402


@contextmanager
def recording():
    """While open, the harness records the program's spans over its
    window (on after ``obs.reset()`` just before the window's span
    opens, off just after it closes), and a traced run's reduction also
    splits the device's idle over them into the yielded dict's
    ``device``. The harness is restored on the way out."""
    got = {}
    base_spans, base_reduce = harness.Spans, trace_reduce.reduce_dir

    class WindowSpans(base_spans):
        def open(self, name):
            if name == program.WINDOW:
                obs.reset()
                obs.enable()
            return super().open(name)

        def close(self, token):
            super().close(token)
            if token[0] == program.WINDOW:
                obs.disable()

    def reduce_dir(trace_dir, n_devices, window_span="bench.window"):
        path = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.load(path)
        got["device"] = program.device_split(program.load(path), trace,
                                             n_devices, window_span)
        return trace_reduce.reduce(trace, n_devices, window_span)

    harness.Spans, trace_reduce.reduce_dir = WindowSpans, reduce_dir
    try:
        yield got
    finally:
        harness.Spans, trace_reduce.reduce_dir = base_spans, base_reduce
        obs.disable()


def run(root, workload, seed, seconds, trace, **kw) -> dict:
    """``harness.run`` with the window's spans recorded; the result
    gains ``program``."""
    with recording() as got:
        result = harness.run(root, workload, seed, seconds, trace, **kw)
    snap = obs.snapshot()
    obs.reset()
    check = result.pop("check")
    result["program"] = program.summary(snap, got.get("device"))
    result["check"] = check
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once, spans recorded.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(harness.HERE.parent, args.workload, args.seed,
                     args.seconds, bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
