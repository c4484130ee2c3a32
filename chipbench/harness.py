"""Run one cell of ``BENCHMARK.json`` once and print its result line.

The harness is driven by data. A cell names a configuration (a JSON file
of sizes, whose ``reference`` key names its plain reference under
``reference/``) and a traffic mix (``traffic/<name>.json``, whose
``kind`` names its module under ``kinds/``). Each metric is a reader
``metrics/<name>.py``. The compared numbers' limits are
``limits/<cell>.json``. Every one of these is looked up by name, first
under the benchmark's own ``paths`` and then beside this file, so a new
configuration, mix, metric or cell is new files plus new entries in
``BENCHMARK.json``.

A traffic kind's module has three functions:

* ``setup(ctx)``: make the inputs from the seed, build the system under
  test, drive it through its first steps with the window's own calls
  (this compiles every program the window runs), and return its state;
* ``window(state, seconds, spans)``: run the cell's traffic for
  ``seconds`` and return a ``Window``;
* ``check(state)``: after the window, free the program's device state,
  run the plain reference and return ``{name: value}`` of the compared
  numbers.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from contextlib import ExitStack, contextmanager
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
# a traced run traces a window of at most this many seconds: the trace
# of a few seconds already holds millions of device operations
TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Window:
    """What one measured window did; metric readers read from it.

    ``seconds``: wall time of the window, from its first call to the
    moment its last work was ready on the device. ``rounds``: federation
    rounds completed (tenant rounds summed, for serving). ``spans``: host
    durations (seconds) of the harness's calls into each layer, by name.
    ``samples``: other per-event readings (episode times, job latencies).
    ``trace``: the reduced device trace of a ``--trace 1`` run.
    ``ops_per_round``: ``work.py``'s count.
    """
    seconds: float
    rounds: int
    attempted: int
    failed: int
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    ops_per_round: Optional[float] = None
    setup_s: Optional[float] = None
    trace: Any = None
    peaks: Optional[dict] = None
    chips: int = 1


class Spans:
    """Host-clock spans around the harness's calls into the program.
    In a traced run each span is also a profiler annotation named
    ``bench.<name>``, so the trace reduction can say what the host was
    doing while the device idled."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.durations: Dict[str, List[float]] = {}

    @contextmanager
    def __call__(self, name: str):
        token = self.open(name)
        try:
            yield
        finally:
            self.close(token)

    def open(self, name: str):
        """Start a span that ``close`` ends, for a call the harness cannot
        wrap (a step inside ``FederationSession.run``, between two of its
        callbacks)."""
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        return name, time.perf_counter(), ann

    def close(self, token) -> None:
        name, t0, ann = token
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)
        if ann is not None:
            ann.__exit__(None, None, None)


@dataclasses.dataclass
class Context:
    """What a traffic kind gets: the cell's configuration and traffic, the
    seed, the devices it may use and a stack for clean-ups that must
    wait until the run ends."""
    workload: str
    config: dict
    traffic: dict
    seed: int
    devices: list
    stack: ExitStack
    bench: "Bench"


def _load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root).resolve()
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)
        dirs = [self.root / p for p in self.spec["paths"]] + [HERE]
        self.dirs: List[Path] = []
        for d in dirs:
            if d.resolve() not in self.dirs:
                self.dirs.append(d.resolve())
        self._modules: Dict[Path, Any] = {}

    def find(self, sub: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / sub / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(
            f"no {sub}/{name}{suffix} under {[str(d) for d in self.dirs]}")

    def module(self, sub: str, name: str):
        path = self.find(sub, name, ".py")
        if path not in self._modules:
            self._modules[path] = _load_module(path, sub)
        return self._modules[path]

    def load_json(self, sub: str, name: str) -> dict:
        with open(self.find(sub, name, ".json")) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def metrics(self, workload: str, trace: bool) -> List[dict]:
        """The metrics a cell reports: its end-to-end metrics without a
        trace, its per-layer metrics with one."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload] if
                                     m["moves"] in moved else [])]

    def reader(self, metric: dict):
        mod = self.module("metrics", metric["name"])
        for key, attr in (("unit", "UNIT"), ("layer", "LAYER"),
                          ("moves", "MOVES")):
            if key in metric and getattr(mod, attr, metric[key]) != metric[key]:
                raise ValueError(
                    f"metric {metric['name']}: reader says {attr}="
                    f"{getattr(mod, attr)!r}, BENCHMARK.json says "
                    f"{metric[key]!r}")
        return mod


def peaks_for(kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {path.name} "
                       f"(have {sorted(table)}); add its peaks with their "
                       "source")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or ``$JAX_COMPILATION_CACHE_DIR``), caching every program
    however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(HERE / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _Compiles:
    """Counts XLA compilations (persistent-cache reads included) while
    ``on``; the window should see none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on = False
        self.n = 0

    def __call__(self, event, duration_secs, **_):
        if self.on and event == self.EVENT:
            self.n += 1


def _fmt(v: float) -> str:
    return repr(float(v))


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, require_chip: bool = True, t_start: Optional[float] = None
        ) -> dict:
    """One run of one cell; returns the result line as a dict (its
    ``check`` key last)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = Bench(root)
    cell = bench.workload(workload)
    config = bench.config(cell["config"])
    traffic = bench.load_json("traffic", cell["traffic"])
    limits = bench.load_json("limits", workload)
    chips = int(cell["chips"])

    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU, JAX reports platform "
                         f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"{workload} needs {chips} chips, JAX reports "
                         f"{len(devices)}")
    peaks = peaks_for(devices[0].device_kind) if require_chip else None
    enable_compile_cache()
    src = bench.root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    metrics = bench.metrics(workload, trace)
    readers = {m["name"]: bench.reader(m) for m in metrics}
    kind = bench.module("kinds", traffic["kind"])
    spans = Spans(annotate=trace)
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    with ExitStack() as stack:
        ctx = Context(workload, config, traffic, int(seed),
                      devices[:chips], stack, bench)
        state = kind.setup(ctx)
        setup_s = time.perf_counter() - t_start
        trace_dir = HERE / ".trace" / workload
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        compiles.on = True
        try:
            win = kind.window(
                state, min(float(seconds), TRACE_SECONDS) if trace
                else float(seconds), spans)
        finally:
            compiles.on = False
            if trace:
                jax.profiler.stop_trace()
        win.setup_s = setup_s
        win.peaks = peaks
        win.chips = chips
        win.spans = spans.durations
        if trace:
            from chipbench import trace_reduce
            win.trace = trace_reduce.reduce_dir(
                trace_dir, n_devices=chips, window_span="bench.window")
            shutil.rmtree(trace_dir, ignore_errors=True)
        peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:chips])
        numbers = kind.check(state)
        del state

    values = {}
    for m in metrics:
        v = readers[m["name"]].read(win)
        if v is not None:
            if not math.isfinite(v):
                raise ValueError(f"metric {m['name']} read {v}")
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    missing = set(limits) ^ set(numbers)
    if missing:
        raise KeyError(f"compared numbers and limits/{workload}.json "
                       f"differ in {sorted(missing)}")
    check = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
             for k in sorted(numbers)}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in check.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_mem)}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed), "metrics": values,
              "device": device}
    if trace:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        result["breakdown"] = win.trace.breakdown()
    result["compiles_in_window"] = compiles.n
    # where a run reads far off, its longest call into a layer says
    # which layer stalled
    result["host_spans"] = {k: {"n": len(v), "seconds": float(sum(v)),
                                "longest_s": float(max(v))}
                            for k, v in spans.durations.items()}
    result["check"] = check
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = HERE.parent
    try:
        result = run(root, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 1
    for name, c in result["check"].items():
        print(f"check {name} {_fmt(c['value'])} limit {_fmt(c['limit'])}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
