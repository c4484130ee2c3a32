#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers on
many seeds, the control's and each planted fault's.

    python3 chipbench/calibrate.py --workload qnn232-to-target \
        --program-seeds 1,2,3 --control-seeds 4,5,6

For every program seed it builds the cell as a run does, runs the
window's first steps (and, for a serving cell, a short window at the
cell's own load) and prints the compared numbers. For every control
seed it puts the plain reference in the program's place, computed one
precision step below what the configuration states (``CONTROL``), and
each fault the cell can have (``FAULTS``), and prints their numbers
against the full-precision reference. One JSON object per line on
standard output. Like ``run.py`` it needs the chip; the benchmark's own
runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import ExitStack  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

# one step below each role of the configuration's precision, on the
# chip's own matmul passes: "highest" -> three bfloat16 passes, "high"
# -> one (the CPU computes every pass in float32, so there the control
# is the reference itself)
CONTROL = {"unitary": "high", "ensemble": "default"}

# the faults each traffic kind can have, as arguments of its module's
# check: the program's place is taken by the reference with the fault
FAULTS = {
    "train": {"half_batch": {"keep_half": True}},
    "to_target": {"half_batch": {"keep_half": True},
                  "stale_eval": {"stale_eval": True}},
    "serve_closed": {"unchanged": {"unchanged": True},
                     "half_batch": {"keep_half": True},
                     "short_answer": {"short": True}},
}
SERVE_WINDOW_S = 3.0


def _fault_args(kind_args: dict, traffic: dict) -> dict:
    """Translate a fault's flags to the reference's arguments."""
    out = dict(kind_args)
    n_p = traffic["nodes_per_round"]
    if out.pop("keep_half", False):
        out.update(keep_nodes=n_p // 2, renorm=True)
    return out


def readings(root: Path, workload: str, seed: int, *, control: bool,
             require_chip: bool = True):
    """Yield (what, numbers) for one seed."""
    import jax
    bench = harness.Bench(root)
    cell = bench.workload(workload)
    config = bench.config(cell["config"])
    traffic = bench.load_json("traffic", cell["traffic"])
    kind = bench.module("kinds", traffic["kind"])
    chips = cell["chips"]
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise harness.NoChip(f"{workload} needs {chips} TPU chips here")
    harness.enable_compile_cache()
    src = bench.root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    with ExitStack() as stack:
        ctx = harness.Context(workload, config, traffic, seed,
                              devices[:chips], stack, bench)
        st = kind.setup(ctx)
        if traffic["kind"] == "serve_closed":
            kind.window(st, SERVE_WINDOW_S, harness.Spans())
        if not control:
            yield "program", kind.check(st)
            return
        if traffic["kind"] == "serve_closed":
            kind.check(st)   # read the served states before the server goes
        yield "control", kind.check(st, precision=CONTROL)
        for name, flags in FAULTS[traffic["kind"]].items():
            yield name, kind.check(st, **_fault_args(flags, traffic))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    plan = ([(int(s), False) for s in args.program_seeds.split(",") if s]
            + [(int(s), True) for s in args.control_seeds.split(",") if s])
    try:
        for seed, control in plan:
            t0 = time.perf_counter()
            for what, numbers in readings(root, args.workload, seed,
                                          control=control):
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "what": what, "numbers": numbers,
                                  "seconds": time.perf_counter() - t0}),
                      flush=True)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
