"""A configuration, a traffic mix, a per-layer metric and a cell added
as new files and new BENCHMARK.json entries, with no edit to the
harness: the cell runs, the new reader is found and reads."""
from pathlib import Path

from chipbench import harness

EXT = Path(__file__).resolve().parent / "data" / "extension"


def test_cell_defined_only_in_test_data_runs():
    res = harness.run(EXT, "qnn121-train-tiny", 3, 0.5, False,
                      require_chip=False)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


def test_metric_defined_only_in_test_data_is_found():
    bench = harness.Bench(EXT)
    (metric,) = bench.metrics("qnn121-train-tiny", trace=True)
    reader = bench.reader(metric)
    assert reader.read(harness.Window(seconds=1.0, rounds=7, attempted=7,
                                      failed=0)) == 7.0
    # the cell's own traffic and the harness's traffic kind are found by name
    assert bench.load_json("traffic", "train-tiny")["kind"] == "train"
    assert bench.find("kinds", "train", ".py").parent.parent == harness.HERE
