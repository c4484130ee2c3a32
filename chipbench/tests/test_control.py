"""The control — the plain reference in the program's place, one
precision step below the configuration's — and every fault a cell can
have come out not correct against the cell's limits, at the cells' own
sizes. The benchmark's own runs never run this.

The control needs the chip: it is the chip's own lower-precision
matmul passes, and the CPU computes every pass in float32, so there it
is computed and not asserted."""
import jax
import pytest

from chipbench import calibrate, harness
from chipbench.tests import tiny

ONE_CHIP = ["qnn232-to-target", "qnn232-serve"]


def _fails(bench, workload, numbers):
    limits = bench.load_json("limits", workload)
    return any(numbers[k] > limits[k] for k in limits)


def _readings(workload):
    bench = harness.Bench(tiny.ROOT)
    on_chip = jax.devices()[0].platform == "tpu"
    got = dict(calibrate.readings(tiny.ROOT, workload, 5, control=True,
                                  require_chip=on_chip))
    kind = bench.load_json("traffic", bench.workload(workload)["traffic"])["kind"]
    assert set(got) == {"control"} | set(calibrate.FAULTS[kind])
    return bench, got


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_faults_are_not_correct(workload):
    bench, got = _readings(workload)
    for what, numbers in got.items():
        if what != "control":
            assert _fails(bench, workload, numbers), (what, numbers)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control is the chip's own lower-precision passes")
    bench, got = _readings(workload)
    assert _fails(bench, workload, got["control"]), got["control"]
