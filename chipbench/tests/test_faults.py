"""Each fault a cell can have, planted in the program under a run that
skips only the look for a chip, makes ``correct`` come out false."""
import jax
import pytest

from chipbench import harness
from chipbench.tests import faults, tiny

CASES = [
    ("qnn232-to-target", "unchanged"), ("qnn232-to-target", "half_batch"),
    ("qnn232-to-target", "answer_altered"),
    ("qnn232-serve", "unchanged"), ("qnn232-serve", "half_batch"),
    ("qnn232-serve", "answer_altered"),
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, workload, fault):
    root = tiny.make_root(tmp_path)
    faults.plant(fault, monkeypatch.setattr)
    try:
        res = harness.run(root, workload, 11, 0.5, False, require_chip=False)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["check"]
