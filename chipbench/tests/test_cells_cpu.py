"""Every cell end to end at a tiny size on the CPU, the chip check
bypassed: set-up, window, check against the reference, result line."""
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests import tiny

ONE_CHIP = ["qnn232-to-target", "qnn232-serve"]


def _check_result(res, workload, root):
    bench = harness.Bench(root)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {m["name"] for m in
                                   bench.metrics(workload, False)}
    assert list(res)[-1] == "check"
    assert res["host_spans"]["window"]["n"] == 1
    assert res["device"]["platform"] == "cpu"
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_runs_end_to_end(tmp_path, workload):
    root = tiny.make_root(tmp_path)
    res = harness.run(root, workload, 2 ** 33 + 7, 1.0, False,
                      require_chip=False)
    _check_result(res, workload, root)


def test_run_without_a_chip_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(tiny.ROOT / "chipbench" / "run.py"),
                          "--workload", "qnn232-serve", "--seed", "1",
                          "--seconds", "1", "--trace", "0"],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "nothing was measured" in out.stderr


def test_run_in_a_directory_without_the_program_fails(tmp_path):
    import shutil
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    code = ("from pathlib import Path; from chipbench import harness; "
            "harness.run(Path('.'), 'qnn232-serve', 1, 1.0, False, "
            "require_chip=False)")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "No module named 'repro'" in out.stderr
