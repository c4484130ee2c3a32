"""A copy of the benchmark with every cell's traffic cut to a size the
CPU runs in seconds: the same configurations, traffic kinds, metrics, limits
and reference, found through the harness's name lookup."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "fed-to-target": {"num_nodes": 20, "nodes_per_round": 4,
                      "n_per_node": 2, "n_test": 8, "target": 0.6,
                      "cap": 40},
    "serve-closed64": {"clients": 6, "slots": 4, "warmup_ticks": 6,
                       "num_nodes": 20, "nodes_per_round": 2,
                       "n_per_node": 2, "n_test": 8, "budget_lo": 2,
                       "budget_hi": 5, "agg_block": 6, "agg_average": 1},
}


def make_root(tmp: Path) -> Path:
    """A benchmark root under ``tmp`` whose traffic files are cut down."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench" / "configs", tmp / "chipbench" / "configs")
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    for name, cut in TINY.items():
        with open(ROOT / "chipbench" / "traffic" / f"{name}.json") as f:
            mix = json.load(f)
        mix.update(cut)
        with open(tmp / "chipbench" / "traffic" / f"{name}.json", "w") as f:
            json.dump(mix, f)
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp
