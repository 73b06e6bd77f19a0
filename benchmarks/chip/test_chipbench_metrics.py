"""The yardstick's small parts: every file a cell and a metric name is
there; a reader with nothing to read returns nothing; the peaks table
refuses an unknown chip; PageRank's least work is counted from shapes."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import counts, peaks  # noqa: E402
from chipbench.harness import load_cell, load_module, reader  # noqa: E402
from reference.analytics import Window  # noqa: E402
from reference.replay import EDGE_ADD, NODE_ADD, History  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TRACE_READERS = ["device_idle_share", "pagerank_device_ms",
                 "pagerank_roofline", "motif_kernel_ms"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_file_a_cell_names_is_there(cell):
    spec = load_cell(ROOT, cell)
    assert spec["ops"] and hasattr(spec["kind"], "requests")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(reader(spec["metrics_dir"], m["name"]))


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_without_trace_returns_nothing(name):
    read = load_module(HERE / "metrics" / f"{name}.py", "test_metric_").read
    assert read({"trace": None, "least": {}, "records": []}) is None


def test_unknown_chip_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_pagerank_least_work_of_a_known_window():
    # nodes 0, 1, 2 added at t=0; edge (0, 1) at t=1, (1, 2) at t=5
    cols = {"t": np.array([0, 0, 0, 1, 5]),
            "kind": np.array([NODE_ADD] * 3 + [EDGE_ADD] * 2),
            "src": np.array([0, 1, 2, 0, 1]), "dst": np.array([-1, -1, -1, 1, 2]),
            "val": np.array([-1, -1, -1, 1, 1])}
    win = Window(History(cols), 2)  # members 0, 1, 2; (0, 1) live at lo
    peak = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    c = counts.pagerank_least(win, 2, 6, np.array([1, 2]), 10, peak)
    # one event (t=5) seen by two members, one neighbour pair, (3, 2) result
    assert c["bytes"] == 2 * 13 + 2 * 4 + 3 * 2 * 4
    assert c["flops"] == 2 * 10 * 3
    assert c["bound"] == "memory"
    assert c["least_s"] == pytest.approx(c["bytes"] / 1e9)
