"""The ``flip_change_share`` reader: the share of pair events that the
edge-operand exports kept as existence changes, and nothing where the
program has no such counters or built no edge operand."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench.harness import load_module  # noqa: E402


@pytest.mark.parametrize("stats,want", [
    ({"traces": 8, "operand_uploads": 4}, None),  # a program without them
    ({"flip_events": 0, "flip_changes": 0}, None),  # no edge operand built
    ({"flip_events": 481_118, "flip_changes": 34_144},
     100 * 34_144 / 481_118),
])
def test_flip_change_share_reads_the_export_counters(stats, want):
    read = load_module(HERE / "metrics" / "flip_change_share.py",
                       "test_metric_").read
    got = read({"stats_before": stats, "stats_after": stats})
    assert got == (None if want is None else pytest.approx(want))
