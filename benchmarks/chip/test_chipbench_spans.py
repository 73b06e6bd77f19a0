"""The reduction of the store's own spans: known self times of a nest,
no subtraction across threads, coverage of the requests, idle gaps
labelled down to a span, the request reduction untouched by program
spans, and on a real CPU trace of the harness's reads the layers' self
times adding up to the requests' time."""
import copy
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spans, tracing  # noqa: E402
from test_chipbench_trace import small_trace  # noqa: E402

MS = 1e6  # ns
MAIN, OTHER = ("/host:CPU", 0), ("/host:CPU", 1)


def nest():
    """One read, 0-40 ms on the main thread: tgi.get_snapshot 2-38 holds
    kvstore.multiget 4-20, which holds serialize.decode 6-10 and 12-14;
    snapshot.overlay_fold 22-30 follows it.  tgi.maintenance runs 0-100
    on another thread."""
    return {
        "requests": [(0.0, 40 * MS, "req:snapshot:0", MAIN)],
        "program": sorted([
            (2 * MS, 38 * MS, "tgi.get_snapshot", MAIN),
            (4 * MS, 20 * MS, "kvstore.multiget", MAIN),
            (6 * MS, 10 * MS, "serialize.decode", MAIN),
            (12 * MS, 14 * MS, "serialize.decode", MAIN),
            (22 * MS, 30 * MS, "snapshot.overlay_fold", MAIN),
            (0.0, 100 * MS, "tgi.maintenance", OTHER),
        ]),
    }


def test_self_times_of_a_three_deep_nest():
    got = spans.self_times(nest()["program"], (0.0, 40 * MS))
    assert got["serialize.decode"] == pytest.approx(0.006)
    assert got["kvstore.multiget"] == pytest.approx(0.016 - 0.006)
    assert got["snapshot.overlay_fold"] == pytest.approx(0.008)
    assert got["tgi.get_snapshot"] == pytest.approx(0.036 - 0.016 - 0.008)


def test_span_on_another_thread_is_not_subtracted():
    got = spans.self_times(nest()["program"], (0.0, 100 * MS))
    assert got["tgi.maintenance"] == pytest.approx(0.100)
    assert got["tgi.get_snapshot"] == pytest.approx(0.012)


def test_window_clips_self_time():
    got = spans.self_times(nest()["program"], (0.0, 8 * MS))
    assert got["tgi.get_snapshot"] == pytest.approx(0.002)
    assert got["kvstore.multiget"] == pytest.approx(0.002)
    assert got["serialize.decode"] == pytest.approx(0.002)
    assert got["tgi.maintenance"] == pytest.approx(0.008)


def test_reduce_counts_requests_and_coverage():
    red = spans.reduce(nest(), (0.0, 100 * MS))
    assert red["requests"] == {"snapshot": 1}
    # 36 of the read's 40 ms lie in tgi.get_snapshot; the span on the
    # other thread covers none of it
    assert red["coverage"] == pytest.approx(0.9)
    main = [v for k, v in red["layers"].items() if k != "tgi.maintenance"]
    assert sum(main) == pytest.approx(0.036)


def test_gap_labels_name_the_innermost_span():
    label = spans.labeler(nest())
    assert label(7 * MS) == "snapshot/serialize.decode"
    assert label(11 * MS) == "snapshot/kvstore.multiget"
    assert label(15 * MS) == "snapshot/kvstore.multiget"
    assert label(31 * MS) == "snapshot/tgi.get_snapshot"
    # no program span of the request's thread: the operation alone
    assert label(1 * MS) == "snapshot"
    assert label(39 * MS) == "snapshot"
    assert label(50 * MS) == "between requests"


def test_gap_labels_without_program_spans_are_the_old_ones():
    ex = small_trace()
    label = spans.labeler({"program": [], "requests": [
        (s, e, name, MAIN) for s, e, name in ex["spans"]]})
    for t in (5 * MS, 45 * MS, 75 * MS, 97 * MS, 120 * MS):
        assert label(t) == tracing._span_label(ex["spans"], t)


def test_program_names_are_told_from_the_profilers():
    assert spans.is_program("tgi.fetch_delta")
    assert not spans.is_program("req:snapshot:0")
    assert not spans.is_program("$tgi.py:1089 get_snapshot")
    assert not spans.is_program("tgi.")
    assert not spans.is_program("jit_prog.1")


def test_request_reduction_is_unchanged_by_program_spans():
    ex = small_trace()
    want = tracing.reduce(copy.deepcopy(ex), (0.0, 100 * MS))
    ex["program"] = [(5 * MS, 35 * MS, "tgi.get_snapshot", MAIN)]
    assert tracing.reduce(ex, (0.0, 100 * MS)) == want


READS_MIX = {"kind": "reads", "block": {"snapshot": 2, "snapshots": 1,
                                        "node_history": 1, "k_hop": 1},
             "params": {"batch_timepoints": 4, "batch_days": [0.5, 1.0],
                        "history_days": 5, "k": 1, "zipf_exponent": 1.0},
             "sample_per_op": 1}


def test_traced_reads_add_up_on_a_real_trace(tmp_path, monkeypatch, capsys):
    """A traced CPU run of a small reads cell with the store's spans on:
    the spans cover most of each read, and the layers' self times plus
    the time no span covers make up the reads' time."""
    import json
    import shutil

    from chipbench import harness
    from repro import trace
    from test_chipbench_reference import TINY

    root = tmp_path / "root"
    chip = root / "benchmarks" / "chip"
    for d in ("histories", "metrics", "operations", "traffic"):
        shutil.copytree(HERE / d, chip / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "configs").mkdir()
    (chip / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (chip / "traffic" / "tiny_reads.json").write_text(json.dumps(READS_MIX))
    bench = {"configs": [{"name": "tiny",
                          "file": "benchmarks/chip/configs/tiny.json"}],
             "workloads": [{"name": "tiny.reads", "config": "tiny",
                            "traffic": "tiny_reads", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    got = {}
    real_extract = tracing.extract

    def extract(trace_dir):  # the program's spans of the same trace
        got["ex"] = spans.extract(trace_dir)
        return real_extract(trace_dir)

    monkeypatch.setattr(harness.tracing, "extract", extract)
    was = trace.enable()
    try:
        rc = harness.main(["--workload", "tiny.reads", "--seed", "11",
                           "--seconds", "1", "--trace", "1"],
                          root=root, require_tpu=False)
    finally:
        trace.enable(was)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res
    ex = got["ex"]
    reqs = ex["requests"]
    window = (reqs[0][0], reqs[-1][1])
    red = spans.reduce(ex, window)
    assert sum(red["requests"].values()) == res["attempted"] == len(reqs)
    assert red["coverage"] > 0.5
    req_s = sum(e - s for s, e, _, _ in reqs) / 1e9
    assert sum(red["layers"].values()) == pytest.approx(
        red["coverage"] * req_s, rel=1e-6)
    for name in ("tgi.get_snapshot", "tgi.get_snapshots", "kvstore.multiget",
                 "overlay.dispatch", "snapshot.delta_to_graph"):
        assert red["layers"][name] > 0, name
