"""The harness end to end on the CPU, with its look for a chip skipped:
a configuration and a cell added as data alone, and a traffic kind added
as a file, run and come out correct; an answer altered where the program
produces it, half of a query's timepoints left out, an iteration that
returns its state unchanged, and the bfloat16 PageRank control come out
not correct.  Without a TPU, or without the store under test, the entry
point exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from test_chipbench_reference import MIX, TINY  # noqa: E402

SECONDS = "1.5"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout root that holds the benchmark with one more
    configuration, one more mix and one more cell, added as files and
    entries only."""
    root = tmp_path_factory.mktemp("bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = root / "benchmarks" / "chip"
    for d in ("metrics", "operations", "traffic"):
        shutil.copytree(HERE / d, chip / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "configs").mkdir()
    (chip / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (chip / "traffic" / "tiny_mix.json").write_text(
        json.dumps(dict(MIX, sample_per_op=1)))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.mix", "config": "tiny",
                               "traffic": "tiny_mix", "chips": 1,
                               "why": "test"})
    (chip / "traffic" / "tiny_kind.py").write_text(
        (HERE / "traffic" / "analytics.py").read_text())
    (chip / "traffic" / "tiny_kind_mix.json").write_text(
        json.dumps(dict(MIX, kind="tiny_kind", sample_per_op=1)))
    bench["workloads"].append({"name": "tiny.kind", "config": "tiny",
                               "traffic": "tiny_kind_mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "email_eu_core.analytics64" in m.get("workloads", []):
            m["workloads"] += ["tiny.mix", "tiny.kind"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, capsys, seed=2**31 + 5, trace=0, control=0, cell="tiny.mix",
        extra=()):
    from chipbench.harness import main

    rc = main(["--workload", cell, "--seed", str(seed), "--seconds",
               SECONDS, "--trace", str(trace), "--control", str(control),
               *extra], root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_data_only_cell_runs_correct(tiny_root, capsys, tmp_path):
    records = tmp_path / "records.jsonl"
    rc, res = run(tiny_root, capsys, extra=("--records", str(records)))
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    lines = [json.loads(x) for x in records.read_text().splitlines()]
    assert len(lines) == res["attempted"] and lines[0]["latency_s"] > 0
    assert set(res["metrics"]) == {"analytics_queries_per_s",
                                   "analytics_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["pagerank_gap"]["value"] < 1e-4


def test_traced_run_reports_per_layer_metrics(tiny_root, capsys):
    rc, res = run(tiny_root, capsys, trace=1)
    assert rc == 0 and res["correct"], res
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["operand_uploads_in_window"]["value"] == 0
    assert "window_s" in res["device"] and "busy_s" in res["device"]


@pytest.mark.parametrize("op", ["pagerank", "components", "component_count",
                                "timeslice", "triangles"])
def test_altered_answer_is_not_correct(tiny_root, capsys, monkeypatch, op):
    from repro.taf import plan

    real = plan.PlanExecutor.run

    # the final stage each operation's plan ends in
    stage = {"pagerank": ("compute", "pagerank"),
             "components": ("compute", "components"),
             "triangles": ("compute", "triangles"),
             "component_count": ("evolution", "components.count_components"),
             "timeslice": ("slice", None)}[op]

    def altered(self, p):
        res = real(self, p)
        last = p.stages[-1]
        if (last.kind, getattr(getattr(last, "fn", None), "name", None)) == stage:
            v = res.value
            arr = v["present"] if isinstance(v, dict) else v[1]
            arr.flat[0] = arr.flat[0] + 1
        return res

    monkeypatch.setattr(plan.PlanExecutor, "run", altered)
    rc, res = run(tiny_root, capsys)
    assert rc == 0 and not res["correct"]
    bad = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert bad == {f"{op}_mismatch" if op != "pagerank" else "pagerank_gap"}


def test_new_kind_file_is_found_by_name(tiny_root, capsys):
    rc, res = run(tiny_root, capsys, cell="tiny.kind")
    assert rc == 0 and res["correct"], res


def test_half_the_timepoints_left_out_is_not_correct(tiny_root, capsys,
                                                     monkeypatch):
    """Each series answers its first half of timepoints and repeats them
    over the second half."""
    from repro.taf import plan

    real = plan.PlanExecutor.run

    def halved(self, p):
        res = real(self, p)
        v = res.value
        arr = v["present"] if isinstance(v, dict) else v[1]  # (..., T)
        h = arr.shape[-1] // 2
        arr[..., h:2 * h] = arr[..., :h]
        return res

    monkeypatch.setattr(plan.PlanExecutor, "run", halved)
    rc, res = run(tiny_root, capsys)
    assert rc == 0 and not res["correct"]


@pytest.mark.parametrize("op", ["pagerank", "components"])
def test_state_left_unchanged_is_not_correct(tiny_root, capsys, monkeypatch,
                                             op):
    """The iteration returns its state unchanged: the program runs no
    iterations at all."""
    from repro.taf import compile as tc

    real = getattr(tc, op)
    if op == "pagerank":
        monkeypatch.setattr(tc, op, lambda damping, iters: real(damping, 0))
    else:
        monkeypatch.setattr(tc, op, lambda iters: real(0))
    rc, res = run(tiny_root, capsys)
    assert rc == 0 and not res["correct"]
    name = "pagerank_gap" if op == "pagerank" else "components_mismatch"
    assert res["checks"][name]["value"] > res["checks"][name]["limit"]


def test_bfloat16_control_is_not_correct(tiny_root, capsys):
    rc, res = run(tiny_root, capsys, control=1)
    assert rc == 0 and not res["correct"]
    c = res["checks"]["pagerank_gap"]
    assert c["value"] > c["limit"]


def _entry(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, str(cwd / "benchmarks" / "chip" / "run_cell.py"),
         "--workload", "email_eu_core.analytics64", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _entry(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _entry(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert "correct" not in p.stdout


READS = {"kind": "reads", "block": {"snapshot": 2, "snapshots": 1,
                                    "node_history": 1, "k_hop": 1},
         "params": {"batch_timepoints": 4, "batch_days": [0.5, 1.0],
                    "history_days": 5, "k": 1, "zipf_exponent": 1.0},
         "sample_per_op": 2}


@pytest.fixture(scope="module")
def reads_root(tiny_root):
    chip = tiny_root / "benchmarks" / "chip"
    (chip / "traffic" / "tiny_reads.json").write_text(json.dumps(READS))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.reads", "config": "tiny",
                               "traffic": "tiny_reads", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "email_eu_core.reads" in m.get("workloads", []):
            m["workloads"].append("tiny.reads")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return tiny_root


def run_reads(root, capsys, control=0, trace=0):
    from chipbench.harness import main

    rc = main(["--workload", "tiny.reads", "--seed", "9", "--seconds",
               SECONDS, "--trace", str(trace), "--control", str(control)],
              root=root, require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_reads_cell_runs_correct(reads_root, capsys):
    rc, res = run_reads(reads_root, capsys)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"read_p95_ms", "setup_s"}


def test_reads_cell_reports_read_layers(reads_root, capsys):
    rc, res = run_reads(reads_root, capsys, trace=1)
    assert rc == 0 and res["correct"], res
    m = res["metrics"]
    assert m["decoded_bytes_per_read"]["value"] > 0
    assert 0 <= m["pool_byte_share"]["value"] <= 100


def test_stale_read_control_is_not_correct(reads_root, capsys):
    rc, res = run_reads(reads_root, capsys, control=1)
    assert rc == 0 and not res["correct"]


def _alter_graph(g):
    g = g.copy()
    on = np.nonzero(g.present)[0]
    g.present[on[0]] = 0
    return g


@pytest.mark.parametrize("fault", ["snapshot", "snapshots", "half_batch",
                                   "node_history", "k_hop"])
def test_faulty_read_is_not_correct(reads_root, capsys, monkeypatch, fault):
    from repro.taf import HistoricalGraphStore as S

    name = "snapshots" if fault == "half_batch" else fault
    real = getattr(S, name)

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        if fault == "half_batch":
            return out[: len(out) // 2]
        if fault == "snapshots":
            return [_alter_graph(out[0])] + out[1:]
        if fault == "node_history":
            init, log = out
            return init, log.take(slice(1, None))
        return _alter_graph(out)

    monkeypatch.setattr(S, name, broken)
    rc, res = run_reads(reads_root, capsys)
    assert rc == 0 and not res["correct"]
    bad = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert bad == {f"{name}_mismatch"}, res["checks"]
