"""The harness end to end on the CPU, with its look for a chip skipped:
a configuration and a cell added as data alone, a traffic kind added as
a file, and a history with node and edge deletes added as a file that
its configuration names, run and come out correct; an answer altered
where the program produces it, half of a query's timepoints left out, an
iteration that returns its state unchanged, and the bfloat16 PageRank
control come out not correct.  Without a TPU, without the store under
test, or with a history that is not there, the entry point exits
non-zero and prints no result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from test_chipbench_reference import MIX, TINY  # noqa: E402

SECONDS = "1.5"

# A history with deletes, written into the test root only: a later
# deployment adds its generator the same way, as a file under histories/.
DELETES_HISTORY = textwrap.dedent('''
    """A small history with deletes: pairs added, deleted and re-added
    (some re-added while live, a value update), each pair's writes at
    distinct seconds; nodes deleted while they have a live edge, every
    other one re-added later."""
    import numpy as np

    NODE_ADD, NODE_DEL, EDGE_ADD, EDGE_DEL = 0, 1, 2, 3
    DAY = 86_400


    def history(cfg):
        pub, a = cfg["published"], cfg["shape"]
        n, n_pairs = pub["nodes"], pub["pairs"]
        span = pub["time_span_days"] * DAY
        rng = np.random.default_rng(a["structure_seed"])
        iu, iv = np.triu_indices(n, 1)
        pick = rng.choice(len(iu), n_pairs, replace=False)
        t0 = span // 20  # every node is added before the first edge write
        ev = [(int(t), NODE_ADD, i, -1, -1)
              for i, t in enumerate(rng.integers(0, t0, n))]
        for u, v in zip(iu[pick], iv[pick]):
            w = int(rng.integers(1, a["max_writes"] + 1))
            ts = np.sort(t0 + rng.choice(span - t0, w, replace=False))
            live = False
            for t in ts:
                update = live and rng.random() < a["update_share"]
                kind = EDGE_DEL if live and not update else EDGE_ADD
                live = kind == EDGE_ADD
                ev.append((int(t), kind, int(u), int(v),
                           int(rng.integers(1, 4)) if live else -1))
        edges = sorted(e for e in ev if e[3] >= 0)
        deleted = []
        for t in np.sort(rng.integers(int(0.55 * span), int(0.85 * span),
                                      a["node_deletes"])):
            last = {}
            for e in edges:  # each pair's last write at or before t
                if e[0] <= t:
                    last[e[2:4]] = e[1]
            live = [x for p, k in last.items() if k == EDGE_ADD for x in p]
            node = next(x for x in live if x not in deleted)
            deleted.append(node)
            ev.append((int(t), NODE_DEL, node, -1, -1))
            if len(deleted) % 2:
                ev.append((int(t + (span - t) // 2), NODE_ADD, node, -1, -1))
        ev.sort(key=lambda e: e[0])
        c = np.array(ev, np.int64)
        cols = {"t": c[:, 0], "kind": c[:, 1].astype(np.int8),
                "src": c[:, 2].astype(np.int32), "dst": c[:, 3].astype(np.int32),
                "key": np.full(len(c), -1, np.int16),
                "val": c[:, 4].astype(np.int32)}
        counts = {k: int((cols["kind"] == i).sum()) for i, k in enumerate(
            ("node_adds", "node_dels", "edge_adds", "edge_dels"))}
        counts.update(nodes=n, pairs=n_pairs,
                      time_span_days=float(c[-1, 0] - c[0, 0]) / DAY)
        return {"cols": cols, "counts": counts}


    def tails(cols, spans=()):
        """The most writes and the most existence changes of one pair, over
        the history and in each (lo, hi] span."""
        e = cols["dst"] >= 0
        pair = cols["src"][e].astype(np.int64) * (1 << 32) + cols["dst"][e]
        t = cols["t"][e]
        order = np.lexsort((t, pair))
        pair, t = pair[order], t[order]
        live = cols["kind"][e][order] == EDGE_ADD
        first = np.r_[True, pair[1:] != pair[:-1]]
        change = live != np.where(first, False, np.r_[False, live[:-1]])

        def most(sel):
            def top(x):
                return int(np.unique(x, return_counts=True)[1].max()) if len(x) else 0
            return {"max_pair_writes": top(pair[sel]),
                    "max_pair_changes": top(pair[sel & change])}

        out = most(np.ones(len(t), bool))
        for lo, hi in spans:
            out.setdefault("windows", []).append(most((t > lo) & (t <= hi)))
        return out
''')
TINY_DELETES = {"name": "tiny_deletes", "history": "tiny_deletes",
                "published": {"nodes": 40, "pairs": 160, "time_span_days": 30},
                "shape": {"structure_seed": 7, "max_writes": 8,
                          "update_share": 0.25, "node_deletes": 8},
                "store": {"n_shards": 4, "parts_per_shard": 2}}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout root that holds the benchmark with more
    configurations, mixes and cells, added as files and entries only:
    among them a history with deletes, and a configuration that names a
    history with no file."""
    root = tmp_path_factory.mktemp("bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = root / "benchmarks" / "chip"
    for d in ("histories", "metrics", "operations", "traffic"):
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
    (chip / "histories" / "tiny_deletes.py").write_text(DELETES_HISTORY)
    for name, cfg in (("tiny_deletes", TINY_DELETES),
                      ("tiny_unknown", dict(TINY, history="no_such_history"))):
        (chip / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmarks/chip/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.mix", "config": name,
                                   "traffic": "tiny_mix", "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "email_eu_core.analytics64" in m.get("workloads", []):
            m["workloads"] += ["tiny.mix", "tiny.kind", "tiny_deletes.mix"]
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


ALTERED = ["pagerank", "components", "component_count", "timeslice",
           "triangles"]


@pytest.mark.parametrize("op,cell", [
    *(pytest.param(op, "tiny.mix", id=op) for op in ALTERED),
    *(pytest.param(op, "tiny_deletes.mix", id=f"deletes-{op}")
      for op in ALTERED)])
def test_altered_answer_is_not_correct(tiny_root, capsys, monkeypatch, op,
                                       cell):
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
    rc, res = run(tiny_root, capsys, cell=cell)
    assert rc == 0 and not res["correct"]
    bad = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert bad == {f"{op}_mismatch" if op != "pagerank" else "pagerank_gap"}


def test_new_kind_file_is_found_by_name(tiny_root, capsys):
    rc, res = run(tiny_root, capsys, cell="tiny.kind")
    assert rc == 0 and res["correct"], res


def test_deletes_history_has_what_it_is_for(tiny_root):
    """Nodes deleted while an edge of theirs lives, some re-added; pairs
    deleted and re-added; no pair written twice in one second."""
    from chipbench.harness import load_cell
    from reference.replay import NODE_ADD, NODE_DEL, History

    spec = load_cell(tiny_root, "tiny_deletes.mix")
    cols = spec["history"].history(spec["cfg"])["cols"]
    ref = History(cols)
    dels = np.nonzero(cols["kind"] == NODE_DEL)[0]
    assert len(dels) == TINY_DELETES["shape"]["node_deletes"]
    for i in dels:
        exists, _ = ref.pairs_at(int(cols["t"][i]))
        node = cols["src"][i]
        assert ((ref.pair_u[exists] == node) | (ref.pair_v[exists] == node)).any()
    readded = [i for i in dels if ((cols["kind"] == NODE_ADD)
                                   & (cols["src"] == cols["src"][i])
                                   & (cols["t"] > cols["t"][i])).any()]
    assert 0 < len(readded) < len(dels)
    e = cols["dst"] >= 0
    written = np.c_[cols["src"][e], cols["dst"][e], cols["t"][e]]
    assert len(np.unique(written, axis=0)) == len(written)
    assert spec["history"].tails(cols)["max_pair_changes"] >= 3


def test_history_with_deletes_runs_correct(tiny_root, capsys):
    rc, res = run(tiny_root, capsys, cell="tiny_deletes.mix")
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"analytics_queries_per_s",
                                   "analytics_p95_ms", "setup_s"}
    assert res["checks"]["pagerank_gap"]["value"] < 1e-4


def test_deletes_traced_run_reads_a_wide_change_table(tiny_root, capsys,
                                                      monkeypatch):
    """The edge operands keep at least three changes of one pair, and
    most pair events change existence."""
    from repro.taf import compile as tc
    from repro.taf.replay import EdgeReplay

    real = EdgeReplay.device_export
    widths = []

    def export(self):
        out = real(self)
        widths.append(out["chg_t"].shape[1])
        return out

    monkeypatch.setattr(EdgeReplay, "device_export", export)
    for k in ("flip_events", "flip_changes"):  # this run's exports alone
        monkeypatch.setitem(tc.STATS, k, 0)
    rc, res = run(tiny_root, capsys, trace=1, cell="tiny_deletes.mix")
    assert rc == 0 and res["correct"], res
    m = res["metrics"]
    assert m["flip_change_share"]["value"] > 50
    assert max(widths) >= 3
    assert m["compiles_in_window"]["value"] == 0


# sha256 over email_eu_core's six generated columns, each as its name,
# its dtype and its bytes, in the store's column order
EMAIL_DIGEST = "dc0072d81987395d20fba16dfc6fb157e270bc3fe0a6725d65cf4af92c821b35"


def test_email_history_is_unchanged():
    from chipbench.harness import load_cell

    spec = load_cell(ROOT, "email_eu_core.analytics64")
    cols = spec["history"].history(spec["cfg"])["cols"]
    d = hashlib.sha256()
    for k in ("t", "kind", "src", "dst", "key", "val"):
        c = np.ascontiguousarray(cols[k])
        d.update(f"{k}:{c.dtype.str}:".encode())
        d.update(c.tobytes())
    assert d.hexdigest() == EMAIL_DIGEST


@pytest.mark.parametrize("cell,module", [
    ("email_eu_core.analytics64", "interactions"),
    ("tiny.mix", "interactions"),
    ("tiny_deletes.mix", "tiny_deletes")])
def test_history_is_found_by_name(tiny_root, cell, module):
    """A configuration without ``history`` gets ``interactions``."""
    from chipbench.harness import load_cell

    root = ROOT if cell.startswith("email_eu_core") else tiny_root
    spec = load_cell(root, cell)
    assert ("history" in spec["cfg"]) == (module != "interactions")
    assert Path(spec["history"].__file__) == (
        root / "benchmarks" / "chip" / "histories" / f"{module}.py")


def test_unknown_history_exits_nonzero_without_result(tiny_root):
    p = subprocess.run(
        [sys.executable, str(HERE / "run_cell.py"), "--workload",
         "tiny_unknown.mix", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "histories/no_such_history.py" in p.stderr
    assert p.stdout.strip() == ""


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
    bench["workloads"].append({"name": "tiny_deletes.reads",
                               "config": "tiny_deletes",
                               "traffic": "tiny_reads", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "email_eu_core.reads" in m.get("workloads", []):
            m["workloads"] += ["tiny.reads", "tiny_deletes.reads"]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return tiny_root


def run_reads(root, capsys, control=0, trace=0, cell="tiny.reads"):
    from chipbench.harness import main

    rc = main(["--workload", cell, "--seed", "9", "--seconds",
               SECONDS, "--trace", str(trace), "--control", str(control)],
              root=root, require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_reads_cell_runs_correct(reads_root, capsys):
    rc, res = run_reads(reads_root, capsys)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"read_p95_ms", "setup_s"}


def test_deletes_reads_cell_runs_correct(reads_root, capsys):
    rc, res = run_reads(reads_root, capsys, cell="tiny_deletes.reads")
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0
    assert not any(k.endswith("_unchecked") for k in res["checks"])


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
