"""The ``ldbc_knows`` history (LDBC SNB person-``knows`` with DEL1 and
DEL8) on the CPU: it keeps the invariants of the source's deletes, the
store answers PageRank, components and component counts over it as the
plain reference does, its configured depth of components stays within
half of the mix's rounds, and a tiny copy of its analytics cell, added
as data, runs correct and reads a change table of existence changes
alone and node-event tables with node deletes."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.harness import load_history, load_module  # noqa: E402
from reference.analytics import Windows  # noqa: E402
from reference.replay import (EDGE_ADD, EDGE_DEL, NODE_ADD, NODE_DEL,  # noqa: E402
                              History)

CELL = "ldbc_snb_sf1_knows.analytics64"
CONFIG = json.loads((HERE / "configs" / "ldbc_snb_sf1_knows.json").read_text())
MIX = json.loads((HERE / "traffic" / "analytics64_social.json").read_text())
HISTORY = load_history(HERE, CONFIG)
ANALYTICS = load_module(HERE / "traffic" / "analytics.py", "test_ldbc_kind_")
# the same shape at about 300 persons
TINY = dict(CONFIG, name="tiny_ldbc",
            published={"nodes": 300, "static_edges": 5_475,
                       "time_span_days": 1_096},
            assumed=dict(CONFIG["assumed"], locality_mean_rank=3))
TINY["assumed"].pop("tails")


@pytest.fixture(scope="module")
def tiny():
    return HISTORY.history(TINY)


@pytest.fixture(scope="module")
def sf1():
    return HISTORY.history(CONFIG)


@pytest.fixture(scope="module")
def built(tiny):
    from repro.core.events import EventLog
    from repro.taf import HistoricalGraphStore

    store = HistoricalGraphStore.build(EventLog(**tiny["cols"]),
                                       **TINY["store"])
    return store, Windows(History(tiny["cols"]))


@pytest.mark.parametrize("which", ["tiny", "sf1"])
def test_history_keeps_the_invariants_of_its_deletes(which, request):
    """Sorted by time; the published counts; every person added once and
    deleted at most once, never re-added; no pair written twice in one
    second, and no person added or deleted at a second that writes one
    of its friendships' creation; at every event time (a sample of them
    at SF1) friendships join present persons only, so a person deleted
    by DEL1 keeps no live friendship."""
    h = request.getfixturevalue(which)
    cfg = TINY if which == "tiny" else CONFIG
    cols = h["cols"]
    t, kind, src, dst = (cols["t"], cols["kind"], cols["src"].astype(np.int64),
                         cols["dst"].astype(np.int64))
    assert (np.diff(t) >= 0).all()
    pub = cfg["published"]
    assert (h["counts"]["nodes"], h["counts"]["static_edges"]) == (
        pub["nodes"], pub["static_edges"])
    assert (kind == EDGE_ADD).sum() == pub["static_edges"]
    adds, dels = src[kind == NODE_ADD], src[kind == NODE_DEL]
    assert len(np.unique(adds)) == len(adds) == pub["nodes"]
    assert len(np.unique(dels)) == len(dels) > 0
    added_at = np.zeros(pub["nodes"], np.int64)
    added_at[adds] = t[kind == NODE_ADD]
    assert (t[kind == NODE_DEL] > added_at[dels]).all()
    e = dst >= 0
    assert (kind[e] != NODE_ADD).all() and (kind[~e] <= NODE_DEL).all()
    assert (src[e] < dst[e]).all()
    written = np.c_[src[e], dst[e], t[e]]
    assert len(np.unique(written, axis=0)) == len(written)
    assert ((kind == EDGE_DEL).sum()
            == sum(h["counts"][k] for k in ("knows_deletes",
                                            "cascade_deletes")))
    # a person's own writes and its friendships' creations: no shared second
    node_at = np.c_[src[~e], t[~e]]
    made = e & (kind == EDGE_ADD)
    made_at = np.r_[np.c_[src[made], t[made]], np.c_[dst[made], t[made]]]
    both = np.r_[np.unique(node_at, axis=0), np.unique(made_at, axis=0)]
    assert len(np.unique(both, axis=0)) == len(both)
    ref = History(cols)
    times = np.unique(t)
    if which == "sf1":
        times = np.r_[t[kind == NODE_DEL][:40],
                      times[:: max(len(times) // 40, 1)]]
    for x in times:
        exists, _ = ref.pairs_at(int(x))
        present = ref.present_at(int(x))
        assert present[ref.pair_u[exists]].all()
        assert present[ref.pair_v[exists]].all()


def test_most_pair_events_change_existence(tiny):
    """A friendship is made once and deleted at most once: every pair
    event changes its existence, at most two a pair."""
    assert HISTORY.tails(tiny["cols"])["max_pair_changes"] == 2


def test_configured_depth_within_half_the_rounds(sf1):
    """Min-label propagation settles in as many rounds as the deepest
    hop distance to a component's least row: over the whole history and
    in each window of the cell's mix it is at most half of the mix's
    rounds."""
    rounds = MIX["params"]["components_iters"]
    cols = sf1["cols"]
    span = (int(cols["t"].min()), int(cols["t"].max()))
    got = HISTORY.tails(cols, ANALYTICS.windows(MIX, span))
    assert got["max_component_depth"] == (
        CONFIG["assumed"]["tails"]["max_component_depth"])
    assert got["max_component_depth"] <= rounds // 2
    for w in got["windows"]:
        assert w["max_component_depth"] <= rounds // 2


def test_depth_of_a_known_history():
    """Persons 0..4: 3-4 and 0-4 from t=1, 1-2 from t=2; 0-4 deleted at
    t=5, and person 4 at t=6 with 3-4.  At t=1 the component {0, 3, 4}
    has its least row 0 two hops from 3; in the window (5, 6] (all five
    members) 3-4 and 1-2 are one hop deep, and person 4 writes twice."""
    cols = {"t": np.array([0, 0, 0, 0, 0, 1, 1, 2, 5, 6, 6]),
            "kind": np.array([NODE_ADD] * 5 + [EDGE_ADD] * 3
                             + [EDGE_DEL] * 2 + [NODE_DEL]),
            "src": np.array([0, 1, 2, 3, 4, 3, 0, 1, 0, 3, 4]),
            "dst": np.array([-1] * 5 + [4, 4, 2, 4, 4, -1])}
    got = HISTORY.tails(cols, [(5, 6)])
    assert got["max_component_depth"] == 2
    assert got["max_partners"] == 2 and got["max_pair_changes"] == 2
    assert got["windows"] == [{"max_person_events": 2,
                               "max_pair_changes": 1,
                               "max_component_depth": 1}]


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 2**33 + 5])
@pytest.mark.parametrize("op", ["pagerank", "components", "component_count"])
def test_store_agrees_with_reference(built, op, seed):
    store, ref = built
    mod = load_module(HERE / "operations" / f"{op}.py", "test_ldbc_op_")
    reqs = ANALYTICS.requests(MIX, store.time_range(), seed, None)
    req = next(r for r in reqs if r["op"] == op)
    got = mod.answer(mod.run(store, req, MIX["params"]))
    name, val = mod.compare(req, got, mod.expect(ref, req, MIX["params"]))
    assert val <= mod.LIMITS[name]
    if op != "pagerank":
        assert val == 0


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A checkout root holding a tiny copy of the cell: the committed
    history, mix, operations and metrics, and a configuration of the
    same shape at about 300 persons, added as data alone."""
    root = tmp_path_factory.mktemp("ldbc")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = root / "benchmarks" / "chip"
    for d in ("histories", "metrics", "operations", "traffic"):
        shutil.copytree(HERE / d, chip / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "configs").mkdir()
    (chip / "configs" / "tiny_ldbc.json").write_text(json.dumps(TINY))
    bench["configs"].append({"name": "tiny_ldbc", "source": "test",
                             "file": "benchmarks/chip/configs/tiny_ldbc.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_ldbc.analytics64",
                               "config": "tiny_ldbc",
                               "traffic": "analytics64_social", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_ldbc.analytics64")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_tiny_cell_runs_correct_over_deletes(tiny_cell, capsys, monkeypatch):
    from chipbench.harness import main
    from repro.taf import compile as tc

    for k in ("flip_events", "flip_changes", "node_events", "node_slots",
              "node_deletes"):  # this run's exports alone
        monkeypatch.setitem(tc.STATS, k, 0)
    rc = main(["--workload", "tiny_ldbc.analytics64", "--seed",
               str(2**31 + 3), "--seconds", "1.5", "--trace", "1"],
              root=tiny_cell, require_tpu=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0
    assert not any(k.endswith("_unchecked") for k in res["checks"])
    m = res["metrics"]
    assert m["flip_change_share"]["value"] > 90
    assert 0 < m["node_slot_fill"]["value"] < 100
    assert m["compiles_in_window"]["value"] == 0
    assert m["operand_uploads_in_window"]["value"] == 0
    assert "motif_kernel_ms" not in m
    assert tc.STATS["node_deletes"] > 0
