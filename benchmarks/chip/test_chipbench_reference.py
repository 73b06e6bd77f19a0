"""The plain reference agrees with the store on small generated
histories (CPU); the generator meets the counts and tails it is given;
the traffic kinds hold every block to its counts."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.harness import load_history, load_module  # noqa: E402
from reference.analytics import Windows  # noqa: E402
from reference.replay import History  # noqa: E402

INTERACTIONS = load_module(HERE / "histories" / "interactions.py",
                           "test_history_")

TINY = {
    "name": "tiny",
    "published": {"nodes": 60, "temporal_edges": 1800, "static_edges": 300,
                  "time_span_days": 40},
    "assumed": {"structure_seed": 3, "out_degree_exponent": 0.85,
                "in_degree_exponent": 0.85, "repeat_exponent": 0.5,
                "growth_exponent": 1.5, "burst_days": 3.0,
                "edge_value_shares": {"1": 2, "2": 1}},
    "store": {"n_shards": 4, "parts_per_shard": 2},
}
MIX = {"kind": "analytics", "windows": [[0.5, 0.8], [0.6, 1.0]],
       "timepoints": 16,
       "block": {"pagerank": 1, "components": 1, "component_count": 1,
                 "timeslice": 1, "triangles": 1},
       "params": {"damping": 0.85, "pagerank_iters": 20,
                  "components_iters": 32}}
CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))
ANALYTICS = load_module(HERE / "traffic" / "analytics.py", "test_kind_")
READS = load_module(HERE / "traffic" / "reads.py", "test_kind_")
READ_MIX = {"kind": "reads", "block": {"snapshot": 2, "snapshots": 1,
                                       "node_history": 1, "k_hop": 1},
            "params": {"batch_timepoints": 8, "batch_days": [0.5, 1.0],
                       "history_days": 5, "k": 1, "zipf_exponent": 1.0}}


def operation(op):
    return load_module(HERE / "operations" / f"{op}.py", "test_op_")


@pytest.fixture(scope="module")
def built():
    from repro.core.events import EventLog
    from repro.taf import HistoricalGraphStore

    h = INTERACTIONS.history(TINY)
    store = HistoricalGraphStore.build(EventLog(**h["cols"]), **TINY["store"])
    return h, store, Windows(History(h["cols"]))


def test_generator_meets_published_counts():
    h = INTERACTIONS.history(TINY)
    c = h["counts"]
    pub = TINY["published"]
    assert (c["nodes"], c["temporal_edges"], c["static_edges"]) == (
        pub["nodes"], pub["temporal_edges"], pub["static_edges"])
    cols = h["cols"]
    assert (np.diff(cols["t"]) >= 0).all()
    assert ((cols["kind"] == INTERACTIONS.NODE_ADD).sum()) == pub["nodes"]


def configured(name):
    """A configuration and the history module it names."""
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return cfg, load_history(HERE, cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_configured_counts_are_the_published_ones(name):
    """Every published count the history reports is met; the time span
    to within a day."""
    cfg, hist = configured(name)
    c = hist.history(cfg)["counts"]
    pub = cfg["published"]
    counted = [k for k in pub if k in c and k != "time_span_days"]
    assert counted
    for k in counted:
        assert c[k] == pub[k]
    assert abs(c["time_span_days"] - pub["time_span_days"]) < 1


@pytest.mark.parametrize("name", CONFIGS)
def test_configured_tails_are_the_generated_ones(name):
    cfg, hist = configured(name)
    assert hist.tails(hist.history(cfg)["cols"]) == cfg["assumed"]["tails"]


def test_tails_of_a_known_history():
    # pair (0, 1) at t = 1, 2, 3; pair (1, 2) at t = 4; node 3 alone
    cols = {"t": np.array([0, 1, 2, 3, 4]), "src": np.array([3, 0, 0, 0, 1]),
            "dst": np.array([-1, 1, 1, 1, 2])}
    assert INTERACTIONS.tails(cols, [(1, 4), (3, 4)]) == {
        "max_partners": 2, "max_pair_interactions": 3,
        "max_node_interactions": 4,
        "windows": [{"max_node_interactions": 3, "max_pair_interactions": 2},
                    {"max_node_interactions": 1, "max_pair_interactions": 1}]}


def test_same_seed_same_history():
    x, y = INTERACTIONS.history(TINY), INTERACTIONS.history(TINY)
    for k in x["cols"]:
        assert np.array_equal(x["cols"][k], y["cols"][k])


def test_every_block_holds_the_mix_counts():
    rng = (1_000, 90_000)
    wins = ANALYTICS.windows(MIX, rng)
    block = len(ANALYTICS.block(MIX))
    reqs = ANALYTICS.requests(MIX, rng, 2**33 + 7, None)
    want = Counter((op, w) for op in MIX["block"] for w in range(len(wins)))
    for _ in range(3):
        got = [next(reqs) for _ in range(block)]
        assert Counter((r["op"], r["window"]) for r in got) == want
        for r in got:
            lo, hi = wins[r["window"]]
            assert len(r["ts"]) == MIX["timepoints"]
            assert (np.diff(r["ts"]) > 0).all()
            assert lo <= r["ts"][0] and r["ts"][-1] <= hi
    a = [next(ANALYTICS.requests(MIX, rng, s, None))["ts"] for s in (5, 5, 6)]
    assert np.array_equal(a[0], a[1]) and not np.array_equal(a[0], a[2])


def test_warm_covers_every_operation_on_every_window():
    warm = ANALYTICS.warm(MIX, (1_000, 90_000), 11, None)
    assert Counter((r["op"], r["window"]) for r in warm) == Counter(
        (op, w) for op in MIX["block"] for w in range(len(MIX["windows"])))


@pytest.mark.parametrize("op", sorted(MIX["block"]))
def test_analytics_agree(built, op):
    _, store, ref = built
    mod = operation(op)
    reqs = ANALYTICS.requests(MIX, store.time_range(), 123, None)
    req = next(r for r in reqs if r["op"] == op)
    got = mod.answer(mod.run(store, req, MIX["params"]))
    name, val = mod.compare(req, got, mod.expect(ref, req, MIX["params"]))
    assert val <= mod.LIMITS[name]
    if op != "pagerank":
        assert val == 0


def test_every_read_block_holds_the_mix_counts(built):
    h, store, _ = built
    block = len(READS.block(READ_MIX))
    reqs = READS.requests(READ_MIX, store.time_range(), 2**33 + 9, h["cols"])
    for _ in range(3):
        got = Counter(next(reqs)["op"] for _ in range(block))
        assert got == Counter(READ_MIX["block"])
    warm = READS.warm(READ_MIX, store.time_range(), 4, h["cols"])
    assert Counter(r["op"] for r in warm) == Counter(
        {"snapshots": 2, "snapshot": 1, "node_history": 1, "k_hop": 1})


@pytest.mark.parametrize("frac", [0.1, 0.5, 0.77, 1.0])
def test_snapshot_k_hop_and_history_agree(built, frac):
    h, store, ref = built
    t0, t1 = store.time_range()
    t = int(t0 + frac * (t1 - t0))
    hub = int(np.bincount(h["cols"]["src"]).argmax())
    for req in ({"op": "snapshot", "t": t},
                {"op": "k_hop", "t": t, "nid": hub},
                {"op": "node_history", "t": t0 + (t - t0) // 2, "t1": t,
                 "nid": hub},
                {"op": "snapshots",
                 "ts": np.sort(t - np.arange(0, 8 * 3600, 3600))}):
        mod = operation(req["op"])
        got = mod.answer(mod.run(store, req, READ_MIX["params"]))
        name, val = mod.compare(req, got, mod.expect(ref, req,
                                                     READ_MIX["params"]))
        assert val == 0, (req, name, val)
