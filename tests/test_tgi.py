"""TGI correctness: index-reconstructed state == naive full replay, for
snapshots, node histories, and k-hop neighborhoods — plus storage-layer
behaviors (replication failover, placement spread)."""
import numpy as np
import pytest

from repro.core.events import EDGE_ADD, NATTR_SET, NODE_ADD, NODE_DEL, EventLog
from repro.core.snapshot import GraphState
from repro.core.tgi import TGI, TGIConfig
from repro.data.temporal_graph_gen import generate, naive_state_at
from repro.storage.kvstore import DeltaStore, StorageNodeDown

N_EVENTS = 6000


@pytest.fixture(scope="module")
def built():
    events = generate(N_EVENTS, seed=7)
    cfg = TGIConfig(n_shards=4, parts_per_shard=2, events_per_span=1500,
                    eventlist_size=128, checkpoints_per_span=4)
    store = DeltaStore(m=4, r=2, backend="mem")
    tgi = TGI.build(events, cfg, store)
    return events, cfg, store, tgi


def _states_equal(a: GraphState, b: GraphState):
    n = max(len(a.present), len(b.present))
    a.grow(n)
    b.grow(n)
    assert (a.present == b.present).all(), "presence mismatch"
    on = a.present == 1
    assert (a.attrs[on] == b.attrs[on]).all(), "attr mismatch"
    assert len(a.edge_key) == len(b.edge_key), (
        f"edge count {len(a.edge_key)} vs {len(b.edge_key)}"
    )
    assert (a.edge_key == b.edge_key).all()
    assert (a.edge_val == b.edge_val).all(), "edge attr mismatch"


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("frac", [0.05, 0.3, 0.5, 0.77, 0.99])
def test_snapshot_matches_naive_replay(built, frac, use_kernel):
    events, cfg, store, tgi = built
    t0, t1 = events.time_range()
    t = int(t0 + frac * (t1 - t0))
    tgi.invalidate_caches()  # the snapshot LRU does not key on use_kernel
    got = tgi.get_snapshot(t, use_kernel=use_kernel)
    want = naive_state_at(events, t, cfg.n_attrs)
    _states_equal(got, want)


def test_snapshot_parallel_fetch_equal(built):
    events, cfg, store, tgi = built
    t = int(np.mean(events.time_range()))
    a = tgi.get_snapshot(t, c=1)
    b = tgi.get_snapshot(t, c=4)
    _states_equal(a, b)


def test_snapshot_with_kernel_path(built):
    events, cfg, store, tgi = built
    t = int(np.mean(events.time_range()))
    a = tgi.get_snapshot(t, use_kernel=False)
    tgi.invalidate_caches()  # else the LRU serves the host fold's result
    b = tgi.get_snapshot(t, use_kernel=True)
    _states_equal(a, b)


def _history_window(events):
    t0g, t1g = events.time_range()
    return int(t0g + 0.3 * (t1g - t0g)), int(t0g + 0.8 * (t1g - t0g))


def _node_of_kind(events, kind, t0, t1):
    """A node id of one kind, picked from the log: a hub present over the
    whole window; one deleted at or before t0; one deleted inside
    (t0, t1]; one first added inside (t0, t1]; an id never present."""
    dels = events.kind == NODE_DEL
    if kind == "hub":
        st = naive_state_at(events, t0)
        gone = events.src[dels & (events.t <= t1)]
        deg = np.where(np.isin(np.arange(len(st.present)), gone), -1, st.degree())
        return int(np.argmax(deg))
    if kind == "deleted_before":
        return int(events.src[dels & (events.t <= t0)][0])
    if kind == "deleted_inside":
        return int(events.src[dels & (events.t > t0) & (events.t <= t1)][0])
    if kind == "added_inside":
        adds = (events.kind == NODE_ADD) & (events.t > t0) & (events.t <= t1)
        return int(events.src[adds][0])
    return events.n_nodes + 7  # never present


def test_node_history_matches_naive(built):
    events, cfg, store, tgi = built
    t0, t1 = _history_window(events)
    # pick active nodes
    want_state = naive_state_at(events, t0, cfg.n_attrs)
    nids = want_state.node_ids()[:5]
    for nid in nids:
        _check_node_history(events, cfg, tgi, int(nid), t0, t1, want_state)


@pytest.mark.parametrize("kind", ["hub", "deleted_before", "deleted_inside",
                                  "added_inside", "never_present"])
def test_node_history_by_kind_of_node(built, kind):
    events, cfg, store, tgi = built
    t0, t1 = _history_window(events)
    nid = _node_of_kind(events, kind, t0, t1)
    want_state = naive_state_at(events, t0, cfg.n_attrs)
    present_t0 = nid < len(want_state.present) and bool(want_state.present[nid])
    assert present_t0 == (kind in ("hub", "deleted_inside"))
    init, ev = _check_node_history(events, cfg, tgi, nid, t0, t1, want_state)
    assert (init is not None) == present_t0
    kinds = set(ev.kind.tolist())
    assert (NODE_DEL in kinds) == (kind == "deleted_inside")
    assert (NODE_ADD in kinds) == (kind == "added_inside")
    assert (len(ev) > 0) == (kind in ("hub", "deleted_inside", "added_inside"))


def _check_node_history(events, cfg, tgi, nid, t0, t1, want_state):
    init, ev = tgi.get_node_history(nid, t0, t1)
    # init matches naive state at t0
    if nid < len(want_state.present) and want_state.present[nid]:
        assert init is not None
        assert (init["attrs"] == want_state.attrs[nid]).all()
        naive_neigh = set()
        src, dst, _ = want_state.edges()
        naive_neigh |= set(dst[src == nid].tolist())
        naive_neigh |= set(src[dst == nid].tolist())
        assert set(init["neighbors"].tolist()) == naive_neigh
    else:
        assert init is None
    # events match naive filter
    sel = ((events.src == nid) | (events.dst == nid)) & (events.t > t0) & (events.t <= t1)
    want_ev = events.take(np.nonzero(sel)[0])
    assert len(ev) == len(want_ev)
    for col in ("t", "kind", "src", "dst", "key", "val"):
        assert (getattr(ev, col) == getattr(want_ev, col)).all(), col
    return init, ev


@pytest.fixture(scope="module")
def readded(built):
    """The module's history with one deleted node added back before the
    k-hop timepoint: a NODE_ADD, a label, and edges to three nodes the
    log never deletes, all in one otherwise empty second."""
    events, cfg, _, _ = built
    t = _k_hop_time(events)
    dels = events.kind == NODE_DEL
    nid, t_del = int(events.src[dels][0]), int(events.t[dels][0])
    ts = np.unique(events.t)
    free = ts[:-1][(np.diff(ts) > 1) & (ts[:-1] > t_del) & (ts[:-1] < t - 1)] + 1
    t_re = int(free[len(free) // 2])
    st = naive_state_at(events, t_re, cfg.n_attrs)
    keep = np.isin(np.arange(len(st.present)), events.src[dels], invert=True)
    nbrs = [int(v) for v in np.argsort(-np.where(keep, st.degree(), -1))[:3]]
    assert all(st.present[v] for v in nbrs)
    m = len(nbrs)
    extra = EventLog.from_arrays(
        [t_re] * (2 + m), [NODE_ADD, NATTR_SET] + [EDGE_ADD] * m,
        [nid, nid] + [min(nid, v) for v in nbrs],
        [-1, -1] + [max(nid, v) for v in nbrs],
        [-1, 0] + [-1] * m, [-1, 5] + [3] * m)
    events = events.concat(extra)
    tgi = TGI.build(events, cfg, DeltaStore(m=4, r=2, backend="mem"))
    return events, cfg, tgi, nid


def _k_hop_time(events):
    t0g, t1g = events.time_range()
    return int(t0g + 0.6 * (t1g - t0g))


@pytest.mark.parametrize("k,method,node", [
    (1, "expand", "hub"), (1, "snapshot", "hub"), (2, "expand", "hub"),
    (1, "expand", "deleted"), (2, "expand", "deleted"),
    (1, "expand", "readded"), (2, "expand", "readded")])
def test_k_hop_matches_filtered_snapshot(built, request, k, method, node):
    if node == "readded":
        events, cfg, tgi, nid = request.getfixturevalue("readded")
    else:
        events, cfg, store, tgi = built
    t = _k_hop_time(events)
    want_full = naive_state_at(events, t, cfg.n_attrs)
    if node == "hub":
        nid = int(np.argmax(want_full.degree()))
    elif node == "deleted":
        nid = int(events.src[(events.kind == NODE_DEL) & (events.t <= t)][0])
    assert bool(want_full.present[nid]) == (node != "deleted")
    got = tgi.get_k_hop(nid, t, k, method=method)
    want = tgi._filter_k_hop(want_full, nid, k)
    _states_equal(got, want)


def test_1hop_history(built):
    events, cfg, store, tgi = built
    t0g, t1g = events.time_range()
    t0 = int(t0g + 0.4 * (t1g - t0g))
    t1 = int(t0g + 0.7 * (t1g - t0g))
    state = naive_state_at(events, t0, cfg.n_attrs)
    nid = int(np.argmax(state.degree()))
    out = tgi.get_node_1hop_history(nid, t0, t1)
    assert out["hood"].present[nid]
    for m, ev_m in out["neighbor_events"].items():
        sel = ((events.src == m) | (events.dst == m)) & (events.t > t0) & (events.t <= t1)
        assert len(ev_m) == int(sel.sum())


def test_replication_failover(built):
    events, cfg, store, tgi = built
    t = int(np.mean(events.time_range()))
    want = tgi.get_snapshot(t)
    store.stats.reset()
    store.fail_node(0)
    tgi.invalidate_caches()  # force a real storage read past the snapshot LRU
    try:
        got = tgi.get_snapshot(t)
        _states_equal(got, want)
        assert store.stats.failovers > 0
    finally:
        store.heal_node(0)


def test_all_replicas_down_raises():
    events = generate(800, seed=1)
    cfg = TGIConfig(n_shards=2, parts_per_shard=2, events_per_span=400,
                    eventlist_size=64, checkpoints_per_span=2)
    store = DeltaStore(m=2, r=1, backend="mem")
    tgi = TGI.build(events, cfg, store)
    store.fail_node(0)
    store.fail_node(1)
    with pytest.raises((StorageNodeDown, KeyError)):
        tgi.get_snapshot(int(np.mean(events.time_range())))


def test_incremental_update_equals_bulk_build():
    events = generate(4000, seed=3)
    half = len(events) // 2
    cfg = TGIConfig(n_shards=2, parts_per_shard=2, events_per_span=1000,
                    eventlist_size=100, checkpoints_per_span=2)
    s1 = DeltaStore(m=2, r=1, backend="mem")
    bulk = TGI.build(events, cfg, s1)
    s2 = DeltaStore(m=2, r=1, backend="mem")
    inc = TGI.build(events.take(slice(0, half)), cfg, s2)
    inc.update(events.take(slice(half, len(events))))
    t0, t1 = events.time_range()
    for frac in (0.25, 0.6, 0.95):
        t = int(t0 + frac * (t1 - t0))
        _states_equal(bulk.get_snapshot(t), inc.get_snapshot(t))


def test_locality_partitioning_build():
    events = generate(2500, seed=11)
    cfg = TGIConfig(n_shards=2, parts_per_shard=2, events_per_span=900,
                    eventlist_size=100, checkpoints_per_span=2,
                    partition_strategy="locality", replicate_1hop=True)
    store = DeltaStore(m=2, r=1, backend="mem")
    tgi = TGI.build(events, cfg, store)
    t0, t1 = events.time_range()
    t = int(t0 + 0.7 * (t1 - t0))
    _states_equal(tgi.get_snapshot(t), naive_state_at(events, t, cfg.n_attrs))


def test_file_backend_roundtrip(tmp_path):
    events = generate(1200, seed=5)
    cfg = TGIConfig(n_shards=2, parts_per_shard=2, events_per_span=600,
                    eventlist_size=64, checkpoints_per_span=2)
    store = DeltaStore(m=3, r=2, backend="file", root=str(tmp_path))
    tgi = TGI.build(events, cfg, store)
    t0, t1 = events.time_range()
    t = int(t0 + 0.8 * (t1 - t0))
    _states_equal(tgi.get_snapshot(t), naive_state_at(events, t, cfg.n_attrs))
    # and under single-node failure
    store.fail_node(1)
    _states_equal(tgi.get_snapshot(t), naive_state_at(events, t, cfg.n_attrs))
