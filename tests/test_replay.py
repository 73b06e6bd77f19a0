"""Batched replay engine: property tests against the reference per-event
loops (randomized event logs incl. NODE_DEL-clears-attrs and same-
timestamp orderings), the one-replay plan golden, batched snapshot
parity, and the executor's replay LRU."""
import numpy as np
import pytest

from repro.core.events import (
    EDGE_ADD,
    EDGE_DEL,
    NATTR_SET,
    NODE_ADD,
    NODE_DEL,
)
from repro.core.snapshot import pack_edge_key
from repro.data.temporal_graph_gen import generate
from repro.storage.kvstore import DeltaStore
from repro.taf import HistoricalGraphStore, TemporalQuery, operators as ops, replay
from repro.taf.son import SoTS


# ---------------------------------------------------------------------------
# Randomized operands (direct construction: full control over orderings)
# ---------------------------------------------------------------------------


def random_sots(rng, N=10, K=3, t_max=40, id_stride=3):
    """Random SoTS with adversarial structure: same-timestamp event runs,
    NODE_DEL / NATTR interleavings, edge events referencing both member
    and non-member ids, sparse node ids."""
    node_ids = np.sort(
        rng.choice(np.arange(N * id_stride), size=N, replace=False)
    ).astype(np.int32)
    init_present = (rng.rand(N) < 0.7).astype(np.int8)
    init_attrs = rng.randint(-1, 6, size=(N, K)).astype(np.int32)
    counts = rng.randint(0, 14, size=N)
    indptr = np.r_[0, np.cumsum(counts)].astype(np.int64)
    E = int(indptr[-1])
    ev_t = np.empty(E, np.int64)
    ev_kind = np.empty(E, np.int8)
    ev_key = np.full(E, -1, np.int16)
    ev_val = np.full(E, -1, np.int32)
    ev_other = np.full(E, -1, np.int32)
    other_pool = np.concatenate([node_ids, node_ids + 1])  # some non-members
    kinds_pool = [NODE_ADD, NODE_DEL, NATTR_SET, NATTR_SET, EDGE_ADD,
                  EDGE_ADD, EDGE_DEL]
    for i in range(N):
        lo, hi = int(indptr[i]), int(indptr[i + 1])
        n = hi - lo
        if not n:
            continue
        tt = np.sort(rng.randint(0, t_max, size=n))
        # force same-timestamp runs: collapse random adjacent gaps
        for j in range(1, n):
            if rng.rand() < 0.4:
                tt[j] = tt[j - 1]
        ev_t[lo:hi] = np.sort(tt)
        ev_kind[lo:hi] = rng.choice(kinds_pool, size=n)
        ev_key[lo:hi] = rng.randint(0, K, size=n)
        ev_val[lo:hi] = rng.randint(0, 9, size=n)
        ev_other[lo:hi] = rng.choice(other_pool, size=n)
    # initial adjacency: sorted unique neighbors per center
    adj_counts = rng.randint(0, 4, size=N)
    adj_indptr = np.r_[0, np.cumsum(adj_counts)].astype(np.int64)
    adj_nbr = np.empty(int(adj_indptr[-1]), np.int32)
    for i in range(N):
        lo, hi = int(adj_indptr[i]), int(adj_indptr[i + 1])
        if hi > lo:
            adj_nbr[lo:hi] = np.sort(
                rng.choice(other_pool, size=hi - lo, replace=False))
    return SoTS(
        node_ids=node_ids, t0=0, t1=t_max,
        init_present=init_present, init_attrs=init_attrs,
        ev_indptr=indptr, ev_t=ev_t, ev_kind=ev_kind, ev_key=ev_key,
        ev_val=ev_val, ev_other=ev_other,
        adj_indptr=adj_indptr, adj_nbr=adj_nbr,
        adj_val=np.full(len(adj_nbr), -1, np.int32),
    )


# ---------------------------------------------------------------------------
# state_at_many == _state_at_ref column-by-column
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_state_at_many_matches_reference_loop(seed):
    rng = np.random.RandomState(seed)
    sots = random_sots(rng)
    # unsorted, duplicated, and out-of-range timepoints
    ts = rng.randint(-5, 50, size=13).astype(np.int64)
    ts[3] = ts[7]
    present, attrs = replay.state_at_many(sots, ts)
    assert present.shape == (len(sots), len(ts))
    assert attrs.shape == (len(sots), len(ts), sots.init_attrs.shape[1])
    for j, t in enumerate(ts):
        p_ref, a_ref = ops._state_at_ref(sots, int(t))
        np.testing.assert_array_equal(present[:, j], p_ref, err_msg=f"t={t}")
        np.testing.assert_array_equal(attrs[:, j], a_ref, err_msg=f"t={t}")


def test_state_at_many_delete_clears_then_rewrite_batched():
    """The NODE_DEL-clears-all-attrs + same-timestamp NATTR resurrection
    ordering, evaluated at every timepoint in one batch."""
    son = SoTS(
        node_ids=np.asarray([0, 1], np.int32), t0=0, t1=10,
        init_present=np.asarray([1, 1], np.int8),
        init_attrs=np.asarray([[5, 6], [7, 8]], np.int32),
        ev_indptr=np.asarray([0, 3, 5], np.int64),
        ev_t=np.asarray([1, 2, 2, 2, 2], np.int64),
        ev_kind=np.asarray([NODE_DEL, NATTR_SET, NATTR_SET,
                            NODE_DEL, NATTR_SET], np.int8),
        ev_key=np.asarray([-1, 0, 1, -1, 0], np.int16),
        ev_val=np.asarray([-1, 9, 11, -1, 4], np.int32),
        ev_other=np.full(5, -1, np.int32),
        adj_indptr=np.zeros(3, np.int64),
        adj_nbr=np.empty(0, np.int32), adj_val=np.empty(0, np.int32),
    )
    ts = np.asarray([0, 1, 2, 3, 10], np.int64)
    present, attrs = replay.state_at_many(son, ts)
    for j, t in enumerate(ts):
        p_ref, a_ref = ops._state_at_ref(son, int(t))
        np.testing.assert_array_equal(present[:, j], p_ref)
        np.testing.assert_array_equal(attrs[:, j], a_ref)


# ---------------------------------------------------------------------------
# EdgeReplay == the per-event set-replay loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_neighbors_at_matches_reference_loop(seed):
    rng = np.random.RandomState(100 + seed)
    sots = random_sots(rng)
    ts = (-1, 0, 7, 20, 39, 45)
    for t in ts:
        for i in range(len(sots)):
            want = ops._neighbors_at_ref(sots, i, t)
            got = ops.neighbors_at(sots, i, t)
            np.testing.assert_array_equal(got, want, err_msg=f"i={i} t={t}")
    # and the batched per-center form over the shared table
    for i in range(len(sots)):
        many = replay.neighbors_at_many(sots, i, ts)
        for t, got in zip(ts, many):
            np.testing.assert_array_equal(got, ops._neighbors_at_ref(sots, i, t))


@pytest.mark.parametrize("seed", range(4))
def test_degree_series_matches_neighbor_counts(seed):
    rng = np.random.RandomState(200 + seed)
    sots = random_sots(rng)
    ts = np.asarray([0, 5, 17, 39], np.int64)
    deg = replay.degree_series(sots, ts)
    for j, t in enumerate(ts):
        for i in range(len(sots)):
            assert deg[i, j] == len(ops._neighbors_at_ref(sots, i, int(t)))


@pytest.mark.parametrize("seed", range(4))
def test_graph_matches_reference_construction(seed):
    """graph() on the CSR path == the old per-node set-loop construction
    (present centers, members-only edges, canonical packed keys)."""
    rng = np.random.RandomState(300 + seed)
    sots = random_sots(rng)
    for t in (0, 11, 39):
        g = ops.graph(sots, t)
        present, _ = ops._state_at_ref(sots, t)
        member = set(int(x) for x in sots.node_ids)
        keys = []
        for i in range(len(sots)):
            if not present[i]:
                continue
            u = int(sots.node_ids[i])
            for v in ops._neighbors_at_ref(sots, i, t):
                if int(v) in member:
                    keys.append(pack_edge_key([min(u, int(v))],
                                              [max(u, int(v))])[0])
        want = np.unique(np.asarray(keys, np.int64)) if keys else \
            np.empty(0, np.int64)
        np.testing.assert_array_equal(g.edge_key, want)
        np.testing.assert_array_equal(g.present[sots.node_ids], present)


def test_pack_edge_key_guards_range():
    with pytest.raises(ValueError):
        pack_edge_key([2**31], [0])
    with pytest.raises(ValueError):
        pack_edge_key([0], [-1])
    # distinct pairs stay distinct near the boundary (the old arithmetic
    # pack collided once dst crossed 2^31)
    k = pack_edge_key([1, 2], [2**31 - 1, 0])
    assert len(np.unique(k)) == 2


# ---------------------------------------------------------------------------
# Vectorized delta fold == scalar fold
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_vectorized_delta_fold_matches_scalar(seed):
    rng = np.random.RandomState(400 + seed)
    sots = random_sots(rng)
    pts = np.asarray([3, 9, 9, 21, 39], np.int64)

    def f_s(present, attrs, son, i, init):
        deg = son.adj_indptr[i + 1] - son.adj_indptr[i]
        return None, float(deg if present else 0)

    def fd_s(aux, val, kind, key, val_, other, i, son):
        if kind == EDGE_ADD:
            return aux, val + 1.0
        if kind == EDGE_DEL:
            return aux, val - 1.0
        return aux, val

    def f_v(present, attrs, son, init, **kw):
        deg = (son.adj_indptr[1:] - son.adj_indptr[:-1]).astype(np.float64)
        return None, np.where(present == 1, deg, 0.0)

    def fd_v(aux, val, node, kind, son, **kw):
        np.add.at(val, node[kind == EDGE_ADD], 1.0)
        np.add.at(val, node[kind == EDGE_DEL], -1.0)
        return aux, val

    f_v.vectorized = True
    fd_v.vectorized = True
    ts_s, out_s = ops.node_compute_delta(sots, f_s, fd_s, points=pts)
    ts_v, out_v = ops.node_compute_delta(sots, f_v, fd_v, points=pts)
    np.testing.assert_array_equal(ts_s, ts_v)
    np.testing.assert_allclose(out_s, out_v)


# ---------------------------------------------------------------------------
# Plan integration: one replay per multi-timepoint plan + the LRU
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def store_setup():
    events = generate(3000, seed=11)
    store = HistoricalGraphStore.build(
        events, n_shards=2, parts_per_shard=2, events_per_span=900,
        eventlist_size=128, checkpoints_per_span=3,
        store=DeltaStore(m=2, r=1, backend="mem"))
    t0g, t1g = store.time_range()
    t0 = int(t0g + 0.3 * (t1g - t0g))
    t1 = int(t0g + 0.8 * (t1g - t0g))
    return store, t0, t1


def test_multi_ts_plan_issues_exactly_one_replay(store_setup):
    store, t0, t1 = store_setup
    ts = [t0, (t0 + t1) // 2, t1]
    q = store.nodes(t0, t1).timeslice(ts)
    before = replay.STATS["state_at_many"]
    out = q.execute()
    assert replay.STATS["state_at_many"] - before == 1
    assert out["present"].shape[1] == len(ts)
    # and a temporal compute over pinned points batches the same way
    def f(present, attrs, son, t, **kw):
        return present.astype(np.float64)

    f.vectorized = True
    before = replay.STATS["state_at_many"]
    store.nodes(t0, t1).timeslice(ts).node_compute(f, style="temporal").execute()
    assert replay.STATS["state_at_many"] - before == 1


def test_repeated_slice_hits_executor_lru(store_setup):
    store, t0, t1 = store_setup
    sots = store.subgraphs(t0, t1).materialize()
    ts = [t0, t1]
    before = replay.STATS["state_at_many"]
    a = sots.timeslice(ts).execute()
    b = sots.timeslice(ts).execute()
    assert replay.STATS["state_at_many"] - before == 1  # second is an LRU hit
    np.testing.assert_array_equal(a["present"], b["present"])


def test_replay_cache_rejects_recycled_operand_identity():
    """An LRU entry must die with its operand: id() recycling after gc
    must not serve operand A's states for a different operand B."""
    cache = replay.ReplayCache(maxsize=4)

    def make(val):
        return SoTS(
            node_ids=np.asarray([0], np.int32), t0=0, t1=10,
            init_present=np.asarray([1], np.int8),
            init_attrs=np.asarray([[val]], np.int32),
            ev_indptr=np.asarray([0, 0], np.int64),
            ev_t=np.empty(0, np.int64), ev_kind=np.empty(0, np.int8),
            ev_key=np.empty(0, np.int16), ev_val=np.empty(0, np.int32),
            ev_other=np.empty(0, np.int32),
            adj_indptr=np.zeros(2, np.int64),
            adj_nbr=np.empty(0, np.int32), adj_val=np.empty(0, np.int32),
        )

    a = make(111)
    key_a = (replay.operand_key(a), ("scalar", 5))
    cache.put(key_a, {"attrs": a.init_attrs}, owner=a)
    assert cache.get(key_a, owner=a) is not None
    del a  # operand dies; its address may be recycled by the next alloc
    b = make(222)
    key_b = (replay.operand_key(b), ("scalar", 5))
    hit = cache.get(key_b, owner=b)
    assert hit is None or hit["attrs"][0, 0] == 222


def test_cached_slice_results_are_mutation_safe(store_setup):
    """Mutating an executed timeslice result must not poison the LRU."""
    store, t0, t1 = store_setup
    q = store.nodes(t0, t1).materialize()
    ts = [t0, (t0 + t1) // 2]
    first = q.timeslice(ts).execute()
    want = first["present"].copy()
    first["present"][:] = -7
    again = q.timeslice(ts).execute()
    np.testing.assert_array_equal(again["present"], want)


def test_get_snapshots_does_not_pollute_single_snapshot_cost(store_setup):
    """Batch members share one fetch; a later single get_snapshot must
    report its own exact logical cost, not the group's."""
    store, t0, t1 = store_setup
    tgi = store.tgi
    ts = np.linspace(t0, t1, 5).astype(np.int64).tolist()
    tgi.invalidate_caches()
    tgi.get_snapshot(int(ts[0]))
    cold = tgi.last_cost.n_deltas
    tgi.invalidate_caches()
    tgi.get_snapshots(ts)
    tgi.get_snapshot(int(ts[0]))  # after the batch: same accounting
    assert tgi.last_cost.n_deltas == cold


def test_timeslice_multi_matches_scalar_slices(store_setup):
    store, t0, t1 = store_setup
    son = store.nodes(t0, t1).materialize().operand
    ts = np.linspace(t0 - 1, t1 + 1, 7).astype(np.int64)
    sl = ops.timeslice(son, ts)
    for j, t in enumerate(ts):
        single = ops.timeslice(son, int(t))
        np.testing.assert_array_equal(sl["present"][:, j], single["present"])
        np.testing.assert_array_equal(sl["attrs"][:, j], single["attrs"])


# ---------------------------------------------------------------------------
# Batched snapshot retrieval (TGI.get_snapshots)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel,clustered", [
    (False, False), (True, False), (True, True)],
    ids=["False", "True", "clustered-True"])
def test_get_snapshots_matches_single_snapshots(store_setup, use_kernel,
                                                clustered):
    store, t0, t1 = store_setup
    tgi = store.tgi
    if clustered:
        # 8 timepoints 5 ticks apart: (span, leaf) groups with T > 1 and
        # T > K, so the time-batched kernel branch folds them
        tm = (t0 + t1) // 2
        ts = [tm + 5 * j for j in range(8)]
        with tgi.read_guard() as view:
            sis = [tgi._span_index(t, view) for t in ts]
            groups = {(si.span.tsid, tgi._leaf_for(si, t))
                      for si, t in zip(sis, ts)}
        assert len(groups) < len(ts) and len(ts) > store.cfg.n_attrs
    else:
        ts = np.linspace(t0, t1, 5).astype(np.int64).tolist()
    tgi.invalidate_caches()
    want = []
    for t in ts:
        tgi.invalidate_caches()
        want.append(tgi.get_snapshot(int(t)))
    tgi.invalidate_caches()
    got = tgi.get_snapshots(ts, use_kernel=use_kernel)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.present, w.present)
        np.testing.assert_array_equal(g.attrs, w.attrs)
        np.testing.assert_array_equal(g.edge_key, w.edge_key)


def test_get_snapshots_shares_fetches(store_setup):
    """Timepoints under one (span, checkpoint) group must not re-pay the
    hierarchy path per t: the batch costs less than T singles."""
    store, t0, t1 = store_setup
    tgi = store.tgi
    ts = np.linspace(t0, t1, 6).astype(np.int64).tolist()
    singles = 0
    for t in ts:
        tgi.invalidate_caches()
        tgi.get_snapshot(int(t))
        singles += tgi.last_cost.n_deltas
    tgi.invalidate_caches()
    tgi.get_snapshots(ts)
    assert tgi.last_cost.n_deltas < singles


def test_snapshot_cache_replays_logical_cost(store_setup):
    store, t0, t1 = store_setup
    tgi = store.tgi
    tm = (t0 + t1) // 2
    tgi.invalidate_caches()
    g1 = tgi.get_snapshot(tm)
    cost1 = (tgi.last_cost.n_deltas, tgi.last_cost.n_bytes)
    reads = store.store.stats.reads
    g2 = tgi.get_snapshot(tm)  # LRU hit: no storage reads, same accounting
    assert store.store.stats.reads == reads
    assert (tgi.last_cost.n_deltas, tgi.last_cost.n_bytes) == cost1
    np.testing.assert_array_equal(g1.present, g2.present)
    np.testing.assert_array_equal(g1.edge_key, g2.edge_key)
    g2.present[:] = 0  # cached copies must not alias
    assert tgi.get_snapshot(tm).present.sum() == g1.present.sum()


# ---------------------------------------------------------------------------
# Aggregation fix: sign-aware saturate
# ---------------------------------------------------------------------------


def test_saturate_sign_aware():
    pos = np.asarray([0.0, 0.5, 0.96, 1.0])
    assert ops.temp_aggregate(pos, "saturate") == 2
    neg = -pos  # e.g. a difference series from compare()
    assert ops.temp_aggregate(neg, "saturate") == 2
    # the old >= 0.95*final test would return 0 here
    drift = np.asarray([-0.1, -0.4, -0.97, -1.0])
    assert ops.temp_aggregate(drift, "saturate") == 2


# ---------------------------------------------------------------------------
# Device parity: time-batched degree kernel
# ---------------------------------------------------------------------------


def test_sharded_degree_series_matches_replay(store_setup):
    from repro.taf import exec as taf_exec

    store, t0, t1 = store_setup
    sots = store.subgraphs(t0, t1).materialize().operand
    ts = np.linspace(t0, t1, 4).astype(np.int64)
    got = taf_exec.sharded_degree_series(sots, ts)
    want = replay.degree_series(sots, ts)
    on = sots.init_present == 1
    np.testing.assert_array_equal(got[on], want[on])
