"""Whole-plan compilation (repro.taf.compile): randomized fused-vs-staged
parity over adversarial operands, compile-cache no-retrace guarantees,
fallback coverage notes, the aggregate sum/std extension, and the
style="kernel" device-operand cache."""
import numpy as np
import pytest

from repro.core.events import EDGE_ADD, EDGE_DEL, NATTR_SET, NODE_ADD, NODE_DEL
from repro.taf import TemporalQuery, compile as tc, replay
from repro.taf.plan import PlanExecutor
from repro.taf.son import SoTS

from tests.test_replay import random_sots


def _both(q):
    """Run one query fused and staged; returns (fused, staged) results."""
    fused = q.run()
    with tc.disabled():
        staged = q.run()
    return fused, staged


def _ts(rng, t_max=40, T=20):
    return np.sort(rng.randint(0, t_max + 1, size=T)).astype(np.int64)


def _pair_sots(runs, adj=(), N=8, t_max=40, present=None):
    """SoTS of N nodes (ids 0..N-1), present at t0 as ``present`` says
    (all by default), with no attributes, whose events are ``runs`` —
    (center, t, kind, other), chronological per center — over the initial
    adjacency ``adj``, a list of (center, other) pairs."""
    runs = sorted(runs, key=lambda r: (r[0], r[1]))  # stable: same-second order kept
    center = np.array([r[0] for r in runs], np.int64)
    adj = sorted(adj)
    adj_c = np.array([a[0] for a in adj], np.int64)
    return SoTS(
        node_ids=np.arange(N, dtype=np.int32), t0=0, t1=t_max,
        init_present=(np.ones(N, np.int8) if present is None
                      else np.asarray(present, np.int8)),
        init_attrs=np.zeros((N, 3), np.int32),
        ev_indptr=np.searchsorted(center, np.arange(N + 1)).astype(np.int64),
        ev_t=np.array([r[1] for r in runs], np.int64),
        ev_kind=np.array([r[2] for r in runs], np.int8),
        ev_key=np.full(len(runs), -1, np.int16),
        ev_val=np.full(len(runs), -1, np.int32),
        ev_other=np.array([r[3] for r in runs], np.int32),
        adj_indptr=np.searchsorted(adj_c, np.arange(N + 1)).astype(np.int64),
        adj_nbr=np.array([a[1] for a in adj], np.int32),
        adj_val=np.full(len(adj), -1, np.int32),
    )


def _readd_operand(rng, t_max=40):
    """Add-only, as an e-mail history: pair (0, 1) re-added 300 times from
    each end, first at the window's first second and last at its last;
    (2, 3), there from the start, re-added 200 times; (1, 2), (4, 5) and
    (5, 6) added once, (4, 5) at the window's last second."""
    runs = []
    for c, o in ((0, 1), (1, 0)):
        tt = np.sort(np.r_[0, t_max, rng.randint(0, t_max + 1, 298)])
        runs += [(c, int(t), EDGE_ADD, o) for t in tt]
    for c, o in ((2, 3), (3, 2)):
        runs += [(c, int(t), EDGE_ADD, o)
                 for t in np.sort(rng.randint(0, t_max + 1, 200))]
    runs += [(1, 17, EDGE_ADD, 2), (4, t_max, EDGE_ADD, 5),
             (6, 9, EDGE_ADD, 5)]
    ts = np.sort(np.r_[0, 16, 17, t_max - 1, t_max,
                       rng.randint(0, t_max + 1, 13)]).astype(np.int64)
    return _pair_sots(runs, adj=[(2, 3), (3, 2)], t_max=t_max), ts


def _toggle_operand(rng, t_max=40):
    """Adds and deletes that toggle: same-second runs that end where they
    began or one change further, toggles across seconds, deletes of dead
    pairs and re-adds of live ones, from one end or both."""
    runs = [(0, 5, EDGE_ADD, 1), (0, 5, EDGE_DEL, 1), (0, 5, EDGE_ADD, 1),
            (0, 10, EDGE_DEL, 1), (0, 10, EDGE_ADD, 1), (0, 12, EDGE_ADD, 1),
            (0, 20, EDGE_DEL, 1), (0, 20, EDGE_DEL, 1), (0, 21, EDGE_ADD, 1),
            (0, 22, EDGE_DEL, 1), (0, 23, EDGE_ADD, 1),
            (1, 7, EDGE_DEL, 0), (1, 7, EDGE_ADD, 0), (1, 30, EDGE_DEL, 0),
            (2, 3, EDGE_DEL, 3), (2, 3, EDGE_DEL, 3), (2, 8, EDGE_ADD, 3),
            (2, 8, EDGE_DEL, 3)]
    for c, o in ((4, 5), (5, 6), (6, 4)):
        tt = np.sort(rng.randint(0, t_max + 1, 60))
        tt[1::3] = tt[0::3][: len(tt[1::3])]  # same-second runs
        kind = rng.choice([EDGE_ADD, EDGE_DEL], size=60)
        runs += [(c, int(t), int(k), o) for t, k in zip(np.sort(tt), kind)]
    ts = np.sort(np.r_[3, 5, 7, 8, 10, 20, 21, 22, 23, 30,
                       rng.randint(0, t_max + 1, 8)]).astype(np.int64)
    return _pair_sots(runs, adj=[(1, 0), (6, 4)], t_max=t_max), ts


def _presence_operand(rng, t_max=40):
    """Node events that do and do not change presence: a node that leaves
    and returns in one second, then leaves; one that is added while
    present, then leaves; an absent one added and deleted in one second;
    a delete, then an attribute write that restores presence; a delete of
    an absent node, an add, a re-add; a node with edge events alone and
    one with no event.  Timepoints fall on every change's second."""
    runs = [(0, 5, NODE_DEL, -1), (0, 5, NODE_ADD, -1), (0, 12, NODE_DEL, -1),
            (1, 7, NODE_ADD, -1), (1, 7, NODE_DEL, -1),
            (2, 6, NODE_ADD, -1), (2, 6, NODE_DEL, -1),
            (3, 4, NODE_DEL, -1), (3, 9, NATTR_SET, -1),
            (3, 15, NATTR_SET, -1),
            (4, 3, NODE_DEL, -1), (4, 8, NODE_ADD, -1), (4, 10, NODE_ADD, -1),
            (5, 4, EDGE_ADD, 6), (6, 4, EDGE_ADD, 5), (0, 2, EDGE_ADD, 3),
            (3, 2, EDGE_ADD, 0), (1, 11, EDGE_ADD, 4), (4, 11, EDGE_ADD, 1)]
    ts = np.sort(np.r_[3, 4, 5, 6, 7, 8, 9, 10, 12, 15,
                       rng.randint(0, t_max + 1, 8)]).astype(np.int64)
    return _pair_sots(runs, adj=[(1, 3), (3, 1)], t_max=t_max,
                      present=[1, 1, 0, 1, 0, 1, 1, 0]), ts


# parity operands beyond the random ones, by name
SPECIAL = {"readd": _readd_operand, "toggle": _toggle_operand,
           "presence": _presence_operand}


def _operand(case, offset):
    """(sots, ts) of a parity case: a random operand drawn from the
    RandomState of ``offset + case``, or the named special operand."""
    if case in SPECIAL:
        return SPECIAL[case](np.random.RandomState(offset))
    rng = np.random.RandomState(offset + case)
    return random_sots(rng, N=rng.randint(4, 12)), _ts(rng, T=18)


def _pair_changes(sots):
    """(existence changes per directed (center row, other id) pair, pair
    events) by a plain replay of each center's edge events."""
    state, changes, n_events = {}, {}, 0
    for i in range(len(sots)):
        for o in sots.neighbors_of(i)[0]:
            state[(i, int(o))] = 1
    for i in range(len(sots)):
        for j in range(sots.ev_indptr[i], sots.ev_indptr[i + 1]):
            if sots.ev_kind[j] not in (EDGE_ADD, EDGE_DEL):
                continue
            pair, new = (i, int(sots.ev_other[j])), int(sots.ev_kind[j] == EDGE_ADD)
            n_events += 1
            changes[pair] = changes.get(pair, 0) + (state.get(pair, 0) != new)
            state[pair] = new
    return changes, n_events


def _node_changes(sots):
    """(presence changes per node row, NODE_DELs) by a plain replay of
    each row's events."""
    changes = np.zeros(len(sots), np.int64)
    for i in range(len(sots)):
        state = int(sots.init_present[i])
        for j in range(sots.ev_indptr[i], sots.ev_indptr[i + 1]):
            if sots.ev_kind[j] in (NODE_ADD, NODE_DEL, NATTR_SET):
                new = int(sots.ev_kind[j] != NODE_DEL)
                changes[i] += new != state
                state = new
    return changes, int((sots.ev_kind == NODE_DEL).sum())


# ---------------------------------------------------------------------------
# Randomized parity: fused == staged
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [*range(6), "presence"])
def test_fused_slice_bit_identical_randomized(seed):
    if seed in SPECIAL:
        sots, ts = SPECIAL[seed](np.random.RandomState(0))
    else:
        rng = np.random.RandomState(seed)
        sots = random_sots(rng, N=rng.randint(3, 14))
        ts = _ts(rng, T=rng.randint(tc.MIN_FUSE_T, 40))
    fused, staged = _both(TemporalQuery.over(sots).timeslice(list(ts)))
    assert any("fused slice" in n for n in fused.notes), fused.notes
    np.testing.assert_array_equal(fused.value["present"],
                                  staged.value["present"])
    np.testing.assert_array_equal(fused.value["attrs"], staged.value["attrs"])
    assert fused.value["present"].dtype == staged.value["present"].dtype
    assert fused.value["attrs"].dtype == staged.value["attrs"].dtype


@pytest.mark.parametrize("seed", [*range(4), *SPECIAL])
def test_fused_pagerank_matches_staged_randomized(seed):
    """Float op: identical math, f32 device vs f64 host — documented
    tolerance (docs/api.md), not bit parity."""
    sots, ts = _operand(seed, 100)
    q = TemporalQuery.over(sots).node_compute(
        tc.pagerank(iters=8), style="temporal", points=ts)
    fused, staged = _both(q)
    assert any("fused compute[pagerank]" in n for n in fused.notes)
    np.testing.assert_allclose(fused.value[1], staged.value[1],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seed", [*range(4), *SPECIAL])
def test_fused_components_bit_identical_randomized(seed):
    sots, ts = _operand(seed, 200)
    q = TemporalQuery.over(sots).node_compute(
        tc.components(iters=12), style="temporal", points=ts)
    fused, staged = _both(q)
    assert any("fused compute[components]" in n for n in fused.notes)
    np.testing.assert_array_equal(fused.value[1], staged.value[1])


@pytest.mark.parametrize("seed", [*range(4), *SPECIAL])
def test_fused_triangles_bit_identical_randomized(seed):
    sots, ts = _operand(seed, 300)
    q = TemporalQuery.over(sots).node_compute(
        tc.triangles(), style="temporal", points=ts)
    fused, staged = _both(q)
    assert any("fused compute[triangles]" in n for n in fused.notes)
    np.testing.assert_array_equal(fused.value[1], staged.value[1])


@pytest.mark.parametrize("case", [*range(4), *SPECIAL])
def test_edge_export_keeps_only_existence_changes(case):
    """The edge operand holds each pair's existence changes and no other
    event: its width is the most changes of any pair, the counters add
    the events seen and the changes kept, and the parity of the changes
    at or before t gives the host replay's existence."""
    sots, ts = _operand(case, 400)
    changes, n_events = _pair_changes(sots)
    before = dict(tc.STATS)
    res = TemporalQuery.over(sots).node_compute(
        tc.components(iters=12), style="temporal", points=ts).run()
    assert any("fused compute" in n for n in res.notes), res.notes
    assert tc.STATS["flip_events"] - before["flip_events"] == n_events
    assert (tc.STATS["flip_changes"] - before["flip_changes"]
            == sum(changes.values()))
    er = replay.edge_replay(sots)
    exp = er.device_export()
    assert exp["chg_t"].shape[1] == max([1, *changes.values()])
    if case == "readd":  # hundreds of events, at most one change a pair
        assert exp["chg_t"].shape[1] == 1 and n_events > 1000
    cnt = (exp["chg_t"][:, :, None] <= ts[None, None, :]).sum(axis=1)
    np.testing.assert_array_equal(exp["base"][:, None] ^ (cnt & 1),
                                  er.exist_matrix(ts))


@pytest.mark.parametrize("case", [*range(6), *SPECIAL])
def test_presence_from_change_table_matches_host_replay(case):
    """Presence by the parity of each node's presence changes, on the host
    table and in the device program, equals the host replay's presence
    bit for bit; the table is as wide as the most changes of one node."""
    import jax
    import jax.numpy as jnp

    sots, ts = _operand(case, 600)
    want, _ = replay.state_at_many(sots, ts)
    exp = replay.node_presence_changes(sots)
    changes, _ = _node_changes(sots)
    assert exp["pres_t"].shape == (len(sots), max(int(changes.max()), 1))
    cnt = (exp["pres_t"][:, :, None] <= ts[None, None, :]).sum(axis=1)
    np.testing.assert_array_equal(exp["base"][:, None] ^ (cnt & 1), want)
    node = tc._node_arrays(sots)
    assert set(node) == {"pres_t", "init_present"}
    got = jax.jit(lambda n, t: tc._dev_presence(jnp, n, t))(node, tc._tsv(ts))
    np.testing.assert_array_equal(np.asarray(got), want)
    if case == "presence":  # three changes of node 0; none of nodes 5-7
        np.testing.assert_array_equal(changes, [3, 1, 2, 2, 1, 0, 0, 0])


@pytest.mark.parametrize("case", ["deletes", *range(3), *SPECIAL])
def test_node_export_counts_events_slots_and_deletes(case):
    """The node operand's counters add the presence changes of its table,
    the table's slots (N x the most changes of one node), every event of
    the rows it read and the NODE_DELs among them, once per export."""
    if case == "deletes":  # node 0 leaves and returns, node 1 leaves
        sots = _pair_sots([(0, 5, NODE_DEL, -1), (0, 9, NODE_ADD, -1),
                           (1, 3, EDGE_ADD, 2), (2, 3, EDGE_ADD, 1),
                           (1, 7, NODE_DEL, -1), (1, 8, EDGE_DEL, 2)], N=4)
        want = {"node_events": 3, "node_slots": 4 * 2,
                "node_events_seen": 6, "node_deletes": 2}
    else:
        sots, _ = _operand(case, 500)
        changes, n_deletes = _node_changes(sots)
        want = {"node_events": int(changes.sum()),
                "node_slots": len(sots) * max(int(changes.max()), 1),
                "node_events_seen": len(sots.ev_t),
                "node_deletes": n_deletes}
    before = dict(tc.STATS)
    tc._node_arrays(sots)
    tc._node_arrays(sots)  # served from the operand cache: counted once
    assert {k: tc.STATS[k] - before[k] for k in want} == want


@pytest.mark.parametrize("mk,exact", [
    (lambda: tc.triangle_count(), True),
    (lambda: tc.component_count(iters=12), True),
    (lambda: tc.max_pagerank(iters=8), False),
])
def test_fused_evolution_matches_staged(mk, exact):
    rng = np.random.RandomState(7)
    sots = random_sots(rng, N=10)
    ts = _ts(rng, T=18)
    fused, staged = _both(TemporalQuery.over(sots).evolution(mk(), points=ts))
    assert any("fused evolution" in n for n in fused.notes), fused.notes
    got, want = np.asarray(fused.value[1]), np.asarray(staged.value[1])
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_fused_after_select_matches_staged():
    """Select runs staged (host), the terminal stage still fuses over the
    filtered operand."""
    rng = np.random.RandomState(8)
    sots = random_sots(rng, N=12)
    ts = _ts(rng, T=18)
    q = (TemporalQuery.over(sots)
         .filter(lambda s: s.node_ids % 2 == 0)
         .node_compute(tc.components(iters=12), style="temporal", points=ts))
    fused, staged = _both(q)
    assert any("fused compute" in n for n in fused.notes)
    np.testing.assert_array_equal(fused.value[1], staged.value[1])


def test_fused_aggregate_epilogue_matches_staged():
    """Aggregate is a host epilogue over the device series: fused and
    staged agree for every per-node reduction incl. the new sum/std."""
    rng = np.random.RandomState(9)
    sots = random_sots(rng, N=10)
    ts = _ts(rng, T=18)
    for op in ("max", "min", "mean", "sum", "std"):
        q = (TemporalQuery.over(sots)
             .node_compute(tc.components(iters=12), style="temporal",
                           points=ts)
             .aggregate(op))
        fused, staged = _both(q)
        np.testing.assert_array_equal(np.asarray(fused.value),
                                      np.asarray(staged.value))


# ---------------------------------------------------------------------------
# Compile cache: zero re-trace on repeated shapes
# ---------------------------------------------------------------------------


def test_repeated_plan_shape_hits_compile_cache():
    rng = np.random.RandomState(10)
    sots = random_sots(rng, N=10)
    ts = _ts(rng, T=20)
    q = TemporalQuery.over(sots).node_compute(
        tc.pagerank(iters=6), style="temporal", points=ts)
    first = q.run()
    traces0 = tc.STATS["traces"]
    # same shape, shifted timepoint *values*: no re-trace, cache hit note
    ts2 = np.minimum(ts + 1, sots.t1).astype(np.int64)
    q2 = TemporalQuery.over(sots).node_compute(
        tc.pagerank(iters=6), style="temporal", points=ts2)
    second = q2.run()
    assert tc.STATS["traces"] == traces0
    assert any("cache hit" in n for n in second.notes), second.notes
    assert any("traced" in n for n in first.notes), first.notes


def test_repeated_fused_slice_rides_replay_lru():
    """A fused slice lands in the executor's replay LRU under the staged
    key: the second identical slice dispatches nothing."""
    rng = np.random.RandomState(11)
    sots = random_sots(rng, N=10)
    ts = _ts(rng, T=20)
    q = TemporalQuery.over(sots).timeslice(list(ts))
    q.run()
    runs0 = tc.STATS["fused_runs"]
    second = q.run()
    assert any("replay-LRU hit" in n for n in second.notes), second.notes
    assert tc.STATS["fused_runs"] == runs0  # served from the LRU


# ---------------------------------------------------------------------------
# Fallback coverage: uncovered shapes run staged, with the reason noted
# ---------------------------------------------------------------------------


def test_small_T_slice_stays_staged_and_counts_replay():
    rng = np.random.RandomState(12)
    sots = random_sots(rng, N=8)
    ts = [3, 9]  # T=2 < MIN_FUSE_T
    before = dict(replay.STATS)
    res = TemporalQuery.over(sots).timeslice(ts).run()
    assert any("staged slice" in n and "MIN_FUSE_T" in n for n in res.notes)
    assert replay.STATS["state_at_many"] == before["state_at_many"] + 1


def test_plain_fn_compute_stays_staged():
    rng = np.random.RandomState(13)
    sots = random_sots(rng, N=8)

    def mean_attr(present, attrs, son, i, t):
        return float(attrs[0])

    res = TemporalQuery.over(sots).node_compute(
        mean_attr, style="temporal", points=[1, 2, 3]).run()
    assert any("staged compute" in n and "not a FusedOp" in n
               for n in res.notes), res.notes


def test_fused_op_is_a_valid_staged_fn():
    """The FusedOp object itself runs on the staged path when fusion is
    off — it IS a vectorized temporal fn (what the parity tests rely on)."""
    rng = np.random.RandomState(14)
    sots = random_sots(rng, N=8)
    with tc.disabled():
        res = TemporalQuery.over(sots).node_compute(
            tc.triangles(), style="temporal", points=[1, 5, 9]).run()
    assert any("fusion disabled" in n for n in res.notes)
    ts_out, series = res.value
    assert series.shape == (8, 3)


# ---------------------------------------------------------------------------
# Aggregate satellite: sum/std per-node reductions
# ---------------------------------------------------------------------------


def test_aggregate_sum_std_per_node_series():
    series = np.arange(12, dtype=np.float64).reshape(3, 4)
    value = (np.arange(4), series)
    np.testing.assert_allclose(
        PlanExecutor._aggregate(value, "sum"), series.sum(axis=1))
    np.testing.assert_allclose(
        PlanExecutor._aggregate(value, "std"), series.std(axis=1))
    with pytest.raises(ValueError):
        PlanExecutor._aggregate(value, "peak")


# ---------------------------------------------------------------------------
# exec satellite: device-resident operands for style="kernel"
# ---------------------------------------------------------------------------


def test_sharded_compute_memoizes_device_operands():
    from repro.taf import exec as taf_exec

    rng = np.random.RandomState(15)
    sots = random_sots(rng, N=9)
    ts = tuple(range(0, 12, 3))
    before = dict(taf_exec.STATS)
    d1 = taf_exec.sharded_degree_series(sots, ts)
    mid = dict(taf_exec.STATS)
    d2 = taf_exec.sharded_degree_series(sots, ts)
    after = dict(taf_exec.STATS)
    np.testing.assert_array_equal(d1, d2)
    # sharded_degree_series patches init_attrs -> a fresh operand per
    # call, so each run transfers once; re-running the SAME operand hits
    son = sots
    k = taf_exec.degree_at_kernel(5)
    # bake degree column the way the helpers do
    import dataclasses as dc

    deg0 = (son.adj_indptr[1:] - son.adj_indptr[:-1]).astype(np.int32)
    patched = dc.replace(
        son, init_attrs=np.concatenate([son.init_attrs, deg0[:, None]], 1))
    taf_exec.sharded_node_compute(patched, k)
    base = taf_exec.STATS["operand_cache_hits"]
    taf_exec.sharded_node_compute(patched, k)
    assert taf_exec.STATS["operand_cache_hits"] == base + 1
    assert after["operand_transfers"] >= mid["operand_transfers"] >= \
        before["operand_transfers"]


def test_kernel_compile_key_shares_jitted_program():
    from repro.taf import exec as taf_exec

    k1 = taf_exec.degree_series_kernel([1, 2, 3])
    k2 = taf_exec.degree_series_kernel([1, 2, 3])
    assert k1 is not k2 and k1.compile_key == k2.compile_key
    assert taf_exec.degree_at_kernel(7).compile_key == ("degree_at", 7)
