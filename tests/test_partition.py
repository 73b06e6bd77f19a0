"""Timespan partitioning (paper §4.5): Ω time-collapse against a plain
per-event replay, the balanced static partitioner, the per-span node
sets, and equal-event timespans that never split a timestamp."""
import numpy as np
import pytest

from repro.core.events import EDGE_ADD, EDGE_DEL, NODE_DEL, EventLog
from repro.core.partition import collapse, partition_graph, partition_timespan
from repro.core.timespan import split_timespans
from repro.data.temporal_graph_gen import generate


def _collapse_loop(events: EventLog, omega: str, t0: int, t1: int):
    """Ω by one pass over the edge events in log order, starting from no
    edges: median keeps the edges present at (t0 + t1) // 2 with their
    last added weight; union_max keeps every edge added in the log at
    its largest added weight; union_mean weighs each edge added in the
    log by the share of [t0, t1] it is present."""
    tm = (t0 + t1) // 2
    at_tm, w_max, on_since, on_time = {}, {}, {}, {}
    for i in range(len(events)):
        kind = int(events.kind[i])
        if kind not in (EDGE_ADD, EDGE_DEL):
            continue
        e = (int(events.src[i]), int(events.dst[i]))
        t = int(events.t[i])
        w = float(events.val[i]) if events.val[i] >= 0 else 1.0
        on_time.setdefault(e, 0)
        if kind == EDGE_ADD:
            w_max[e] = max(w_max.get(e, 0.0), w)
            if t <= tm:
                at_tm[e] = w
            on_since.setdefault(e, t)
        else:
            if t <= tm:
                at_tm.pop(e, None)
            if e in on_since:
                on_time[e] += t - on_since.pop(e)
    for e, since in on_since.items():
        on_time[e] += t1 - since
    if omega == "median":
        out = at_tm
    elif omega == "union_max":
        out = {e: w for e, w in w_max.items() if w > 0}
    else:
        span = max(t1 - t0, 1)
        out = {e: float(np.float32(s / span)) for e, s in on_time.items() if s > 0}
    return sorted((s, d, w) for (s, d), w in out.items())


@pytest.mark.parametrize("window", ["whole", "sub"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("omega", ["median", "union_max", "union_mean"])
def test_collapse_matches_per_event_loop(omega, seed, window):
    events = generate(1500, seed=seed)
    assert (events.kind == EDGE_DEL).any() and (events.kind == NODE_DEL).any()
    ta, tb = events.time_range()
    if window == "sub":
        ta, tb = ta + (tb - ta) // 4, ta + 3 * (tb - ta) // 4
        events = events.slice_time(ta, tb)
        src, dst, w = collapse(events, omega, t0=ta, t1=tb)
    else:
        src, dst, w = collapse(events, omega)
    want = _collapse_loop(events, omega, ta, tb)
    got = sorted(zip(src.tolist(), dst.tolist(), w.tolist()))
    assert len(want) > 0
    assert got == want


@pytest.fixture(scope="module")
def span_graph():
    events = generate(1200, seed=5)
    nids, _ = partition_timespan(events, 1, "hash")
    return events, nids, collapse(events)


_K = [1, 2, 3, 4, 8]


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("k", _K)
def test_partition_graph_assigns_every_node_once(span_graph, k, weighted):
    _, nids, (src, dst, w) = span_graph
    weights = w if weighted else None
    assign = partition_graph(nids, src, dst, weights, k, seed=3)
    assert assign.shape == nids.shape
    assert ((assign >= 0) & (assign < k)).all()
    assert np.bincount(assign, minlength=k).max() <= -(-len(nids) // k)  # ceil
    again = partition_graph(nids, src, dst, weights, k, seed=3)
    np.testing.assert_array_equal(assign, again)


_BELOW_FLOOR = pytest.mark.xfail(
    strict=True, reason="partition_graph caps parts at ceil(n/k) but sets no "
    "floor: the greedy fills parts to the cap and refinement moves nodes out, "
    "so a part can end below floor(n/k), against its docstring's ceil/floor")


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize(
    "k", [pytest.param(k, marks=_BELOW_FLOOR) if k in (4, 8) else k for k in _K])
def test_partition_graph_parts_hold_at_least_floor(span_graph, k, weighted):
    _, nids, (src, dst, w) = span_graph
    assign = partition_graph(nids, src, dst, w if weighted else None, k, seed=3)
    assert np.bincount(assign, minlength=k).min() >= len(nids) // k


@pytest.mark.parametrize("strategy", ["hash", "locality"])
def test_partition_timespan_node_ids(strategy):
    events = generate(900, seed=8)
    nids, assign = partition_timespan(events, 4, strategy)
    ids = np.concatenate([events.src, events.dst])
    np.testing.assert_array_equal(nids, np.unique(ids[ids >= 0]))
    if strategy == "hash":
        assert assign is None
    else:
        assert assign.shape == nids.shape
        assert ((assign >= 0) & (assign < 4)).all()


_SPLIT_N = 3000


@pytest.mark.parametrize("per_span", [1, 2, 7, 128, 1500, _SPLIT_N, _SPLIT_N + 1, "empty"])
def test_split_timespans_tiles_the_log(per_span):
    if per_span == "empty":
        spans = split_timespans(EventLog.empty(), 64)
        assert [(s.tsid, s.ev_lo, s.ev_hi) for s in spans] == [(0, 0, 0)]
        return
    events = generate(_SPLIT_N, seed=2)
    t = events.t
    n = len(events)
    spans = split_timespans(events, per_span)
    assert [s.tsid for s in spans] == list(range(len(spans)))
    assert spans[0].ev_lo == 0 and spans[-1].ev_hi == n
    for a, b in zip(spans, spans[1:]):
        assert a.ev_hi == b.ev_lo  # no gap, no overlap
        assert t[a.ev_hi - 1] < t[b.ev_lo]  # no timestamp split
    for s in spans:
        assert s.ev_lo < s.ev_hi
        assert (s.t_start, s.t_end) == (t[s.ev_lo], t[s.ev_hi - 1])
        if s.ev_hi - s.ev_lo > per_span:  # only to keep one second whole
            assert t[s.ev_lo + per_span - 1] == t[s.ev_hi - 1]
    if per_span >= n:
        assert len(spans) == 1
