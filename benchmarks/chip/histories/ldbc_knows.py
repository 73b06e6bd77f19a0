"""The ``ldbc_knows`` history: the person-``knows``-person graph of the
LDBC Social Network Benchmark (Interactive workload v2) with its person
and friendship deletes, generated vectorised from a configuration file.

The published counts are met exactly: every person the scale factor
creates and every friendship.  Like a replay of Datagen's output, the
history is drawn from the configuration alone, with its
``assumed.structure_seed``, and is the same for every ``--seed``: the
seed draws the traffic.

What the public sources do not give is set under ``assumed``: the
degree distribution (lognormal target degrees, Facebook-like, with the
published mean), how friendships follow the sort keys Datagen uses
(two correlated dimensions and a random one), when persons join and
friendships form, and which persons and friendships are deleted and
when.  ``tails`` records what those choices yield; the configuration
states it under ``assumed.tails``.

Mapping onto the store's events (``knows`` is symmetric, so it is the
store's undirected pair ``(min, max)``):

* each person's creation is a ``NODE_ADD`` at its creation second;
* each ``knows`` creation is an ``EDGE_ADD`` with value 1, at least a
  second after both persons' creation and at least a second before
  either's deletion;
* DEL8 (remove friendship) is an ``EDGE_DEL``, at least a second after
  the friendship's creation and before either person's deletion;
* DEL1 (remove person) is, at one second, an explicit ``EDGE_DEL`` of
  each friendship of the person still live, then its ``NODE_DEL``.  A
  friendship whose two persons are deleted at the same second is
  deleted once.  Deleted persons are never re-added (SNB ids are not
  reused).

So no pair is written twice in one second, and no person's creation or
deletion shares a second with the creation of one of its friendships.

Columns are returned as plain numpy arrays in the store's event column
layout (``t, kind, src, dst, key, val``), sorted by time; within a
second node adds come first and node deletes last.
"""
from __future__ import annotations

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

NODE_ADD, NODE_DEL, EDGE_ADD, EDGE_DEL = 0, 1, 2, 3
DAY = 86_400
# order of the kinds within one second
_RANK = np.array([0, 3, 1, 2], np.int8)


def _persons(rng, n: int, span: int, a: dict):
    """Creation and deletion seconds of each person (deletion ``span +
    1`` for a person never deleted), and its target-degree weight."""
    c = np.floor(rng.random(n) * a["join_share_of_span"] * span).astype(
        np.int64)
    c -= c.min()
    gone = rng.random(n) < a["person_delete_share"]
    # a deleted person leaves at a uniform time between joining and the end
    d = np.where(gone, c + 1 + np.floor(rng.random(n) * (span - c - 1)),
                 span + 1).astype(np.int64)
    w = rng.lognormal(0.0, a["degree_sigma"], n)
    return c, d, w / w.sum()


def _knows(rng, n: int, n_pairs: int, c, d, w, a: dict) -> np.ndarray:
    """``n_pairs`` distinct friendships (u < v) between persons whose
    lifetimes overlap by at least ``min_overlap_days``.  Each is drawn
    from one person chosen by weight and, in a dimension chosen by its
    share, a partner near it in that dimension's sort order (the random
    dimension has no order): of ``candidates`` drawn there, the one of
    greatest weight-biased Gumbel key."""
    dims = a["dimension_shares"]
    shares = np.asarray(list(dims.values()), np.float64)
    orders = [rng.permutation(n) for _ in dims]
    ranks = [np.argsort(o) for o in orders]
    local = [name != "random" for name in dims]
    k, mean = a["candidates"], a["locality_mean_rank"]
    min_overlap = a["min_overlap_days"] * DAY
    logw = np.log(w)
    seen = np.empty(0, np.int64)
    keys = []
    while len(seen) < n_pairs:
        m = int(1.3 * (n_pairs - len(seen))) + 1024
        u = rng.choice(n, m, p=w)
        dim = rng.choice(len(shares), m, p=shares / shares.sum())
        cand = rng.integers(0, n, (m, k))  # the random dimension
        for j, (order, rank) in enumerate(zip(orders, ranks)):
            if not local[j]:
                continue
            sel = dim == j
            off = rng.geometric(1.0 / mean, (int(sel.sum()), k))
            off *= rng.choice((-1, 1), off.shape)
            cand[sel] = order[(rank[u[sel]][:, None] + off) % n]
        pick = np.argmax(logw[cand] + rng.gumbel(size=cand.shape), axis=1)
        v = cand[np.arange(m), pick]
        lo, hi = np.minimum(u, v), np.maximum(u, v)
        ok = (lo != hi) & (np.minimum(d[lo], d[hi])
                           - np.maximum(c[lo], c[hi]) >= min_overlap)
        key = lo[ok].astype(np.int64) * n + hi[ok]
        _, first = np.unique(key, return_index=True)
        key = key[np.sort(first)]  # draw order, duplicates dropped
        key = key[~np.isin(key, seen)][: n_pairs - len(seen)]
        seen = np.union1d(seen, key)
        keys.append(key)
    keys = np.concatenate(keys)
    return np.stack([keys // n, keys % n], 1)


def structure(cfg: dict) -> dict:
    """The history as abstract person indices, from the configuration
    alone: columns sorted by time and the counts produced."""
    pub, a = cfg["published"], cfg["assumed"]
    n, n_pairs = pub["nodes"], pub["static_edges"]
    span = pub["time_span_days"] * DAY
    rng = np.random.default_rng(a["structure_seed"])
    c, d, w = _persons(rng, n, span, a)
    pairs = _knows(rng, n, n_pairs, c, d, w, a)
    u, v = pairs[:, 0], pairs[:, 1]
    born, ends = np.maximum(c[u], c[v]), np.minimum(d[u], d[v])
    # creation: a second after both joined, a second before either leaves,
    # most soon after the later one joined
    room = ends - born - 2
    t_add = born + 1 + np.floor(room * rng.random(n_pairs)
                                ** a["knows_delay_exponent"]).astype(np.int64)
    # DEL8: a share of the friendships with a second to spare
    later = ends - 1 - t_add
    del8 = (rng.random(n_pairs) < a["knows_delete_share"]) & (later >= 1)
    t_del8 = t_add + 1 + np.floor(later * rng.random(n_pairs)).astype(np.int64)
    # DEL1's cascade: each friendship still live when a person of it leaves
    cascade = ~del8 & (ends <= span)
    gone = np.nonzero(d <= span)[0]
    ids = rng.permutation(n)  # person ids independent of joining order
    cols = {
        "t": np.concatenate([c, t_add, t_del8[del8], ends[cascade], d[gone]]),
        "kind": np.concatenate([
            np.full(n, NODE_ADD), np.full(n_pairs, EDGE_ADD),
            np.full(int(del8.sum()), EDGE_DEL),
            np.full(int(cascade.sum()), EDGE_DEL),
            np.full(len(gone), NODE_DEL)]),
        "a": np.concatenate([np.arange(n), u, u[del8], u[cascade], gone]),
        "b": np.concatenate([np.full(n, -1), v, v[del8], v[cascade],
                             np.full(len(gone), -1)]),
    }
    order = np.lexsort((_RANK[cols["kind"]], cols["t"]))
    cols = {k: x[order] for k, x in cols.items()}
    a_id = ids[cols["a"]]
    b_id = np.where(cols["b"] >= 0, ids[cols["b"]], -1)
    edge = b_id >= 0
    out = {"t": cols["t"], "kind": cols["kind"],
           "src": np.where(edge, np.minimum(a_id, b_id), a_id),
           "dst": np.where(edge, np.maximum(a_id, b_id), -1)}
    counts = {"nodes": n, "static_edges": int(n_pairs),
              "person_deletes": len(gone),
              "knows_deletes": int(del8.sum()),
              "cascade_deletes": int(cascade.sum()),
              "events": len(cols["t"]),
              "time_span_days": round(float(cols["t"].max()
                                            - cols["t"].min()) / DAY, 3)}
    return {"cols": out, "counts": counts}


def history(cfg: dict) -> dict:
    """Event columns of the configuration's history in the store's
    layout, sorted by time, with ``counts`` of what was produced."""
    s = structure(cfg)
    cols = s["cols"]
    n_ev = len(cols["t"])
    out = {"t": cols["t"].astype(np.int64),
           "kind": cols["kind"].astype(np.int8),
           "src": cols["src"].astype(np.int32),
           "dst": cols["dst"].astype(np.int32),
           "key": np.full(n_ev, -1, np.int16),
           "val": np.where(cols["kind"] == EDGE_ADD, 1, -1).astype(np.int32)}
    return {"cols": out, "counts": s["counts"]}


class _Lives:
    """Each person's and each pair's one life, ``[start, end)``, read
    from the columns of a history that writes a person or a pair at
    most once each way (as this one does)."""

    def __init__(self, cols: dict):
        t, kind = np.asarray(cols["t"], np.int64), np.asarray(cols["kind"])
        src = np.asarray(cols["src"], np.int64)
        dst = np.asarray(cols["dst"], np.int64)
        self.n = n = int(max(src.max(), dst.max())) + 1
        never = np.iinfo(np.int64).max
        self.born = np.full(n, never)
        self.gone = np.full(n, never)
        self.born[src[kind == NODE_ADD]] = t[kind == NODE_ADD]
        self.gone[src[kind == NODE_DEL]] = t[kind == NODE_DEL]
        add, rm = kind == EDGE_ADD, kind == EDGE_DEL
        self.u, self.v, self.start = src[add], dst[add], t[add]
        key = self.u * n + self.v
        order = np.argsort(key)
        self.end = np.full(len(key), never)
        self.end[order[np.searchsorted(key, src[rm] * n + dst[rm],
                                       sorter=order)]] = t[rm]

    def present(self, t: int) -> np.ndarray:
        return (self.born <= t) & (t < self.gone)

    def live(self, t: int) -> np.ndarray:
        return (self.start <= t) & (t < self.end)


def _depth(lives: _Lives, members: np.ndarray, t: int) -> int:
    """The deepest hop distance, at ``t``, from any present member to the
    least member of its component: the rounds min-label propagation over
    member rows (rows in id order) needs to settle."""
    n = lives.n
    on = members & lives.present(t)
    e = lives.live(t) & on[lives.u] & on[lives.v]
    # a source joined to the least present member of every component
    g = sparse.coo_matrix((np.ones(int(e.sum())), (lives.u[e], lives.v[e])),
                          shape=(n + 1, n + 1)).tocsr()
    _, comp = csgraph.connected_components(g, directed=False)
    rows = np.nonzero(on)[0]
    least = np.full(n + 1, n + 1)
    np.minimum.at(least, comp[rows], rows)
    roots = np.unique(least[comp[rows]])
    g = g + sparse.coo_matrix((np.ones(len(roots)), (np.full(len(roots), n),
                                                     roots)),
                              shape=(n + 1, n + 1)).tocsr()
    dist = csgraph.shortest_path(g, directed=False, unweighted=True,
                                 indices=n)
    return int(dist[rows].max()) - 1 if len(rows) else 0


def _points(lo: int, hi: int, inside: int = 32) -> np.ndarray:
    return np.unique(np.r_[lo, hi, lo + (hi - lo) * np.arange(1, inside + 1)
                           // (inside + 1)])


def tails(cols: dict, spans=()) -> dict:
    """The tail statistics that set the operand shapes and the depth of
    components: the most partners of one person, the most events of one
    person, the most writes of one pair (each changes its existence: a
    pair is made once and deleted at most once), and the deepest hop
    distance from a present person to its component's least row (at the
    ends and at 32 even timepoints inside).  Over the whole history every person
    is a member; in each ``(lo, hi)`` span the members are the persons
    present at ``lo``, and events count with ``lo < t <= hi``."""
    lives = _Lives(cols)
    t = np.asarray(cols["t"], np.int64)
    src = np.asarray(cols["src"], np.int64)
    dst = np.asarray(cols["dst"], np.int64)
    e = dst >= 0
    pair = src[e] * lives.n + dst[e]

    def events(sel, members):
        ends = np.r_[src[sel], dst[sel & e]]
        ends = ends[members[ends]]
        return int(np.bincount(ends).max()) if len(ends) else 0

    def changes(sel):
        p = pair[sel[e]]
        return int(np.unique(p, return_counts=True)[1].max()) if len(p) else 0

    everyone = np.ones(lives.n, bool)
    out = {"max_partners": int(np.bincount(np.r_[lives.u, lives.v]).max()),
           "max_person_events": events(np.ones(len(t), bool), everyone),
           "max_pair_changes": changes(np.ones(len(t), bool)),
           "max_component_depth": max(
               _depth(lives, everyone, int(x))
               for x in _points(int(t.min()), int(t.max())))}
    for lo, hi in spans:
        sel = (t > lo) & (t <= hi)
        members = lives.present(lo)
        out.setdefault("windows", []).append({
            "max_person_events": events(sel, members),
            "max_pair_changes": changes(sel),
            "max_component_depth": max(_depth(lives, members, int(x))
                                       for x in _points(lo, hi))})
    return out
