"""The ``interactions`` history: a vectorised generator of a temporal
interaction network from its configuration file.  It is the history of
every configuration that names none (``"history"`` in the file).

The history (which pairs interact, how often, and when) is drawn from
the configuration alone, with its ``assumed.structure_seed``, to the
published counts: every node, every temporal edge and every distinct
directed pair the source lists.  It stands where a replay of the
source's edge list would stand, and like such a replay it is the same
for every ``--seed``: the seed draws the traffic.  So every run stores
the same history and uses the same operand and kernel shapes, and
nothing compiles after the first run of a cell.

The shape of the history (degree skew, repeats per pair, growth and
burstiness) is not published; the configuration states it under
``assumed``, with the tail statistics it yields (``assumed.tails``,
from ``tails``), which set the padded operand shapes of the fused
programs.

Mapping of an interaction network onto the store's events:

* each interaction ``(u, v, t)`` is one ``EDGE_ADD`` at second ``t`` of
  the undirected pair ``(min(u, v), max(u, v))``; a repeated pair re-adds
  the existing edge, which the store applies as a value update;
* a node's first interaction is preceded, at the same second, by its
  ``NODE_ADD``;
* the source has no deletions, so none are generated.

Columns are returned as plain numpy arrays in the store's event column
layout (``t, kind, src, dst, key, val``); the caller wraps them.
"""
from __future__ import annotations

import numpy as np

NODE_ADD, EDGE_ADD = 0, 2  # the store's event kinds (core/events.py)
DAY = 86_400


def _weights(rng, n: int, exponent: float) -> np.ndarray:
    """Zipf-like activity weights ``rank**-exponent`` in a random node
    order, normalised to sum 1."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return w[rng.permutation(n)] / w.sum()


def _pairs(rng, n: int, n_pairs: int, w_out, w_in) -> np.ndarray:
    """``n_pairs`` distinct directed pairs (u != v), drawn by weight,
    with every node in at least one pair.  Returns (n_pairs, 2)."""
    # one forced pair per node so that every node interacts at least once
    partner = rng.choice(n, size=n, p=w_in)
    partner = np.where(partner == np.arange(n), (partner + 1) % n, partner)
    out_first = rng.random(n) < 0.5
    forced = np.where(out_first[:, None],
                      np.stack([np.arange(n), partner], 1),
                      np.stack([partner, np.arange(n)], 1))
    keys = forced[:, 0].astype(np.int64) * n + forced[:, 1]
    seen = np.unique(keys)
    want = n_pairs - len(seen)
    while want > 0:
        m = int(want * 1.5) + 1024
        u = rng.choice(n, size=m, p=w_out)
        v = rng.choice(n, size=m, p=w_in)
        k = u.astype(np.int64) * n + v
        k = k[u != v]
        _, first = np.unique(k, return_index=True)
        k = k[np.sort(first)]  # draw order, duplicates dropped
        k = k[~np.isin(k, seen)][:want]
        seen = np.union1d(seen, k)
        keys = np.concatenate([keys, k])
        want = n_pairs - len(seen)
    _, first = np.unique(keys, return_index=True)
    keys = keys[np.sort(first)][:n_pairs]
    return np.stack([keys // n, keys % n], 1)


def structure(cfg: dict) -> dict:
    """The history as abstract node indices, from the configuration
    alone.  Returns columns sorted by time (``NODE_ADD`` before the
    interactions of its second) and the counts produced."""
    pub, a = cfg["published"], cfg["assumed"]
    n, n_ev, n_pairs = (pub["nodes"], pub["temporal_edges"],
                        pub["static_edges"])
    span = pub["time_span_days"] * DAY
    rng = np.random.default_rng(a["structure_seed"])
    w_out = _weights(rng, n, a["out_degree_exponent"])
    w_in = _weights(rng, n, a["in_degree_exponent"])
    pairs = _pairs(rng, n, n_pairs, w_out, w_in)
    # interactions per pair: one each, the rest by a skewed pair weight
    pw = (w_out[pairs[:, 0]] * w_in[pairs[:, 1]]) ** a["repeat_exponent"]
    reps = 1 + rng.multinomial(n_ev - n_pairs, pw / pw.sum())
    # a pair starts on a growth curve and repeats in a burst after it
    start = span * rng.random(n_pairs) ** (1.0 / a["growth_exponent"])
    pair_of = np.repeat(np.arange(n_pairs), reps)
    first = np.r_[0, np.cumsum(reps)[:-1]]
    lag = rng.exponential(a["burst_days"] * DAY, n_ev)
    lag[first] = 0.0
    # a burst that would run past the end of the history wraps back into
    # the pair's own lifetime, so no second piles up at the end
    life = np.maximum(span - start[pair_of], 1.0)
    t = (start[pair_of] + np.mod(lag, life)).astype(np.int64)
    types = np.asarray(list(a["edge_value_shares"]), np.int64)
    shares = np.asarray(list(a["edge_value_shares"].values()), np.float64)
    val = types[rng.choice(len(types), size=n_ev, p=shares / shares.sum())]
    u, v = pairs[pair_of, 0], pairs[pair_of, 1]
    # NODE_ADD at each node's first interaction
    first_t = np.full(n, np.iinfo(np.int64).max)
    np.minimum.at(first_t, u, t)
    np.minimum.at(first_t, v, t)
    cols = {
        "t": np.concatenate([first_t, t]),
        "kind": np.concatenate([np.full(n, NODE_ADD), np.full(n_ev, EDGE_ADD)]),
        "src": np.concatenate([np.arange(n), np.minimum(u, v)]),
        "dst": np.concatenate([np.full(n, -1), np.maximum(u, v)]),
        "val": np.concatenate([np.full(n, -1), val]),
    }
    order = np.argsort(cols["t"], kind="stable")  # NODE_ADDs come first
    cols = {k: c[order] for k, c in cols.items()}
    undirected = np.unique(np.minimum(pairs[:, 0], pairs[:, 1]) * n
                           + np.maximum(pairs[:, 0], pairs[:, 1]))
    counts = {"nodes": int(len(np.unique(np.r_[pairs[:, 0], pairs[:, 1]]))),
              "temporal_edges": int(n_ev), "static_edges": int(len(pairs)),
              "undirected_pairs": int(len(undirected)),
              "time_span_days": round(float(t.max() - t.min()) / DAY, 3)}
    return {"cols": cols, "counts": counts}


def history(cfg: dict) -> dict:
    """Event columns of the configuration's history in the store's
    layout, sorted by time, with ``counts`` of what was produced."""
    s = structure(cfg)
    cols = s["cols"]
    out = {"t": cols["t"], "kind": cols["kind"].astype(np.int8),
           "src": cols["src"].astype(np.int32),
           "dst": cols["dst"].astype(np.int32),
           "key": np.full(len(cols["t"]), -1, np.int16),
           "val": cols["val"].astype(np.int32)}
    return {"cols": out, "counts": s["counts"]}


def tails(cols: dict, spans=()) -> dict:
    """The tail statistics of a history that set the operand shapes: the
    most distinct partners of one node, the most interactions of one
    pair, the most events of one node; and in each ``(lo, hi)`` span,
    the most events of one node and the most interactions of one pair
    with ``lo < t <= hi``."""
    e = cols["dst"] >= 0
    src, dst, t = (cols["src"][e].astype(np.int64),
                   cols["dst"][e].astype(np.int64), cols["t"][e])
    n = int(max(src.max(), dst.max())) + 1
    pair = src * n + dst

    def most(sel):
        ends = np.r_[src[sel], dst[sel]]
        return (int(np.bincount(ends).max()) if len(ends) else 0,
                int(np.unique(pair[sel], return_counts=True)[1].max())
                if sel.any() else 0)

    distinct = np.unique(pair)
    node_events, pair_events = most(np.ones(len(t), bool))
    out = {"max_partners": int(np.bincount(np.r_[distinct // n,
                                                 distinct % n]).max()),
           "max_pair_interactions": pair_events,
           "max_node_interactions": node_events}
    for lo, hi in spans:
        ne, pe = most((t > lo) & (t <= hi))
        out.setdefault("windows", []).append(
            {"max_node_interactions": ne, "max_pair_interactions": pe})
    return out
