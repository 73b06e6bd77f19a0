"""Plain multi-timepoint analytics over replayed snapshots.

A query over the window ``[lo, hi]`` has as members the nodes present at
``lo``, in increasing id order (one row each).  At each timepoint ``t``
the graph is the snapshot at ``t`` restricted to members present at
``t``: an edge counts when its pair exists and both endpoints are
present members.  Over that graph:

* PageRank: damped power iteration, a fixed number of iterations, the
  mass of nodes without edges spread uniformly, absent members 0;
* components: connected components, each labelled by its least member
  row, absent members -1;
* component count: the number of components among present members;
* triangles: per member, the triangles it is in;
* timeslice: each member's presence, and its attributes (-1, unset).

numpy and scipy only; independent of the store under test.
"""
from __future__ import annotations

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

from reference.replay import N_ATTRS, History


class Window:
    """Members of ``[lo, hi]`` and the graph among them at any ``t``."""

    def __init__(self, hist: History, lo: int):
        self.hist = hist
        self.members = np.nonzero(hist.present_at(lo))[0]
        row = np.full(hist.n_nodes, -1, np.int64)
        row[self.members] = np.arange(len(self.members))
        self.row = row
        self.N = len(self.members)

    def graph_at(self, t: int):
        """(active (N,) bool, u, v) with u < v member rows of live edges."""
        present = self.hist.present_at(t)
        active = present[self.members]
        exists, _ = self.hist.pairs_at(t)
        ru = self.row[self.hist.pair_u[exists]]
        rv = self.row[self.hist.pair_v[exists]]
        ok = (ru >= 0) & (rv >= 0) & (ru != rv)
        ru, rv = ru[ok], rv[ok]
        ok = active[ru] & active[rv]
        return active, ru[ok], rv[ok]

    def series(self, ts, fn) -> np.ndarray:
        return np.stack([fn(*self.graph_at(int(t))) for t in ts], axis=-1)


class Windows:
    """The reference over one history: each window's members, made once
    for every request on that window."""

    def __init__(self, hist: History):
        self.hist = hist
        self._windows = {}

    def window(self, lo: int) -> Window:
        if lo not in self._windows:
            self._windows[lo] = Window(self.hist, lo)
        return self._windows[lo]


def pagerank(active, u, v, damping: float = 0.85, iters: int = 20,
             dtype=np.float64) -> np.ndarray:
    N = len(active)
    act = active.astype(dtype)
    n = max(float(act.sum()), 1.0)
    deg = (np.bincount(u, minlength=N) + np.bincount(v, minlength=N))
    r = act / dtype(n)
    dangling_mask = act * (deg == 0)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0).astype(dtype)
    for _ in range(iters):
        contrib = r * inv
        nxt = (np.bincount(v, weights=contrib[u], minlength=N)
               + np.bincount(u, weights=contrib[v], minlength=N)).astype(dtype)
        dangling = (r * dangling_mask).sum()
        r = act * ((1.0 - damping) / n + damping * (nxt + dangling / n))
    return r


def components(active, u, v) -> np.ndarray:
    N = len(active)
    a = sparse.coo_matrix((np.ones(len(u)), (u, v)), shape=(N, N)).tocsr()
    _, comp = csgraph.connected_components(a, directed=False)
    least = np.full(comp.max() + 1 if N else 0, N, np.int64)
    rows = np.nonzero(active)[0]
    np.minimum.at(least, comp[rows], rows)
    return np.where(active, least[comp], -1)


def component_count(active, u, v) -> int:
    labels = components(active, u, v)
    return int((labels == np.arange(len(active))).sum())


def triangles(active, u, v) -> np.ndarray:
    N = len(active)
    a = sparse.coo_matrix((np.ones(len(u)), (u, v)), shape=(N, N)).tocsr()
    a = a + a.T
    return np.asarray((a @ a).multiply(a).sum(axis=1)).ravel() / 2.0


def timeslice(win: Window, ts) -> dict:
    present = np.stack([win.hist.present_at(int(t))[win.members]
                        for t in ts], axis=1).astype(np.int8)
    attrs = np.full((win.N, len(ts), N_ATTRS), -1, np.int32)
    return {"present": present, "attrs": attrs}
