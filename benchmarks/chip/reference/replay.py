"""Plain replay of an event log: the state of the graph at any second.

Last write wins per node and per undirected pair: a node is present at
``t`` when its last ``NODE_ADD``/``NODE_DEL`` at or before ``t`` is an
add; a pair exists when its last ``EDGE_ADD``/``EDGE_DEL`` is an add, and
its value is that add's value.  Node attributes are never written by the
configured histories, so every attribute reads -1 (unset); a log that
writes them is refused rather than replayed wrongly.

Independent of the store under test: numpy only.
"""
from __future__ import annotations

import numpy as np

NODE_ADD, NODE_DEL, EDGE_ADD, EDGE_DEL = 0, 1, 2, 3
N_ATTRS = 4  # the store's default attribute slots per node


class _LastWrite:
    """Per-entity event runs, sorted by (entity, time, log order), asked
    for the index of each entity's last event at or before ``t``."""

    def __init__(self, entity: np.ndarray, t: np.ndarray):
        order = np.lexsort((np.arange(len(t)), t, entity))
        self.order = order
        ent, tt = entity[order], t[order]
        self.ids, self.start = np.unique(ent, return_index=True)
        self.end = np.r_[self.start[1:], len(ent)]
        self._span = int(tt.max() - tt.min() + 2) if len(tt) else 1
        self._t0 = int(tt.min()) if len(tt) else 0
        rank = np.repeat(np.arange(len(self.ids)), self.end - self.start)
        self._comp = rank.astype(np.int64) * self._span + (tt - self._t0)

    def last_at(self, t: int) -> np.ndarray:
        """(n_entities,) position into ``order`` of the last event at or
        before ``t``, -1 where there is none."""
        q = np.clip(int(t) - self._t0, -1, self._span - 1)
        keys = np.arange(len(self.ids), dtype=np.int64) * self._span + q
        pos = np.searchsorted(self._comp, keys, side="right") - 1
        return np.where(pos >= self.start, pos, -1)


class History:
    """The configured event log, replayed on demand."""

    def __init__(self, cols: dict):
        kind = np.asarray(cols["kind"])
        known = np.isin(kind, (NODE_ADD, NODE_DEL, EDGE_ADD, EDGE_DEL))
        if not known.all():
            raise ValueError("the reference replays node and edge adds and "
                             "deletes only; this log writes attributes")
        self.t = np.asarray(cols["t"], np.int64)
        self.kind = kind
        self.src = np.asarray(cols["src"], np.int64)
        self.dst = np.asarray(cols["dst"], np.int64)
        self.val = np.asarray(cols["val"], np.int64)
        self.n_nodes = int(max(self.src.max(), self.dst.max())) + 1
        nmask = (kind == NODE_ADD) | (kind == NODE_DEL)
        self._n_idx = np.nonzero(nmask)[0]
        self._nodes = _LastWrite(self.src[nmask], self.t[nmask])
        emask = ~nmask
        self._e_idx = np.nonzero(emask)[0]
        key = (np.minimum(self.src, self.dst) * self.n_nodes
               + np.maximum(self.src, self.dst))[emask]
        self._edges = _LastWrite(key, self.t[emask])
        self.pair_u = self._edges.ids // self.n_nodes
        self.pair_v = self._edges.ids % self.n_nodes

    def present_at(self, t: int) -> np.ndarray:
        """(n_nodes,) bool."""
        pos = self._nodes.last_at(t)
        ev = self._n_idx[self._nodes.order[np.maximum(pos, 0)]]
        out = np.zeros(self.n_nodes, bool)
        out[self._nodes.ids] = (pos >= 0) & (self.kind[ev] == NODE_ADD)
        return out

    def pairs_at(self, t: int):
        """(exists, value) over ``pair_u``/``pair_v``, each (n_pairs,)."""
        pos = self._edges.last_at(t)
        ev = self._e_idx[self._edges.order[np.maximum(pos, 0)]]
        exists = (pos >= 0) & (self.kind[ev] == EDGE_ADD)
        return exists, np.where(exists, self.val[ev], -1)

    def snapshot(self, t: int) -> dict:
        """Present nodes and the sorted edge list (u < v) with values."""
        exists, val = self.pairs_at(t)
        return {"present": self.present_at(t), "u": self.pair_u[exists],
                "v": self.pair_v[exists], "val": val[exists]}

    def node_events(self, nid: int, t0: int, t1: int) -> dict:
        """Events touching ``nid`` with t in (t0, t1], in log order."""
        sel = (((self.src == nid) | (self.dst == nid))
               & (self.t > t0) & (self.t <= t1))
        idx = np.nonzero(sel)[0]
        return {"t": self.t[idx], "kind": self.kind[idx],
                "src": self.src[idx], "dst": self.dst[idx],
                "val": self.val[idx]}


def k_hop(snap: dict, nid: int, k: int) -> dict:
    """The subgraph induced on the nodes within ``k`` hops of ``nid``
    (over the snapshot's edges), with their presence."""
    keep = np.zeros(len(snap["present"]), bool)
    keep[nid] = True
    frontier = keep.copy()
    u, v = snap["u"], snap["v"]
    for _ in range(k):
        nxt = np.zeros_like(keep)
        nxt[v[frontier[u]]] = True
        nxt[u[frontier[v]]] = True
        frontier = nxt & ~keep
        keep |= nxt
    m = keep[u] & keep[v]
    return {"nodes": np.nonzero(keep)[0], "present": snap["present"] & keep,
            "u": u[m], "v": v[m], "val": snap["val"][m]}
