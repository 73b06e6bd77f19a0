"""Shared by the point-read operations: the comparison of a store
``GraphState`` with a reference snapshot, the control's stale answer
dressed as one, and the retrieval cost a read reports."""
from __future__ import annotations

import numpy as np

STALE_S = 86_400  # the control's lag: one day


def mismatch(g, want: dict, nodes=None) -> int:
    """Nodes whose presence differs plus edges missing, extra or with
    another value, between a store ``GraphState`` and a reference
    snapshot, over ``nodes`` when given."""
    n = max(len(g.present), len(want["present"]))
    got_p = np.zeros(n, bool)
    got_p[:len(g.present)] = np.asarray(g.present) == 1
    want_p = np.zeros(n, bool)
    want_p[:len(want["present"])] = want["present"]
    if nodes is not None:
        sel = np.zeros(n, bool)
        sel[nodes] = True
        got_p &= sel
        want_p &= sel
    bad = int((got_p != want_p).sum())
    src, dst, val = g.edges()
    gk = np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)
    wk = want["u"].astype(np.int64) * n + want["v"].astype(np.int64)
    common, gi, wi = np.intersect1d(gk, wk, return_indices=True)
    bad += (len(gk) - len(common)) + (len(wk) - len(common))
    bad += int((np.asarray(val)[gi] != want["val"][wi]).sum())
    return bad


class Graph:
    """A reference snapshot dressed as the store's ``GraphState``."""

    def __init__(self, snap: dict):
        self.present = snap["present"].astype(np.int8)
        self._edges = (snap["u"], snap["v"], snap["val"])

    def edges(self):
        return self._edges


def stale(req: dict) -> dict:
    """The request one day earlier: what a lagging replica would answer."""
    old = dict(req)
    for k in ("t", "t1"):
        if k in old:
            old[k] = old[k] - STALE_S
    if "ts" in old:
        old["ts"] = old["ts"] - STALE_S
    return old


def cost(store) -> dict:
    """Raw bytes the last read decoded and raw bytes it took from the
    decoded-block pool (``FetchCost``)."""
    c = store.last_cost
    return {"raw": c.n_bytes_decompressed, "pool": c.n_bytes_pool}
