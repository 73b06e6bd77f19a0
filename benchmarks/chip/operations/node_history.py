"""One node's history over an interval (``store.node_history``: its
state at the start and every event touching it after).  Compared
exactly: ``node_history_mismatch``, a wrong initial presence plus the
events missing or extra against the plain replay's, limit 0."""
from __future__ import annotations

from types import SimpleNamespace

from chipbench import retrieval

LIMITS = {"node_history_mismatch": 0}
cost = retrieval.cost


def run(store, req: dict, params: dict):
    return store.node_history(req["nid"], req["t"], req["t1"])


def answer(res):
    return res


def expect(ref, req: dict, params: dict) -> tuple:
    return (ref.hist.present_at(req["t"]),
            ref.hist.node_events(req["nid"], req["t"], req["t1"]))


def _rows(log) -> list:
    return sorted(zip(*(list(map(int, getattr(log, k)))
                        for k in ("t", "kind", "src", "dst", "val"))))


def compare(req: dict, got, want: tuple) -> tuple:
    present0, ev = want
    init, log = got
    bad = int((init is not None) != bool(present0[req["nid"]]))
    got_rows, want_rows = _rows(log), _rows(SimpleNamespace(**ev))
    if got_rows != want_rows:
        bad += max(len(set(got_rows) ^ set(want_rows)), 1)
    return "node_history_mismatch", bad


def control(ref, req: dict, params: dict):
    present0, ev = expect(ref, retrieval.stale(req), params)
    return ({} if present0[req["nid"]] else None), SimpleNamespace(**ev)
