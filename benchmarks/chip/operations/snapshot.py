"""The graph at one second (``store.snapshot``: index fetch, decode and
fold on the host).  Compared exactly: ``snapshot_mismatch``, nodes and
edges that differ from the plain replay's, limit 0."""
from __future__ import annotations

from chipbench import retrieval

LIMITS = {"snapshot_mismatch": 0}
cost = retrieval.cost


def run(store, req: dict, params: dict):
    return store.snapshot(req["t"])


def answer(res):
    return res


def expect(ref, req: dict, params: dict) -> dict:
    return ref.hist.snapshot(req["t"])


def compare(req: dict, got, want: dict) -> tuple:
    return "snapshot_mismatch", retrieval.mismatch(got, want)


def control(ref, req: dict, params: dict):
    return retrieval.Graph(expect(ref, retrieval.stale(req), params))
