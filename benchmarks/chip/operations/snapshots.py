"""The graph at a batch of seconds (``store.snapshots(ts,
use_kernel=True)``: the batched ``delta_overlay`` fold on the device).
Compared exactly: ``snapshots_mismatch``, snapshots missing or extra
plus nodes and edges that differ from the plain replay's, limit 0."""
from __future__ import annotations

from chipbench import retrieval

LIMITS = {"snapshots_mismatch": 0}
cost = retrieval.cost


def run(store, req: dict, params: dict):
    return store.snapshots(req["ts"], use_kernel=True)


def answer(res):
    return res


def expect(ref, req: dict, params: dict) -> list:
    return [ref.hist.snapshot(int(t)) for t in req["ts"]]


def compare(req: dict, got, want: list) -> tuple:
    bad = abs(len(got) - len(want))
    bad += sum(retrieval.mismatch(g, w) for g, w in zip(got, want))
    return "snapshots_mismatch", bad


def control(ref, req: dict, params: dict):
    return [retrieval.Graph(w)
            for w in expect(ref, retrieval.stale(req), params)]
