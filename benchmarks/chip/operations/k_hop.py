"""A node's neighbourhood within ``k`` hops at one second
(``store.k_hop``).  Compared exactly: ``k_hop_mismatch``, nodes and
edges among the reference's neighbourhood that differ from the plain
replay's, limit 0."""
from __future__ import annotations

from chipbench import retrieval
from reference import replay as rr

LIMITS = {"k_hop_mismatch": 0}
cost = retrieval.cost


def run(store, req: dict, params: dict):
    return store.k_hop(req["nid"], req["t"], k=params["k"])


def answer(res):
    return res


def expect(ref, req: dict, params: dict) -> dict:
    return rr.k_hop(ref.hist.snapshot(req["t"]), req["nid"], params["k"])


def compare(req: dict, got, want: dict) -> tuple:
    return "k_hop_mismatch", retrieval.mismatch(got, want, nodes=want["nodes"])


def control(ref, req: dict, params: dict):
    return retrieval.Graph(expect(ref, retrieval.stale(req), params))
