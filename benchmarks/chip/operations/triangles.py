"""Triangles per member at each timepoint (fused device program on the
``temporal_motif`` kernel where the dense stack fits).  Compared
exactly: ``triangles_mismatch``, the counts that differ from the plain
reference's, limit 0."""
from __future__ import annotations

from chipbench import fused
from reference import analytics as ra

LIMITS = {"triangles_mismatch": 0}


def run(store, req: dict, params: dict):
    from repro.taf import compile as tc

    return fused.run(store.subgraphs(req["lo"], req["hi"]).node_compute(
        tc.triangles(), style="temporal", points=req["ts"]))


answer = fused.series


def expect(ref, req: dict, params: dict):
    return ref.window(req["lo"]).series(req["ts"], ra.triangles)


def compare(req: dict, got, want) -> tuple:
    return "triangles_mismatch", fused.mismatch(got, want)
