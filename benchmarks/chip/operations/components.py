"""Connected components over a window's subgraphs at each timepoint
(fused device program), each member labelled by its component's least
member row, absent members -1.  Compared exactly: ``components_mismatch``,
the labels that differ from the plain reference's, limit 0."""
from __future__ import annotations

from chipbench import fused
from reference import analytics as ra

LIMITS = {"components_mismatch": 0}


def run(store, req: dict, params: dict):
    from repro.taf import compile as tc

    return fused.run(store.subgraphs(req["lo"], req["hi"]).node_compute(
        tc.components(params["components_iters"]), style="temporal",
        points=req["ts"]))


answer = fused.series


def expect(ref, req: dict, params: dict):
    return ref.window(req["lo"]).series(req["ts"], ra.components)


def compare(req: dict, got, want) -> tuple:
    return "components_mismatch", fused.mismatch(got, want)
