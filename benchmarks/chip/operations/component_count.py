"""The number of connected components among present members at each
timepoint (``evolution``, fused device program).  Compared exactly:
``component_count_mismatch``, the timepoints whose count differs from
the plain reference's, limit 0."""
from __future__ import annotations

from chipbench import fused
from reference import analytics as ra

LIMITS = {"component_count_mismatch": 0}


def run(store, req: dict, params: dict):
    from repro.taf import compile as tc

    return fused.run(store.subgraphs(req["lo"], req["hi"]).evolution(
        tc.component_count(params["components_iters"]), points=req["ts"]))


answer = fused.series


def expect(ref, req: dict, params: dict):
    return ref.window(req["lo"]).series(req["ts"], ra.component_count)


def compare(req: dict, got, want) -> tuple:
    return "component_count_mismatch", fused.mismatch(got, want)
