"""Each member's presence and attributes at each timepoint
(``timeslice``, one device program).  Compared exactly:
``timeslice_mismatch``, the entries that differ from the plain
reference's, limit 0."""
from __future__ import annotations

import numpy as np

from chipbench import fused
from reference import analytics as ra

LIMITS = {"timeslice_mismatch": 0}


def run(store, req: dict, params: dict):
    return fused.run(store.nodes(req["lo"], req["hi"]).timeslice(req["ts"]))


def answer(res) -> dict:
    return {k: np.asarray(res.value[k]) for k in ("present", "attrs")}


def expect(ref, req: dict, params: dict) -> dict:
    return ra.timeslice(ref.window(req["lo"]), req["ts"])


def compare(req: dict, got, want) -> tuple:
    return "timeslice_mismatch", sum(fused.mismatch(got[k], want[k])
                                     for k in ("present", "attrs"))
