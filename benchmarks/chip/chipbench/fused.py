"""How an analytics operation asks the store: one query over a window's
subgraphs at the request's timepoints, which has to run as one fused
device program."""
from __future__ import annotations

import numpy as np


def run(query):
    res = query.run()
    if not any(n.startswith("compile: fused") for n in res.notes):
        raise RuntimeError(f"the query did not run as one device program: "
                           f"{res.notes}")
    return res


def series(res) -> np.ndarray:
    """The (N, T) or (T,) series of a ``node_compute`` or ``evolution``
    result, as numpy."""
    return np.asarray(res.value[1])


def mismatch(got, want) -> int:
    """Entries that differ, or 1 where the shapes differ."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return 1
    return int((got != want).sum())
