"""The ``analytics`` traffic kind: multi-timepoint queries over fixed
windows, one client waiting on each answer.

A mix of this kind (``traffic/<mix>.json`` with ``"kind": "analytics"``)
gives:

* ``windows``: each as ``[a, b]``, fractions of the history's time
  range.  They are fixed, so every seed uses the same operand shapes;
* ``timepoints``: how many timepoints a query asks for, drawn fresh and
  sorted inside its window for every query;
* ``block``: the operations of one block, each with how many times it
  runs on every window.  Every block holds exactly those counts, in an
  order drawn from the seed, so every seed does the same work;
* ``params``: the operations' parameters.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np


def windows(mix: dict, time_range) -> list:
    t0, t1 = time_range
    return [(int(t0 + a * (t1 - t0)), int(t0 + b * (t1 - t0)))
            for a, b in mix["windows"]]


def block(mix: dict) -> list:
    """One block: ``(operation, window)`` for each operation, its count
    of times on every window."""
    return [(op, w) for op, n in mix["block"].items() for _ in range(n)
            for w in range(len(mix["windows"]))]


def _request(rng, mix: dict, wins: list, op: str, w: int) -> dict:
    lo, hi = wins[w]
    ts = np.sort(lo + rng.choice(hi - lo + 1, mix["timepoints"],
                                 replace=False))
    return {"op": op, "window": w, "lo": lo, "hi": hi,
            "ts": ts.astype(np.int64)}


def requests(mix: dict, time_range, seed: int, cols: dict) -> Iterator[dict]:
    """Endless requests, whole blocks in an order drawn from ``seed``
    (``cols``, the history's event columns, are not needed here)."""
    rng = np.random.default_rng([seed, 1])
    wins = windows(mix, time_range)
    slots = block(mix)
    while True:
        for i in rng.permutation(len(slots)):
            yield _request(rng, mix, wins, *slots[i])


def warm(mix: dict, time_range, seed: int, cols: dict) -> list:
    """One request of each operation on each window: every shape the
    timed requests use."""
    rng = np.random.default_rng([seed, 3])
    wins = windows(mix, time_range)
    return [_request(rng, mix, wins, op, w)
            for op in mix["block"] for w in range(len(wins))]
