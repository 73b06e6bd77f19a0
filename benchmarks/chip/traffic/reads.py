"""The ``reads`` traffic kind: point retrievals of the history, one
client waiting on each answer.

A mix of this kind (``traffic/<mix>.json`` with ``"kind": "reads"``)
gives ``block``, the operations of one block and how many times each
runs in it (every block holds exactly those counts, in an order drawn
from the seed), and ``params``:

* ``batch_timepoints`` and ``batch_days``: a batch of snapshots folds
  that many evenly spaced seconds of one day, on one of the listed days
  (fractions of the history), so every seed folds the same kernel
  shapes;
* ``history_days``: the length of a node history;
* ``k``: the hops of a neighbourhood;
* ``zipf_exponent``: nodes are drawn by final degree, the ``r``-th
  busiest with weight ``r ** -zipf_exponent``.

Single timepoints are drawn uniformly over the history.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

DAY = 86_400


def windows(mix: dict, time_range) -> list:
    return []


def block(mix: dict) -> list:
    return [(op, 0) for op, n in mix["block"].items() for _ in range(n)]


def batch_days(mix: dict, time_range) -> list:
    t0, t1 = time_range
    p = mix["params"]
    step = DAY // p["batch_timepoints"]
    return [int(t0 + f * (t1 - t0 - DAY))
            + np.arange(p["batch_timepoints"], dtype=np.int64) * step
            for f in p["batch_days"]]


def node_rank(cols: dict) -> np.ndarray:
    """Node ids by final degree (distinct partners), highest first."""
    e = cols["dst"] >= 0
    key = np.unique(cols["src"][e].astype(np.int64) * (1 << 32) + cols["dst"][e])
    deg = np.bincount(np.r_[key >> 32, key & 0xFFFFFFFF].astype(np.int64))
    return np.argsort(-deg, kind="stable")


def _request(rng, mix: dict, time_range, op: str, rank, pz) -> dict:
    t0, t1 = time_range
    p = mix["params"]
    req = {"op": op}
    if op == "snapshots":
        days = batch_days(mix, time_range)
        req["ts"] = days[int(rng.integers(len(days)))]
    else:
        req["t"] = int(rng.integers(t0, t1 + 1))
    if op in ("node_history", "k_hop"):
        req["nid"] = int(rank[rng.choice(len(rank), p=pz)])
    if op == "node_history":
        span = int(p["history_days"] * DAY)
        req["t"] = min(req["t"], t1 - span)
        req["t1"] = req["t"] + span
    return req


def _zipf(mix: dict, n: int) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -mix["params"]["zipf_exponent"]
    return w / w.sum()


def requests(mix: dict, time_range, seed: int, cols: dict) -> Iterator[dict]:
    """Endless requests, whole blocks in an order drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    rank = node_rank(cols)
    pz = _zipf(mix, len(rank))
    slots = block(mix)
    while True:
        for i in rng.permutation(len(slots)):
            yield _request(rng, mix, time_range, slots[i][0], rank, pz)


def warm(mix: dict, time_range, seed: int, cols: dict) -> list:
    """A batch of snapshots on every listed day (each folds its own
    kernel shapes) and one request of every other operation."""
    rng = np.random.default_rng([seed, 3])
    rank = node_rank(cols)
    pz = _zipf(mix, len(rank))
    out = [{"op": "snapshots", "ts": ts} for ts in batch_days(mix, time_range)
           if "snapshots" in mix["block"]]
    return out + [_request(rng, mix, time_range, op, rank, pz)
                  for op in mix["block"] if op != "snapshots"]
