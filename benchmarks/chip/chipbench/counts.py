"""Least work of a query, from its shapes, against the chip's peaks.

PageRank over a window ``[lo, hi]`` at T timepoints: any implementation
has to read the window's operand once (each member's event rows in
``(lo, hi]`` as time int64, kind int8 and partner int32, and each
member's initial neighbours as int32), write the (N, T) float32 result
once, and do ``2 * iters * sum_t live_edges(t)`` operations (one
multiply-add per direction of each live edge per iteration).  The least
time is the larger of operations over the peak rate and bytes over the
memory bandwidth; it bounds every implementation from below.
"""
from __future__ import annotations

import numpy as np

EVENT_ROW_BYTES = 8 + 1 + 4
NEIGHBOUR_BYTES = 4
RESULT_BYTES = 4


def pagerank_least(win, lo: int, hi: int, n_live, iters: int,
                   peak: dict) -> dict:
    hist = win.hist
    sel = (hist.t > lo) & (hist.t <= hi)
    rows = int((win.row[hist.src[sel]] >= 0).sum())
    d = hist.dst[sel]
    rows += int((win.row[d[d >= 0]] >= 0).sum())
    _, u, _ = win.graph_at(lo)
    T = len(n_live)
    nbytes = (rows * EVENT_ROW_BYTES + 2 * len(u) * NEIGHBOUR_BYTES
              + win.N * T * RESULT_BYTES)
    flops = 2 * iters * int(np.sum(n_live))
    t_flops = flops / peak["flops_bf16"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes, "bound": "memory" if t_bytes >= t_flops
            else "compute"}
