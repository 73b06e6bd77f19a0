"""Jit'd wrapper for the temporal connected-components kernel: node-axis
padding."""
from __future__ import annotations

from repro.device import interpret
from repro.kernels.temporal_cc import ref
from repro.kernels.temporal_cc.temporal_cc import cc_pallas
from repro.kernels.temporal_pagerank.ops import pad_nodes


def temporal_cc(adj, active, iters: int = 32, use_pallas: bool = True):
    """Component labels (T, N) int32 at every timepoint from dense
    adjacency (min member-row index per component after ``iters``
    propagation rounds; -1 on inactive nodes).

    adj: (T, N, N) symmetric 0/1 adjacency; active: (T, N) mask.
    Accepts numpy or jnp.  Runs the Pallas kernel, or the pure-jnp
    reference with ``use_pallas=False``.
    """
    if not use_pallas:
        return ref.cc_ref(adj, active, iters=iters)
    padded, act, N = pad_nodes(adj, active)
    out = cc_pallas(padded, act, iters=iters, interpret=interpret())
    return out[:, :N]
