"""Jit'd wrapper for the temporal PageRank kernel: node-axis padding to
the 128-lane tile."""
from __future__ import annotations

import jax.numpy as jnp

from repro.device import interpret
from repro.kernels.temporal_pagerank import ref
from repro.kernels.temporal_pagerank.temporal_pagerank import (
    LANE,
    pagerank_pallas,
)


def pad_nodes(adj, active):
    """Pad the node axis to a multiple of 128 (padded nodes inactive,
    no incident edges — they cannot perturb live ranks/labels/counts)."""
    adj = jnp.asarray(adj, jnp.float32)
    active = jnp.asarray(active, jnp.float32)
    N = adj.shape[-1]
    pad = (-N) % LANE
    if pad:
        adj = jnp.pad(adj, ((0, 0), (0, pad), (0, pad)))
        active = jnp.pad(active, ((0, 0), (0, pad)))
    return adj, active, N


def temporal_pagerank(adj, active, damping: float = 0.85, iters: int = 20,
                      use_pallas: bool = True):
    """Ranks (T, N) f32 at every timepoint from dense adjacency.

    adj: (T, N, N) symmetric 0/1 adjacency (zero diagonal);
    active: (T, N) present mask.  Accepts numpy or jnp.  Runs the Pallas
    kernel, or the pure-jnp reference with ``use_pallas=False``.
    """
    if not use_pallas:
        return ref.pagerank_ref(adj, active, damping=damping, iters=iters)
    padded, act, N = pad_nodes(adj, active)
    out = pagerank_pallas(padded, act, damping=damping, iters=iters,
                          interpret=interpret())
    return out[:, :N]
