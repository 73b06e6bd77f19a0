"""Jit'd wrapper for the temporal motif kernel: node-axis padding to the
kernel's tile."""
from __future__ import annotations

import jax.numpy as jnp

from repro.device import interpret
from repro.kernels.temporal_motif import ref
from repro.kernels.temporal_motif.temporal_motif import block_for, motif_pallas


def temporal_motif(adj, use_pallas: bool = True):
    """Per-node triangle counts (T, N) int32 at every timepoint from
    dense adjacency.

    adj: (T, N, N) symmetric 0/1 adjacency (zero diagonal).  Accepts
    numpy or jnp.  Runs the Pallas kernel, or the pure-jnp reference
    with ``use_pallas=False``.
    """
    if not use_pallas:
        return ref.motif_ref(adj)
    adj = jnp.asarray(adj)
    N = adj.shape[-1]
    pad = (-N) % block_for(N)
    if pad:
        adj = jnp.pad(adj, ((0, 0), (0, pad), (0, pad)))
    return motif_pallas(adj, interpret=interpret())[:, :N]
