"""Pallas TPU kernel: temporal PageRank — power iteration per timepoint
batch over dense per-timepoint adjacency tiles.

The whole-plan compiler (repro.taf.compile) materializes the operand's
``EdgeReplay`` pair table at T timepoints; this kernel runs the damped
power iteration for every timepoint in one launch.  Layout choice: on
TPU the per-timepoint graph becomes a dense (N, N) float32 tile so every
iteration's gather-scatter (rank mass flowing along edges) is ONE MXU
matmul — the dense tile is the csr_at gather re-laid-out for the
systolic array, and it stays resident in VMEM across all ``iters``
iterations (the fused jnp path in taf.compile uses the equivalent
pair-table gather/scatter formulation; both are parity-tested).

Grid: (T,).  Blocks are (1, N, N) adjacency + (1, N) activity per
timepoint, N a multiple of 128 (ops.py pads; padded nodes are inactive).
Validated in interpret mode against ref.pagerank_ref.  It does not lower
for a TPU yet: the (1, N) activity block breaks the (8, 128) block rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _pagerank_kernel(adj_ref, active_ref, out_ref, *, damping: float,
                     iters: int):
    a = adj_ref[0]  # (N, N) f32, symmetric, zero diagonal
    act = active_ref[0].astype(jnp.float32)  # (1, N)
    # symmetric adjacency: column sums == row sums == degree
    deg = jnp.sum(a, axis=0, keepdims=True)  # (1, N)
    n = jnp.maximum(jnp.sum(act), 1.0)  # live node count (scalar)
    r = act / n
    dangling_mask = act * (deg == 0).astype(jnp.float32)
    for _ in range(iters):  # static unroll: iters is small
        contrib = jnp.where(deg > 0, r / jnp.maximum(deg, 1.0), 0.0)
        nxt = jnp.dot(contrib, a, preferred_element_type=jnp.float32)
        dangling = jnp.sum(r * dangling_mask)
        r = act * ((1.0 - damping) / n + damping * (nxt + dangling / n))
    out_ref[...] = r.reshape(out_ref.shape)


def pagerank_pallas(adj, active, damping: float = 0.85, iters: int = 20,
                    interpret: bool = True):
    """adj: (T, N, N) f32 symmetric dense adjacency (zero diagonal);
    active: (T, N) int8/f32 node-present mask.  Returns ranks (T, N) f32
    (0 on inactive nodes).  N must be a multiple of 128 (ops.py pads)."""
    T, N, _ = adj.shape
    assert N % LANE == 0, N
    return pl.pallas_call(
        functools.partial(_pagerank_kernel, damping=float(damping),
                          iters=int(iters)),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, N), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, N), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, N), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        interpret=interpret,
    )(adj.astype(jnp.float32), active.astype(jnp.float32))
