"""Pallas TPU kernel: temporal connected components — min-label
propagation per timepoint batch.

Each node starts with its own row index as label; every round folds the
minimum label over the node's neighborhood (a masked min over the dense
adjacency tile — the VPU-wide min-fold variant of a psum), and labels
monotonically shrink to the component minimum.  ``iters`` rounds resolve
every component whose diameter is <= iters; the fused jnp path and the
host reference run the identical bounded propagation, so results are
bit-identical (int32) by construction.

Grid: (T,).  Blocks are (1, N, N) adjacency + (1, N) activity per
timepoint, N a multiple of 128 (ops.py pads).  Inactive (and padded)
nodes take label -1 and never win a min.  Validated in interpret mode
against ref.cc_ref.  It does not lower for a TPU yet: the (1, N) activity
block breaks the (8, 128) block rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _cc_kernel(adj_ref, active_ref, out_ref, *, iters: int):
    a = adj_ref[0]  # (N, N) f32 symmetric, zero diagonal
    act = active_ref[0] != 0  # (1, N)
    N = a.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    big = jnp.int32(N)  # sentinel: larger than any real label
    labels = jnp.where(act, iota, big)
    edge = a > 0  # (N, N); edges only join active endpoints
    for _ in range(iters):  # static unroll
        # min label over each node's neighborhood: broadcast labels down
        # the source axis, mask by adjacency, min-fold the columns
        src = jnp.broadcast_to(labels.reshape(-1, 1), (N, N))
        neigh = jnp.min(jnp.where(edge, src, big), axis=0, keepdims=True)
        labels = jnp.minimum(labels, neigh)
    out_ref[...] = jnp.where(act, labels, -1).reshape(out_ref.shape)


def cc_pallas(adj, active, iters: int = 32, interpret: bool = True):
    """adj: (T, N, N) f32 symmetric dense adjacency; active: (T, N) mask.
    Returns labels (T, N) int32 — min member-row index per component
    after ``iters`` propagation rounds, -1 on inactive nodes.  N must be
    a multiple of 128 (ops.py pads)."""
    T, N, _ = adj.shape
    assert N % LANE == 0, N
    return pl.pallas_call(
        functools.partial(_cc_kernel, iters=int(iters)),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, N), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, N), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, N), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.int32),
        interpret=interpret,
    )(adj.astype(jnp.float32), active.astype(jnp.float32))
