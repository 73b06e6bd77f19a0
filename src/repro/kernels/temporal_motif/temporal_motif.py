"""Pallas TPU kernel: temporal triangle/motif counting per timepoint
batch over the packed pair table's dense adjacency.

Per-node triangle participation at timepoint t is diag(A_t^3) / 2; the
kernel computes column j of it as sum_i (A^2)[i, j] * A[i, j] / 2 —
counting, for every incident edge, the common neighbors that close a
wedge into a triangle.  A^2 is a blocked MXU matmul over (block, block)
bf16 tiles (0/1 entries are exact in bf16) with an f32 VMEM accumulator;
each finished (i, j) tile is reduced over its rows and added to the
int32 output row of block j.  Partial sums stay below 2^24, so counts
are exact, and the fast memory one step holds does not grow with N.

Grid: (T, N/block, N/block, N/block) over (t, j, i, k); the output block
(1, 1, block) of (t, j) stays resident while i and k run.  N is a
multiple of the block (ops.py pads; padded nodes have no edges).
Natively compiled on a TPU backend, interpreted elsewhere
(``repro.device.interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK = 512


def block_for(n: int) -> int:
    """Tile edge for an N-node adjacency: N rounded up to 128 lanes, at
    most ``BLOCK``."""
    return min(BLOCK, -(-n // LANE) * LANE)


def _motif_kernel(a_ik_ref, a_kj_ref, a_ij_ref, out_ref, acc_ref):
    i, k = pl.program_id(2), pl.program_id(3)
    last_i, last_k = pl.num_programs(2) - 1, pl.num_programs(3) - 1

    @pl.when((i == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ik_ref[0], a_kj_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == last_k)
    def _():
        closed = jnp.sum(acc_ref[...] * a_ij_ref[0].astype(jnp.float32),
                         axis=0, keepdims=True)  # (1, block) wedges closed
        out_ref[0] += closed.astype(jnp.int32)

    @pl.when((i == last_i) & (k == last_k))
    def _():
        out_ref[...] = jnp.right_shift(out_ref[...], 1)  # each triangle twice


def motif_pallas(adj, interpret: bool = True):
    """adj: (T, N, N) symmetric 0/1 dense adjacency (zero diagonal).
    Returns per-node triangle counts (T, N) int32.  N must be a multiple
    of ``block_for(N)`` (ops.py pads)."""
    T, N, _ = adj.shape
    b = block_for(N)
    assert N % b == 0, (N, b)
    nb = N // b
    out = pl.pallas_call(
        _motif_kernel,
        grid=(T, nb, nb, nb),
        in_specs=[
            pl.BlockSpec((1, b, b), lambda t, j, i, k: (t, i, k)),
            pl.BlockSpec((1, b, b), lambda t, j, i, k: (t, k, j)),
            pl.BlockSpec((1, b, b), lambda t, j, i, k: (t, i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, b), lambda t, j, i, k: (t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((T, 1, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((b, b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="temporal_motif",
    )(*(adj.astype(jnp.bfloat16),) * 3)
    return out[:, 0, :]
