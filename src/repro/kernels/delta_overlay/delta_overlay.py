"""Pallas TPU kernel: fused h-way last-writer-wins delta overlay.

Snapshot reconstruction (paper Alg. 1) folds h snapshot deltas + e
eventlist deltas.  A naive chain does h+e HBM round-trips over the slot
tiles; this kernel reads all h stacked tiles into VMEM once and writes a
single output tile — bandwidth-optimal for the memory-bound fold.

Layout: slots S on the lanes, partitions P on the sublanes.  valid and
present are (h, P, S); attrs are key-major (h, K, P, S), so every
attribute key is a (P, tile_s) plane shaped like valid and the fold is
elementwise over whole planes — no broadcast between layouts.  Grid:
(S // tile_s,); each block holds the whole h, K and P axes, which
satisfies the TPU's (8, 128) block rule for any P.  ``ops.py`` pads S,
picks tile_s and transposes attrs in and out.  The time-batched variant
reads its layer->timepoint mask from SMEM and writes (T, P, S) /
(T, K, P, S), T a leading axis.  Natively compiled on a TPU backend,
interpreted elsewhere (``repro.device.interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _fold(vi, pi, ais, acc_p, acc_as):
    """One last-writer-wins step over (P, tile_s) planes: a valid layer
    slot overwrites presence, set attrs (!= -1) overwrite, and a slot
    left absent clears every attr."""
    acc_p = jnp.where(vi, pi, acc_p)
    gone = acc_p == 0
    acc_as = tuple(jnp.where(gone, -1, jnp.where(vi & (ai != -1), ai, acc))
                   for ai, acc in zip(ais, acc_as))
    return acc_p, acc_as


def _overlay_kernel(valid_ref, present_ref, attrs_ref,
                    o_valid_ref, o_present_ref, o_attrs_ref, *, h: int, K: int):
    acc_v = valid_ref[0]  # (P, tile_s)
    acc_p = present_ref[0]
    acc_a = tuple(attrs_ref[0, k] for k in range(K))
    for i in range(1, h):  # static unroll: h is small
        vi = valid_ref[i] != 0
        acc_p, acc_a = _fold(vi, present_ref[i],
                             [attrs_ref[i, k] for k in range(K)], acc_p, acc_a)
        acc_v = jnp.maximum(acc_v, vi.astype(acc_v.dtype))
    o_valid_ref[...] = acc_v
    o_present_ref[...] = acc_p
    for k in range(K):
        o_attrs_ref[k] = acc_a[k]


def overlay_pallas(valid, present, attrs, tile_s: int, interpret: bool = True):
    """valid/present: (h, P, S) int32; attrs: (h, K, P, S) int32.
    Returns valid/present (P, S) and attrs (K, P, S).  S must be a
    multiple of tile_s, itself a multiple of 128 (ops.py pads)."""
    h, P, S = valid.shape
    K = attrs.shape[1]
    assert tile_s % LANE == 0 and S % tile_s == 0, (S, tile_s)
    vp_spec = pl.BlockSpec((h, P, tile_s), lambda s: (0, 0, s))
    at_spec = pl.BlockSpec((h, K, P, tile_s), lambda s: (0, 0, 0, s))
    out_vp = pl.BlockSpec((P, tile_s), lambda s: (0, s))
    out_at = pl.BlockSpec((K, P, tile_s), lambda s: (0, 0, s))
    return pl.pallas_call(
        functools.partial(_overlay_kernel, h=h, K=K),
        grid=(S // tile_s,),
        in_specs=[vp_spec, vp_spec, at_spec],
        out_specs=[out_vp, out_vp, out_at],
        out_shape=[
            jax.ShapeDtypeStruct((P, S), valid.dtype),
            jax.ShapeDtypeStruct((P, S), present.dtype),
            jax.ShapeDtypeStruct((K, P, S), attrs.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="delta_overlay",
    )(valid, present, attrs)


# ---------------------------------------------------------------------------
# Time-batched variant: one launch folds T timepoints over shared layers
# ---------------------------------------------------------------------------


def _overlay_batch_kernel(tmask_ref, valid_ref, present_ref, attrs_ref,
                          o_valid_ref, o_present_ref, o_attrs_ref,
                          *, h: int, T: int, K: int):
    """Per output timepoint t, fold the stacked layers whose
    ``tmask[i, t]`` bit is set (neutral start: valid=0/present=0/attrs=-1)
    with the same last-writer-wins overlay as ``_overlay_kernel``.  The
    stacked tiles are read into VMEM ONCE and reused for every timepoint
    — the bandwidth saving over T independent launches.  h and T are
    loops, not unrolls: a group's h grows with its T."""
    zero = jnp.zeros_like(valid_ref[0])  # (P, tile_s)

    def per_t(t, carry):
        def per_layer(i, acc):
            acc_v, acc_p, acc_a = acc
            vi = (valid_ref[i] != 0) & (tmask_ref[i, t] != 0)
            acc_p, acc_a = _fold(vi, present_ref[i],
                                 [attrs_ref[i, k] for k in range(K)],
                                 acc_p, acc_a)
            return jnp.maximum(acc_v, vi.astype(acc_v.dtype)), acc_p, acc_a

        acc_v, acc_p, acc_a = jax.lax.fori_loop(
            0, h, per_layer, (zero, zero, (zero - 1,) * K))
        o_valid_ref[t] = acc_v
        o_present_ref[t] = acc_p
        for k in range(K):
            o_attrs_ref[t, k] = acc_a[k]
        return carry

    jax.lax.fori_loop(0, T, per_t, 0)


def overlay_batch_pallas(valid, present, attrs, tmask, tile_s: int,
                         interpret: bool = True):
    """valid/present: (h, P, S) int32; attrs: (h, K, P, S) int32;
    tmask: (h, T) int32 layer->timepoint validity mask (SMEM).  Returns
    valid/present (T, P, S) and attrs (T, K, P, S).  S must be a multiple
    of tile_s, itself a multiple of 128 (ops.py pads)."""
    h, P, S = valid.shape
    K = attrs.shape[1]
    T = tmask.shape[-1]
    assert tile_s % LANE == 0 and S % tile_s == 0, (S, tile_s)
    mk_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    vp_spec = pl.BlockSpec((h, P, tile_s), lambda s: (0, 0, s))
    at_spec = pl.BlockSpec((h, K, P, tile_s), lambda s: (0, 0, 0, s))
    out_vp = pl.BlockSpec((T, P, tile_s), lambda s: (0, 0, s))
    out_at = pl.BlockSpec((T, K, P, tile_s), lambda s: (0, 0, 0, s))
    return pl.pallas_call(
        functools.partial(_overlay_batch_kernel, h=h, T=T, K=K),
        grid=(S // tile_s,),
        in_specs=[mk_spec, vp_spec, vp_spec, at_spec],
        out_specs=[out_vp, out_vp, out_at],
        out_shape=[
            jax.ShapeDtypeStruct((T, P, S), valid.dtype),
            jax.ShapeDtypeStruct((T, P, S), present.dtype),
            jax.ShapeDtypeStruct((T, K, P, S), attrs.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="delta_overlay_batch",
    )(tmask, valid, present, attrs)
