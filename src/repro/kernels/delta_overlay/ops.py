"""Jit'd wrapper for the delta_overlay kernel: dtype handling, slot-axis
padding, tile choice and the (…, S, K) <-> key-major transposes around
the kernel's lane-dense layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import trace
from repro.device import interpret
from repro.kernels.delta_overlay import ref
from repro.kernels.delta_overlay.delta_overlay import (
    LANE,
    overlay_batch_pallas,
    overlay_pallas,
)

# double-buffered block bytes one grid step may hold in VMEM, and the
# widest slot tile (the batched kernel carries K+2 (P, tile_s) planes in
# vector registers through its layer loop)
VMEM_BUDGET = 8 << 20
MAX_TILE_S = 512


def _tile_s(S_pad: int, lane_bytes: int) -> int:
    """Widest multiple of 128 that divides ``S_pad`` and keeps the
    double-buffered blocks (``lane_bytes`` per slot column) in budget."""
    fit = max(LANE, min(MAX_TILE_S, VMEM_BUDGET // (2 * lane_bytes)))
    m = S_pad // LANE
    d = max(x for x in range(1, m + 1) if m % x == 0 and x * LANE <= fit)
    return d * LANE


def _prep(valid, present, attrs):
    """int32 (h, P, S_pad) planes + key-major (h, K, P, S_pad) attrs."""
    S = valid.shape[-1]
    pad = (-S) % LANE
    v = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, 0), (0, pad)))
    p = jnp.pad(present.astype(jnp.int32), ((0, 0), (0, 0), (0, pad)))
    a = jnp.pad(jnp.transpose(attrs, (0, 3, 1, 2)),
                ((0, 0), (0, 0), (0, 0), (0, pad)), constant_values=-1)
    return v, p, a


@functools.partial(jax.jit, static_argnames="interpret")
def _overlay(valid, present, attrs, interpret):
    h, P, S = valid.shape
    K = attrs.shape[-1]
    v, p, a = _prep(valid, present, attrs)
    tile = _tile_s(v.shape[-1], 4 * (h + 1) * P * (2 + K))
    ov, op, oa = overlay_pallas(v, p, a, tile, interpret=interpret)
    return (ov[:, :S] != 0, op[:, :S].astype(present.dtype),
            jnp.transpose(oa[:, :, :S], (1, 2, 0)))


@functools.partial(jax.jit, static_argnames="interpret")
def _overlay_batch(valid, present, attrs, tmask, interpret):
    h, P, S = valid.shape
    K = attrs.shape[-1]
    T = tmask.shape[-1]
    v, p, a = _prep(valid, present, attrs)
    tile = _tile_s(v.shape[-1], 4 * (h + T) * P * (2 + K))
    ov, op, oa = overlay_batch_pallas(v, p, a, tmask, tile,
                                      interpret=interpret)
    return (jnp.transpose(ov[..., :S], (1, 2, 0)) != 0,
            jnp.transpose(op[..., :S], (1, 2, 0)).astype(present.dtype),
            jnp.transpose(oa[..., :S], (2, 3, 0, 1)))


def overlay(valid, present, attrs, use_pallas: bool = True):
    """Fold stacked deltas (h, P, S[, K]) -> (P, S[, K]); valid comes
    back bool.  Accepts numpy or jnp."""
    with trace.span("overlay.dispatch"):
        valid = jnp.asarray(valid)
        present = jnp.asarray(present)
        attrs = jnp.asarray(attrs)
        if not use_pallas:
            return ref.overlay_ref(valid, present, attrs)
        return _overlay(valid, present, attrs, interpret=interpret())


def overlay_batch(valid, present, attrs, tmask, use_pallas: bool = True):
    """Time-batched fold: stacked deltas (h, P, S[, K]) + layer->timepoint
    mask (h, T) -> per-timepoint outputs (P, S, T[, K]); valid comes back
    bool.

    Timepoint t folds exactly the layers with ``tmask[i, t]`` set
    (typically: every shared hierarchy-path layer + that timepoint's own
    eventlist layer).  Accepts numpy or jnp; runs the Pallas kernel, or
    the pure-jnp reference with ``use_pallas=False``.
    """
    with trace.span("overlay.dispatch"):
        valid = jnp.asarray(valid)
        present = jnp.asarray(present)
        attrs = jnp.asarray(attrs)
        tmask = jnp.asarray(tmask, jnp.int32)
        if not use_pallas:
            out_v, out_p, out_a = ref.overlay_batch_ref(
                valid.astype(jnp.int8), present, attrs, tmask)
            return out_v != 0, out_p, out_a
        return _overlay_batch(valid, present, attrs, tmask,
                              interpret=interpret())
