"""Jit'd wrapper for the rglru_scan kernel: padding + dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.device import interpret
from repro.kernels.rglru_scan.rglru_scan import CHUNK, TILE_W, rglru_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "tile_w"))
def rglru(log_a, b, chunk=CHUNK, tile_w=TILE_W):
    """h_t = exp(log_a_t) h_{t-1} + b_t over axis 1.  (B,S,W) f32.

    Padding: S padded with log_a=0, b=0 (state passthrough, sliced off);
    W padded with zero lanes."""
    B, S, W = log_a.shape
    chunk = min(chunk, max(S, 8))
    tile_w = min(tile_w, max(W, 8))
    ps = (-S) % chunk
    pw = (-W) % tile_w
    la = jnp.pad(log_a.astype(jnp.float32), ((0, 0), (0, ps), (0, pw)))
    bb = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, ps), (0, pw)))
    h = rglru_pallas(la, bb, chunk=chunk, tile_w=tile_w, interpret=interpret())
    return h[:, :S, :W]
