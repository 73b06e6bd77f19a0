"""Jit'd wrapper: padding, head layout, interpret/native dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.device import interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k"))
def flash_attention(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                    blk_q=128, blk_k=128):
    """q: (B,H,Sq,D); k,v: (B,H,Sk,D) (KV pre-expanded to H heads);
    q_pos (Sq,), k_pos (Sk,).  Pads S to block multiples; padded k rows
    carry k_pos=-1 (masked), padded q rows are sliced off."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    blk_q = min(blk_q, max(Sq, 8))
    blk_k = min(blk_k, max(Sk, 8))
    pq = (-Sq) % blk_q
    pk = (-Sk) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    qpos = jnp.pad(q_pos.astype(jnp.int32), (0, pq))
    kpos = jnp.pad(k_pos.astype(jnp.int32), (0, pk), constant_values=-1)
    out = flash_attention_pallas(
        qp, kp, vp, qpos, kpos, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, interpret=interpret()
    )
    return out[:, :, :Sq]
