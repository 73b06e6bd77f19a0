"""PageRank over a window's subgraphs at each timepoint (fused device
program, float32), against the plain float64 power iteration.

Compared: ``pagerank_gap``, the widest gap between the program's score
and the reference's, in units of the mean score ``1/n`` at that
timepoint (n the members present).  Its limit lies between the largest
gap that sound runs read and the smallest that the bfloat16 control
reads (PERF.md gives both readings)."""
from __future__ import annotations

import numpy as np

from chipbench import counts, fused
from reference import analytics as ra

LIMITS = {"pagerank_gap": 2e-2}


def run(store, req: dict, params: dict):
    from repro.taf import compile as tc

    return fused.run(store.subgraphs(req["lo"], req["hi"]).node_compute(
        tc.pagerank(params["damping"], params["pagerank_iters"]),
        style="temporal", points=req["ts"]))


answer = fused.series


def expect(ref, req: dict, params: dict) -> dict:
    win = ref.window(req["lo"])
    graphs = [win.graph_at(int(t)) for t in req["ts"]]
    score = np.stack([ra.pagerank(*g, params["damping"],
                                  params["pagerank_iters"]) for g in graphs],
                     axis=-1)
    return {"score": score,
            "n_active": np.asarray([g[0].sum() for g in graphs]),
            "n_live": np.asarray([len(g[1]) for g in graphs])}


def compare(req: dict, got, want: dict) -> tuple:
    got = np.asarray(got, np.float64)
    if got.shape != want["score"].shape:
        return "pagerank_gap", float("inf")
    gap = np.abs(got - want["score"]) * np.maximum(want["n_active"], 1)[None, :]
    return "pagerank_gap", float(gap.max()) if gap.size else 0.0


def control(ref, req: dict, params: dict) -> np.ndarray:
    """The reference's PageRank put in the program's place and computed
    in bfloat16, the precision below the float32 the configuration
    states.  It runs on whatever device JAX has, as a program would."""
    import jax
    import jax.numpy as jnp

    damping, iters = params["damping"], params["pagerank_iters"]
    win = ref.window(req["lo"])
    bf = jnp.bfloat16

    @jax.jit
    def one(act, src, dst):
        N = act.shape[0]  # dst == N marks padding, summed into a spare row
        deg = jax.ops.segment_sum(jnp.ones_like(src, bf), dst, N + 1)[:N]
        n = jnp.maximum(act.sum(), 1).astype(bf)
        r = act / n
        dmask = act * (deg == 0)
        inv = jnp.where(deg > 0, 1 / jnp.maximum(deg, 1), 0).astype(bf)
        for _ in range(iters):
            nxt = jax.ops.segment_sum((r * inv)[src], dst, N + 1)[:N]
            dangling = (r * dmask).sum()
            r = act * ((1 - damping) / n + damping * (nxt + dangling / n))
        return r

    graphs = [win.graph_at(int(t)) for t in req["ts"]]
    width = 1 << int(np.ceil(np.log2(max(2 * max(len(g[1]) for g in graphs),
                                         2))))
    cols = []
    for active, u, v in graphs:
        src = np.zeros(width, np.int32)
        dst = np.full(width, win.N, np.int32)
        src[:2 * len(u)] = np.r_[u, v]
        dst[:2 * len(u)] = np.r_[v, u]
        r = one(jnp.asarray(active, bf), jnp.asarray(src), jnp.asarray(dst))
        cols.append(np.asarray(r.astype(jnp.float32), np.float64))
    return np.stack(cols, axis=-1)


def least(ref, req: dict, want: dict, params: dict, peak: dict) -> dict:
    return counts.pagerank_least(ref.window(req["lo"]), req["lo"], req["hi"],
                                 want["n_live"], params["pagerank_iters"],
                                 peak)
