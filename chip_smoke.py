#!/usr/bin/env python3
"""On-chip smoke test of the historical graph store's main path.

    python chip_smoke.py [--events 250000] [--seed 0] [--chips 1|4]

One process drives the store through its user entry points on a TPU and
checks every answer against an independent reference:

  1. device     -- JAX must see TPU devices, else exit non-zero;
  2. build      -- ``HistoricalGraphStore.build`` over a generated history;
  3. retrieval  -- snapshots (vs. ``naive_state_at``), node history and
                   2-hop neighbourhood of the highest-degree node;
  4. device fold -- batched snapshots through the ``delta_overlay``
                   kernel, bit-identical to the host fold;
  5. fused analytics -- timeslice, PageRank, components, component-count
                   evolution and triangles (``temporal_motif`` kernel),
                   each compiled into one device program and matched
                   against the staged host path;
  6. served path -- a 3-cell, r=2 subprocess storage cluster serving a
                   store whose answers match an in-process one.

``--chips 4`` runs only the multi-chip path: the degree series under
shard_map over four devices, with operands placed shard by shard,
against the host replay.  The seconds printed per phase are smoke
timings of one cold run (compilation included), not metrics.  Any failed
check raises and exits non-zero.  The last line of stdout is the JSON
verdict ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.data.temporal_graph_gen import generate, naive_state_at  # noqa: E402
from repro.service.cluster import ClusterSpec, LocalCluster  # noqa: E402
from repro.taf import HistoricalGraphStore, compile as tc, replay  # noqa: E402

# PageRank runs in f32 on the device and f64 on the host
PAGERANK_RTOL = 1e-4
PAGERANK_ATOL = 1e-9
CFG = dict(n_shards=4, parts_per_shard=2)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(n, name: str, t_start: float, **facts) -> None:
    body = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"phase {n} {name}: ok {body} "
          f"(smoke timing {time.perf_counter() - t_start:.2f} s)", flush=True)


def states_equal(got, want, what: str) -> None:
    n = max(len(got.present), len(want.present))
    got, want = got.copy(), want.copy()
    got.grow(n)
    want.grow(n)
    check((got.present == want.present).all(), f"{what}: presence differs")
    on = got.present == 1
    check((got.attrs[on] == want.attrs[on]).all(), f"{what}: attrs differ")
    check(np.array_equal(got.edge_key, want.edge_key), f"{what}: edges differ")
    check(np.array_equal(got.edge_val, want.edge_val),
          f"{what}: edge values differ")


def window(store, frac: float = 0.75):
    """[t0 + frac * span, t1]: membership is the nodes alive at the
    window's start, so a late window holds most of the graph."""
    t0, t1 = store.time_range()
    return int(t0 + frac * (t1 - t0)), int(t1)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    t = time.perf_counter()
    from repro.device import interpret, use_compile_cache

    cache = use_compile_cache(ROOT)
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu", f"no TPU found: JAX sees {devs}")
    check(len(devs) >= chips, f"--chips {chips} but {len(devs)} devices")
    check(not interpret(), "Pallas kernels would run interpreted")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    report(1, "device", t, kind=repr(dev["kind"]), count=dev["count"],
           compile_cache=cache)
    return dev


def phase_build(n_events: int, seed: int):
    t = time.perf_counter()
    events = generate(n_events, seed=seed)
    store = HistoricalGraphStore.build(events, **CFG)
    report(2, "build", t, events=len(events), nodes=events.n_nodes,
           spans=len(store.tgi.spans),
           totals=store.storage_report()["totals"])
    return events, store


def phase_retrieval(events, store) -> None:
    t = time.perf_counter()
    t0, t1 = store.time_range()
    K = store.cfg.n_attrs
    ts = [int(t0 + f * (t1 - t0)) for f in (0.3, 0.6, 0.95)]
    want = {}
    for tt in ts:
        want[tt] = naive_state_at(events, tt, K)
        states_equal(store.snapshot(tt), want[tt], f"snapshot@{tt}")
    ta, tb = ts[1], ts[2]
    hub = int(np.argmax(want[tb].degree()))
    init, hist = store.node_history(hub, ta, tb)
    if want[ta].present[hub]:
        check(init is not None and (init["attrs"] == want[ta].attrs[hub]).all(),
              "node_history: initial state differs")
    sel = (((events.src == hub) | (events.dst == hub))
           & (events.t > ta) & (events.t <= tb))
    check(len(hist) == int(sel.sum()) and (hist.t == events.t[sel]).all(),
          "node_history: events differ")
    kh = store.k_hop(hub, tb, k=2)
    states_equal(kh, store.tgi._filter_k_hop(want[tb], hub, 2), "k_hop(2)")
    report(3, "retrieval", t, timepoints=ts, hub=hub, hub_events=len(hist),
           khop_nodes=int(kh.present.sum()), khop_edges=len(kh.edge_key))


def phase_device_fold(store) -> None:
    t = time.perf_counter()
    tgi = store.tgi
    t0, t1 = store.time_range()
    # 4 clusters of 8 timepoints 5 ticks apart: (span, leaf) groups with
    # T > 1 take the time-batched kernel branch
    ts = [int(t0 + f * (t1 - t0)) + 5 * j
          for f in (0.2, 0.45, 0.7, 0.9) for j in range(8)]
    with tgi.read_guard() as view:
        groups = {}
        for tt in ts:
            si = tgi._span_index(tt, view)
            key = (si.span.tsid, tgi._leaf_for(si, tt))
            groups[key] = groups.get(key, 0) + 1
    check(max(groups.values()) > 1, "no (span, leaf) group holds T > 1")
    tgi.invalidate_caches()
    host = store.snapshots(ts, use_kernel=False)
    tgi.invalidate_caches()
    dev = store.snapshots(ts, use_kernel=True)
    for tt, a, b in zip(ts, dev, host):
        for f in ("present", "attrs", "edge_key", "edge_val"):
            check(np.array_equal(getattr(a, f), getattr(b, f)),
                  f"kernel fold @{tt}: {f} differs from the host fold")
    report(4, "device fold", t, timepoints=len(ts), groups=len(groups),
           max_group_T=max(groups.values()), identical=True)


def _both(q, tag: str):
    """Run a query fused and staged; the fused run must carry a
    ``compile: fused`` note and the staged one must not."""
    fused = q.run()
    with tc.disabled():
        staged = q.run()
    check(any(n.startswith("compile: fused") for n in fused.notes),
          f"{tag}: not fused: {fused.notes}")
    check(not any("fused" in n for n in staged.notes),
          f"{tag}: staged run fused: {staged.notes}")
    return fused, staged


def phase_fused(store) -> None:
    t = time.perf_counter()
    lo, hi = window(store)
    sub = store.subgraphs(lo, hi).materialize()
    sots = sub.operand
    N = len(sots)
    pairs = replay.edge_replay(sots).n_pairs
    emax = int(np.diff(sots.ev_indptr).max())
    n_nodes = int(sots.node_ids.max()) + 1
    check(2 * N > n_nodes, f"window [{lo}, {hi}] holds {N} of {n_nodes} nodes")
    ts64 = np.linspace(lo, hi, 64).astype(np.int64)
    ts16 = ts64[::4]
    notes = {}

    f, s = _both(store.nodes(lo, hi).materialize().timeslice(list(ts64)),
                 "timeslice")
    for k in ("present", "attrs"):
        check(np.array_equal(f.value[k], s.value[k]), f"timeslice: {k}")
    notes["timeslice"] = f.notes[-1]

    f, s = _both(sub.node_compute(tc.pagerank(), style="temporal",
                                  points=ts64), "pagerank")
    err = np.abs(f.value[1] - s.value[1])
    check(np.allclose(f.value[1], s.value[1], rtol=PAGERANK_RTOL,
                      atol=PAGERANK_ATOL),
          f"pagerank: max abs err {err.max()} beyond rtol {PAGERANK_RTOL}")
    notes["pagerank"] = f.notes[-1]

    f, s = _both(sub.node_compute(tc.components(), style="temporal",
                                  points=ts64), "components")
    check(np.array_equal(f.value[1], s.value[1]), "components differ")
    notes["components"] = f.notes[-1]

    f, s = _both(sub.evolution(tc.component_count(), points=ts16),
                 "evolution")
    check(np.array_equal(f.value[1], s.value[1]), "component counts differ")
    notes["evolution"] = f.notes[-1]

    # triangles over the highest-degree members: a dense operand with
    # 512 <= N <= 1024 (fewer only if the window holds fewer) at T=32,
    # so T * N^2 stays inside compile.DENSE_BUDGET
    deg = np.diff(sots.adj_indptr)
    top = sots.node_ids[np.argsort(-deg, kind="stable")[:1024]]
    tri_q = store.subgraphs(lo, hi).filter(node_ids=top).materialize()
    n_tri = len(tri_q.operand)
    check(min(512, N) <= n_tri <= 1024, f"triangle operand N={n_tri}")
    f, s = _both(tri_q.node_compute(tc.triangles(), style="temporal",
                                    points=ts64[::2]), "triangles")
    check(np.array_equal(f.value[1], s.value[1]), "triangle counts differ")
    notes["triangles"] = f.notes[-1]

    report(5, "fused analytics", t, window=[lo, hi], N=N, pairs=pairs, emax=emax,
           pagerank_max_abs_err=float(err.max()), triangle_N=n_tri,
           triangles_total=int(f.value[1][:, -1].sum() // 3),
           notes=notes)


def phase_served(cluster, n_events: int, seed: int) -> None:
    t = time.perf_counter()
    events = generate(n_events, seed=seed)
    remote = cluster.client()
    try:
        served = HistoricalGraphStore.build(events, store=remote, **CFG)
        local = HistoricalGraphStore.build(events, **CFG)
        t0, t1 = local.time_range()
        tm = int(t0 + 0.6 * (t1 - t0))
        states_equal(served.snapshot(tm), local.snapshot(tm), "served snapshot")
        lo, hi = window(local)
        ts = np.linspace(lo, hi, 16).astype(np.int64)
        got = served.subgraphs(lo, hi).node_compute(
            tc.components(), style="temporal", points=ts).run()
        want = local.subgraphs(lo, hi).node_compute(
            tc.components(), style="temporal", points=ts).run()
        check(any(n.startswith("compile: fused") for n in got.notes),
              f"served components not fused: {got.notes}")
        check(np.array_equal(got.value[1], want.value[1]),
              "served components differ from the in-process store")
    finally:
        remote.close()
    report(6, "served path", t, cells=cluster.spec.n_cells, r=cluster.spec.r,
           events=len(events), snapshot_t=tm, components_N=len(got.value[1]))


def phase_sharded(n_events: int, seed: int, chips: int) -> None:
    """Degree series under shard_map over ``chips`` devices vs the host
    replay."""
    import jax
    from jax.sharding import NamedSharding

    from repro.taf import exec as taf_exec

    t = time.perf_counter()
    events = generate(n_events, seed=seed)
    store = HistoricalGraphStore.build(events, **CFG)
    lo, hi = window(store)
    sots = store.subgraphs(lo, hi).materialize().operand
    ts = np.linspace(lo, hi, 64).astype(np.int64)
    mesh = taf_exec.make_worker_mesh()
    check(mesh.devices.size == chips, f"mesh has {mesh.devices.size} devices")
    got = taf_exec.sharded_degree_series(sots, ts, mesh=mesh)
    want = replay.degree_series(sots, ts)
    on = sots.init_present == 1
    check(np.array_equal(np.asarray(got)[on], np.asarray(want)[on]),
          "sharded degree series differs from the host replay")
    sharded = [a for a in jax.live_arrays()
               if isinstance(a.sharding, NamedSharding)
               and a.sharding.mesh.devices.size == chips
               and len({s.device for s in a.addressable_shards}) == chips
               and all(s.data.shape[0] * chips == a.shape[0]
                       for s in a.addressable_shards)]
    check(len(sharded) >= 5, f"{len(sharded)} operands sharded over {chips}")
    report("4-chip", "sharded degree series", t, devices=chips, N=len(sots),
           T=len(ts), sharded_operands=len(sharded),
           total_degree_last=int(np.asarray(got)[on, -1].sum()))


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--events", type=int, default=250_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        cluster = None
        try:
            if args.chips == 1:
                # storage-only child processes, started before this
                # process touches JAX (they never import it)
                cluster = LocalCluster(ClusterSpec(
                    n_cells=3, r=2, backend="file", root=root),
                    mode="subprocess")
                cluster.start()
            dev = phase_device(args.chips)
            if args.chips == 4:
                phase_sharded(args.events, args.seed, args.chips)
            else:
                events, store = phase_build(args.events, args.seed)
                phase_retrieval(events, store)
                phase_device_fold(store)
                phase_fused(store)
                phase_served(cluster, args.events // 10, args.seed)
        finally:
            if cluster is not None:
                cluster.stop()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
