"""Spans (``repro.trace``): off, one shared no-op that needs no JAX; on,
the profiler's host plane holds every layer's span of a batched
snapshot read and of a fused PageRank query, each nested in the span
that called it."""
import contextlib
import glob
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import trace
from repro.data.temporal_graph_gen import generate
from repro.taf import HistoricalGraphStore
from repro.taf import compile as tc

SRC = Path(__file__).resolve().parents[1] / "src"
MODULES = ("query", "plan", "tgi", "kvstore", "serialize", "snapshot",
           "delta", "replay", "compile", "overlay")


def test_off_span_is_one_shared_noop():
    assert not trace.enable(False)
    assert trace.span("tgi.get_snapshot") is trace.span("kvstore.multiget")
    with trace.span("tgi.get_snapshot") as got:
        assert got is None


def test_store_imports_and_spans_without_jax():
    code = ("import sys\n"
            "import repro.storage.kvstore, repro.core.tgi\n"
            "from repro import trace\n"
            "with trace.span('tgi.get_snapshot'):\n"
            "    pass\n"
            "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    assert out.stdout.strip() == "False"


def _program_spans(trace_dir):
    """{name: set of parent names}: each program span with the innermost
    program span that encloses it on the same host thread (None at the
    root)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    data = ProfileData.from_file(path[0])
    parents = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, -e.duration_ns, e.name)
                          for e in line.events
                          if e.name.split(".")[0] in MODULES),
                         key=lambda e: (e[0], e[1]))
            stack = []
            for s, neg_d, name in evs:
                while stack and stack[-1][0] <= s:
                    stack.pop()
                parents.setdefault(name, set()).add(
                    stack[-1][1] if stack else None)
                stack.append((s - neg_d, name))
    return parents


@pytest.fixture(scope="module")
def store():
    return HistoricalGraphStore.build(
        generate(4000, seed=3), n_shards=2, parts_per_shard=2,
        events_per_span=1000, eventlist_size=64, checkpoints_per_span=4)


@contextlib.contextmanager
def _traced(log_dir):
    import jax

    trace.enable()
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        trace.enable(False)


def test_batched_snapshot_spans_nest_by_layer(store, tmp_path):
    t0, t1 = store.time_range()
    ts = t0 + (t1 - t0) * 3 // 5 + np.arange(8) * 5
    store.tgi.invalidate_caches()  # cold: every block is decoded
    with _traced(tmp_path):
        store.snapshots(ts, use_kernel=True)
    got = _program_spans(str(tmp_path))
    assert got["tgi.get_snapshots"] == {None}
    for name in ("tgi.read_guard", "tgi.fetch_delta", "tgi.fetch_eventlists",
                 "snapshot.overlay_fold", "snapshot.events_to_delta",
                 "overlay.dispatch", "overlay.readback",
                 "snapshot.delta_to_graph"):
        assert got[name] == {"tgi.get_snapshots"}, (name, got)
    assert got["kvstore.multiget"] == {"tgi.fetch_delta",
                                       "tgi.fetch_eventlists"}
    assert got["serialize.decode"] == {"kvstore.multiget"}
    assert got["delta.delta_sum"] == {"snapshot.overlay_fold"}
    # each timepoint's edges merge onto the shared path's
    assert got["delta.edge_sum"] == {"delta.delta_sum", "tgi.get_snapshots"}


def test_fused_pagerank_query_spans_nest_by_layer(store, tmp_path):
    t0, t1 = store.time_range()
    lo, hi = t0 + (t1 - t0) // 4, t1
    with _traced(tmp_path):
        res = store.subgraphs(lo, hi).node_compute(
            tc.pagerank(iters=4), style="temporal",
            points=np.linspace(lo, hi, 16).astype(np.int64)).run()
        with tc.disabled():  # the staged evolution replays on the host
            store.subgraphs(lo, hi).evolution(
                tc.component_count(), points=np.linspace(lo, hi, 4)).run()
    assert any("fused compute[pagerank]" in n for n in res.notes)
    got = _program_spans(str(tmp_path))
    assert got["query.run"] == {None}
    assert got["plan.run"] == {"query.run"}
    assert got["plan.fetch"] == {"plan.run"}
    assert "plan.fetch" in got["tgi.read_guard"]
    assert "plan.fetch" in got["tgi.get_snapshot"]
    assert got["compile.dispatch"] == {"plan.run"}
    assert got["compile.export_node"] == {"compile.dispatch"}
    assert got["compile.export_edge"] == {"compile.dispatch"}
    assert got["compile.readback"] == {"plan.run"}
    assert got["replay.state_at_many"] == {"plan.run"}
