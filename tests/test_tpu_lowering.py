"""The main path's kernels and fused programs compile for a TPU v5e.

Nothing runs: each test compiles for a described (not attached) v5e
chip, which raises what the chip's compiler would raise — block shapes
off the (8, 128) tiling, layouts Mosaic cannot lower, blocks that
overflow VMEM.  Interpret mode on the CPU cannot see any of these.  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_overlay.delta_overlay import (
    overlay_batch_pallas,
    overlay_pallas,
)
from repro.kernels.delta_overlay.ops import _tile_s
from repro.kernels.temporal_motif.temporal_motif import block_for, motif_pallas
from repro.taf import compile as tc

# the overlay at a deployment's partition tile: h layers, P partitions of
# S slots, K attribute keys, T timepoints per (span, leaf) group
H, P, S, K, T = 6, 8, 4096, 4, 16

# chip_smoke.py's fused-analytics operand at 250k events, seed 0 (window
# = last quarter): N members and pair-table rows as the smoke prints
# them; presence changes per member row as its history gives them (370
# members change once, none twice); existence changes per pair row and
# canonical edges (at most half the pair rows) are upper estimates; 64
# timepoints
SMOKE_N, SMOKE_NODE_CHANGES, SMOKE_PAIRS, SMOKE_CHANGES, SMOKE_E, SMOKE_T = (
    25_336, 1, 213_924, 4, 107_000, 64)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_overlay_kernel_lowers_natively(spec):
    tile = _tile_s(S, 4 * (H + 1) * P * (2 + K))
    compiled = _compile(
        lambda v, p, a: overlay_pallas(v, p, a, tile, interpret=False),
        spec((H, P, S), jnp.int32), spec((H, P, S), jnp.int32),
        spec((H, K, P, S), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_overlay_batch_kernel_lowers_natively(spec):
    h = H + T  # shared path layers + one eventlist layer per timepoint
    tile = _tile_s(S, 4 * (h + T) * P * (2 + K))
    compiled = _compile(
        lambda m, v, p, a: overlay_batch_pallas(v, p, a, m, tile,
                                                interpret=False),
        spec((h, T), jnp.int32), spec((h, P, S), jnp.int32),
        spec((h, P, S), jnp.int32), spec((h, K, P, S), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_motif_kernel_lowers_at_largest_fused_n(spec):
    """The fused triangle path sends the kernel any T * N^2 within
    DENSE_BUDGET; at T=16 that is N=2000, padded to the kernel's tile."""
    t = 16
    n = math.isqrt(tc.DENSE_BUDGET // t)
    assert tc._budget_miss(tc.triangles(), range(n), t) is None
    assert tc._budget_miss(tc.triangles(), range(n + 1), t) is not None
    n_pad = -(-n // block_for(n)) * block_for(n)
    compiled = _compile(lambda a: motif_pallas(a, interpret=False),
                        spec((t, n_pad, n_pad), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def _operands(spec, pairs, changes, edges):
    """Shapes of the fused programs' (node, edge, timepoints) operands:
    smoke-sized node rows and an edge operand of the given pair rows,
    change columns and edges."""
    i32 = jnp.int32
    node = {"pres_t": spec((SMOKE_N, SMOKE_NODE_CHANGES), i32),
            "init_present": spec((SMOKE_N,), i32)}
    edge = {"chg_t": spec((pairs, changes), i32),
            "base": spec((pairs,), i32),
            "edge_valid": spec((edges,), jnp.float32)}
    for k in ("edge_u", "edge_v", "pair_a", "pair_b"):
        edge[k] = spec((edges,), i32)
    for k in ("frow", "fcol", "feid"):
        edge[k] = spec((2 * edges,), i32)
    return node, edge, spec((SMOKE_T,), i32)


def _pagerank_program(spec, pairs, changes, edges):
    """The fused PageRank program compiled for the described chip."""
    prog = tc._build_series_program(tc.pagerank())
    return prog.lower(*_operands(spec, pairs, changes, edges)).compile()


def test_fused_pagerank_program_lowers_at_smoke_shapes(spec):
    compiled = _pagerank_program(spec, SMOKE_PAIRS, SMOKE_CHANGES, SMOKE_E)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_edge_liveness_fuses_at_a_wide_change_table(spec):
    """A deletion-heavy history gives a wide change table.  The per-row
    compare and its parity reduction must fuse: at C = 512 the program
    holds less than one more (P, T) plane than at C = 1, where an
    unfused compare would hold P * C * T int32s (6.5 GB)."""
    pairs, changes, edges = 50_000, 512, 25_000
    temp = {c: _pagerank_program(spec, pairs, c, edges)
            .memory_analysis().temp_size_in_bytes for c in (1, changes)}
    assert temp[changes] - temp[1] < pairs * SMOKE_T * 4


@pytest.mark.parametrize("build", [
    lambda: tc._build_series_program(tc.pagerank()),
    lambda: tc._build_series_program(tc.components()),
    lambda: tc._build_evolution_program(tc.component_count()),
    lambda: tc._build_evolution_program(tc.max_pagerank()),
], ids=["pagerank", "components", "component_count", "max_pagerank"])
def test_fused_programs_lower_without_a_loop(build):
    """Presence and edge liveness are compare-and-sums over change tables,
    and propagation rounds unroll: the programs hold no loop, so no
    search over a padded event table can come back unseen.  Lowered for
    this host, with no chip described.  (The triangle program is left
    out: off the chip its kernel runs interpreted, as a loop over its
    grid.)"""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = build().lower(*_operands(spec, 1_000, 2, 500)).as_text()
    assert not re.search(r"\bwhile\b", text)
