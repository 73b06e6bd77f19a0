"""Per-kernel validation: shape sweeps, compared bit-for-bit against the
ref.py pure-jnp oracles (interpret mode on CPU; same pallas_call lowers on
TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delta_overlay import ops as ov_ops
from repro.kernels.delta_overlay import ref as ov_ref

# ---------------------------------------------------------------------------
# delta_overlay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,P,S,K", [(2, 1, 256, 1), (4, 3, 256, 4),
                                     (8, 2, 512, 2), (3, 2, 300, 3)])
def test_delta_overlay_matches_ref(h, P, S, K):
    rng = np.random.RandomState(h * 100 + P)
    valid = rng.rand(h, P, S) < 0.4
    present = (rng.rand(h, P, S) < 0.7).astype(np.int8)
    attrs = rng.randint(-1, 5, size=(h, P, S, K)).astype(np.int32)
    got = ov_ops.overlay(valid, present, attrs, use_pallas=True)
    want = ov_ref.overlay_ref(jnp.asarray(valid), jnp.asarray(present),
                              jnp.asarray(attrs))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_delta_overlay_matches_numpy_chain():
    """Kernel == the numpy Δ-sum chain used by core.delta (_node_sum)."""
    from repro.core.delta import Delta, delta_sum

    rng = np.random.RandomState(0)
    h, P, S, K = 4, 2, 256, 3
    ds = []
    for i in range(h):
        d = Delta.empty(P, S, K)
        d.valid = rng.rand(P, S) < 0.5
        d.present = np.where(d.valid, (rng.rand(P, S) < 0.8), 0).astype(np.int8)
        d.attrs = np.where(
            (d.valid & (d.present == 1))[..., None],
            rng.randint(-1, 4, size=(P, S, K)), -1
        ).astype(np.int32)
        ds.append(d)
    acc = ds[0]
    for d in ds[1:]:
        acc = delta_sum(acc, d)
    got_v, got_p, got_a = ov_ops.overlay(
        np.stack([d.valid for d in ds]),
        np.stack([d.present for d in ds]),
        np.stack([d.attrs for d in ds]),
    )
    np.testing.assert_array_equal(np.asarray(got_v), acc.valid)
    on = acc.valid
    np.testing.assert_array_equal(np.asarray(got_p)[on], acc.present[on])
    np.testing.assert_array_equal(np.asarray(got_a)[on], acc.attrs[on])


@pytest.mark.parametrize("h,P,S,K,T", [(2, 1, 256, 1, 1), (4, 2, 256, 3, 4),
                                       (6, 2, 300, 2, 3), (8, 1, 512, 2, 8)])
def test_delta_overlay_batch_matches_ref(h, P, S, K, T):
    """Time-batched kernel (interpret mode) == pure-jnp batch oracle,
    bit-for-bit, including masked-out layers."""
    rng = np.random.RandomState(h * 10 + T)
    valid = rng.rand(h, P, S) < 0.4
    present = (rng.rand(h, P, S) < 0.7).astype(np.int8)
    attrs = rng.randint(-1, 5, size=(h, P, S, K)).astype(np.int32)
    tmask = (rng.rand(h, T) < 0.6).astype(np.int8)
    tmask[0, :] = 1  # at least one shared layer per timepoint
    got = ov_ops.overlay_batch(valid, present, attrs, tmask, use_pallas=True)
    want = ov_ref.overlay_batch_ref(
        jnp.asarray(valid, jnp.int8), jnp.asarray(present),
        jnp.asarray(attrs), jnp.asarray(tmask, jnp.int32))
    assert got[0].shape == (P, S, T)
    assert got[2].shape == (P, S, T, K)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]) != 0)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def test_delta_overlay_batch_matches_per_t_overlay():
    """Each timepoint's column == the single-timepoint overlay of its
    selected layers (on valid slots, with delta-invariant inputs:
    attrs set only where present)."""
    rng = np.random.RandomState(7)
    h, P, S, K, T = 5, 2, 256, 3, 4
    valid = rng.rand(h, P, S) < 0.5
    present = np.where(valid, (rng.rand(h, P, S) < 0.8), 0).astype(np.int8)
    attrs = np.where((valid & (present == 1))[..., None],
                     rng.randint(-1, 4, size=(h, P, S, K)), -1).astype(np.int32)
    # column t folds the shared prefix [0, 1] plus its own layer 2 + t
    tmask = np.zeros((h, T), np.int8)
    tmask[:2, :] = 1
    for t in range(min(T, h - 2)):
        tmask[2 + t, t] = 1
    got_v, got_p, got_a = (np.asarray(x) for x in
                           ov_ops.overlay_batch(valid, present, attrs, tmask))
    for t in range(T):
        layers = np.nonzero(tmask[:, t])[0]
        w_v, w_p, w_a = ov_ops.overlay(
            valid[layers], present[layers], attrs[layers], use_pallas=True)
        w_v, w_p, w_a = np.asarray(w_v), np.asarray(w_p), np.asarray(w_a)
        np.testing.assert_array_equal(got_v[..., t], w_v)
        on = w_v & (w_p == 1)
        np.testing.assert_array_equal(got_p[..., t][w_v], w_p[w_v])
        np.testing.assert_array_equal(got_a[:, :, t][on], w_a[on])


# ---------------------------------------------------------------------------
# temporal_motif
# ---------------------------------------------------------------------------


def _random_temporal_graphs(seed, T=3, N=40, p=0.08):
    """(T, N, N) symmetric 0/1 adjacency (zero diagonal) + (T, N) active
    masks; edges only between active nodes."""
    rng = np.random.RandomState(seed)
    active = (rng.rand(T, N) < 0.8).astype(np.int32)
    adj = (rng.rand(T, N, N) < p).astype(np.float32)
    adj = np.maximum(adj, adj.transpose(0, 2, 1))
    for j in range(T):
        adj[j] *= active[j][:, None] * active[j][None, :]
        np.fill_diagonal(adj[j], 0.0)
    return adj, active


@pytest.mark.parametrize("seed,N", [(5, 40), (6, 130), (7, 700)])
def test_temporal_motif_matches_ref_and_bruteforce(seed, N):
    from repro.kernels.temporal_motif import ops as mo_ops
    from repro.kernels.temporal_motif import ref as mo_ref

    adj, _ = _random_temporal_graphs(seed, N=N, p=0.15)
    got = np.asarray(mo_ops.temporal_motif(adj, use_pallas=True))
    want = np.asarray(mo_ref.motif_ref(jnp.asarray(adj)))
    np.testing.assert_array_equal(got, want)
    # brute-force triangle enumeration at timepoint 0
    a = adj[0]
    brute = np.zeros(N, np.int64)
    idx = np.transpose(np.nonzero(np.triu(a)))
    for u, v in idx:
        common = np.nonzero(a[u] * a[v])[0]
        for w in common:
            if w > v:
                brute[u] += 1
                brute[v] += 1
                brute[w] += 1
    np.testing.assert_array_equal(got[0], brute)
