"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
ref.py pure-jnp oracles (interpret mode on CPU; same pallas_call lowers on
TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delta_overlay import ops as ov_ops
from repro.kernels.delta_overlay import ref as ov_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rglru_scan import ops as rg_ops
from repro.kernels.rglru_scan import ref as rg_ref

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
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Sq,Sk,D,causal,window,dtype", [
    (1, 2, 64, 64, 32, True, 0, jnp.float32),
    (2, 1, 128, 128, 16, True, 0, jnp.bfloat16),
    (1, 2, 96, 160, 32, True, 48, jnp.float32),   # sliding window + padding
    (1, 1, 64, 256, 64, False, 0, jnp.float32),   # cross attention
    (2, 2, 1, 96, 32, True, 0, jnp.float32),      # decode-style single query
])
def test_flash_attention_matches_ref(B, H, Sq, Sk, D, causal, window, dtype):
    rng = jax.random.PRNGKey(B * 7 + Sk)
    ks = jax.random.split(rng, 3)
    q = (jax.random.normal(ks[0], (B, H, Sq, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, H, Sk, D)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, H, Sk, D)) * 0.5).astype(dtype)
    q_pos = jnp.arange(Sk - Sq, Sk, dtype=jnp.int32) if causal else jnp.arange(Sq, dtype=jnp.int32)
    k_pos = jnp.arange(Sk, dtype=jnp.int32)
    got = fa_ops.flash_attention(q, k, v, q_pos, k_pos, causal=causal,
                                 window=window, blk_q=32, blk_k=32)
    want = fa_ref.attention_ref(q, k, v, q_pos, k_pos, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_ring_cache_holes():
    """k_pos = -1 holes (unfilled ring-buffer slots) are masked out."""
    B, H, S, D = 1, 1, 64, 16
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, H, 1, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    k_pos = jnp.where(jnp.arange(S) < 40, jnp.arange(S), -1).astype(jnp.int32)
    q_pos = jnp.asarray([39], jnp.int32)
    got = fa_ops.flash_attention(q, k, v, q_pos, k_pos, blk_q=8, blk_k=16)
    want = fa_ref.attention_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,W,chunk", [(1, 128, 128, 32), (2, 64, 256, 16),
                                         (1, 96, 130, 32), (2, 33, 64, 16)])
def test_rglru_matches_associative_scan(B, S, W, chunk):
    rng = np.random.RandomState(S + W)
    log_a = -np.abs(rng.randn(B, S, W)).astype(np.float32) * 0.5
    b = rng.randn(B, S, W).astype(np.float32)
    got = rg_ops.rglru(jnp.asarray(log_a), jnp.asarray(b), chunk=chunk, tile_w=64)
    want = rg_ref.rglru_ref(jnp.asarray(log_a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_rglru_matches_sequential():
    B, S, W = 1, 40, 32
    rng = np.random.RandomState(3)
    log_a = -np.abs(rng.randn(B, S, W)).astype(np.float32)
    b = rng.randn(B, S, W).astype(np.float32)
    h = np.zeros((B, W), np.float32)
    seq = []
    for t in range(S):
        h = np.exp(log_a[:, t]) * h + b[:, t]
        seq.append(h.copy())
    want = np.stack(seq, 1)
    got = rg_ops.rglru(jnp.asarray(log_a), jnp.asarray(b), chunk=8, tile_w=32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# model-level integration: blockwise == direct == pallas paths
# ---------------------------------------------------------------------------


def test_model_attention_impls_agree():
    from repro.models.attention import blockwise_attention, direct_attention

    B, S, H, D = 2, 96, 2, 32
    rng = jax.random.PRNGKey(1)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, S, H, D)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, D)) * 0.3
    pos = jnp.arange(S, dtype=jnp.int32)
    a = blockwise_attention(q, k, v, pos, pos, causal=True, window=0,
                            blk_q=32, blk_k=32)
    b = direct_attention(q, k, v, pos, pos, causal=True, window=0, logit_cap=0.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)
    c = fa_ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        pos, pos, causal=True, blk_q=32, blk_k=32,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(c), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_mlstm_chunkwise_equals_stepwise():
    from repro.models.xlstm_blocks import mlstm_chunkwise, mlstm_step

    B, S, H, d = 2, 64, 2, 16
    rng = jax.random.PRNGKey(5)
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (B, S, H, d)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, d)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, d)) * 0.5
    i_pre = jax.random.normal(ks[3], (B, S, H))
    f_pre = jax.random.normal(ks[4], (B, S, H)) + 2.0
    h_chunk, _ = mlstm_chunkwise(q, k, v, i_pre, f_pre, chunk=16)
    hs = []
    state = (jnp.zeros((B, H, d, d)), jnp.zeros((B, H, d)), jnp.zeros((B, H)))
    for t in range(S):
        h, state = mlstm_step(q[:, t], k[:, t], v[:, t], i_pre[:, t], f_pre[:, t], state)
        hs.append(h)
    h_step = jnp.stack(hs, 1)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_step),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# temporal analytics family (pagerank / connected components / motifs)
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


@pytest.mark.parametrize("seed,N", [(0, 40), (1, 130), (2, 256)])
def test_temporal_pagerank_matches_ref(seed, N):
    from repro.kernels.temporal_pagerank import ops as pr_ops
    from repro.kernels.temporal_pagerank import ref as pr_ref

    adj, active = _random_temporal_graphs(seed, N=N)
    got = pr_ops.temporal_pagerank(adj, active, iters=10, use_pallas=True)
    want = pr_ref.pagerank_ref(jnp.asarray(adj), jnp.asarray(active), iters=10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    # active ranks form a distribution per timepoint
    sums = np.asarray(got).sum(axis=1)
    np.testing.assert_allclose(sums, np.where(active.sum(1) > 0, 1.0, 0.0),
                               atol=1e-4)


@pytest.mark.parametrize("seed,N", [(3, 40), (4, 130)])
def test_temporal_cc_matches_ref(seed, N):
    from repro.kernels.temporal_cc import ops as cc_ops
    from repro.kernels.temporal_cc import ref as cc_ref

    adj, active = _random_temporal_graphs(seed, N=N)
    got = cc_ops.temporal_cc(adj, active, iters=N, use_pallas=True)
    want = cc_ref.cc_ref(jnp.asarray(adj), jnp.asarray(active), iters=N)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # labels agree with a union-find oracle up to relabeling
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    for j in range(adj.shape[0]):
        n_cc, lab = csg.connected_components(sp.csr_matrix(adj[j]),
                                             directed=False)
        g = np.asarray(got)[j]
        on = active[j] == 1
        # same partition: kernel labels constant on each oracle component
        for c in range(n_cc):
            members = on & (lab == c)
            if members.any():
                assert len(np.unique(g[members])) == 1
        assert (g[~on] == -1).all()


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
