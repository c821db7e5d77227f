"""Property-based tests (hypothesis) on system invariants."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro.core import metrics, partitioners
from repro.core.didic import DidicConfig, _init_state, _make_step, make_spmm
from repro.core.dynamism import apply_dynamism, generate_dynamism
from repro.graphs import generators
from repro.graphs.structure import Graph, coalesce_edges, symmetrize


def _random_graph(n: int, e: int, seed: int) -> Graph:
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, size=e)
    r = rng.integers(0, n, size=e)
    keep = s != r
    if keep.sum() == 0:
        s, r = np.array([0]), np.array([1 % n])
        keep = np.array([True])
    return Graph(
        n_nodes=n, senders=s[keep].astype(np.int32), receivers=r[keep].astype(np.int32),
        edge_weight=rng.random(int(keep.sum())).astype(np.float32) + 0.1,
    )


graph_params = st.tuples(
    st.integers(min_value=4, max_value=120),      # n
    st.integers(min_value=2, max_value=400),      # e
    st.integers(min_value=0, max_value=10_000),   # seed
)


class TestPartitionInvariants:
    @given(graph_params, st.integers(min_value=1, max_value=6))
    @settings(max_examples=25, deadline=None)
    def test_edge_cut_bounds(self, gp, k):
        n, e, seed = gp
        g = _random_graph(n, e, seed)
        parts = partitioners.random_partition(n, k, seed)
        cut = metrics.edge_cut(g, parts)
        assert 0.0 <= cut <= float(g.edge_weight.sum()) + 1e-5
        assert 0.0 <= metrics.edge_cut_fraction(g, parts) <= 1.0

    @given(graph_params, st.integers(min_value=2, max_value=6))
    @settings(max_examples=25, deadline=None)
    def test_modularity_upper_bound(self, gp, k):
        n, e, seed = gp
        g = _random_graph(n, e, seed)
        parts = partitioners.random_partition(n, k, seed + 1)
        assert metrics.modularity(g, parts) <= 1.0 + 1e-6

    @given(graph_params)
    @settings(max_examples=25, deadline=None)
    def test_linear_partition_covers(self, gp):
        n, _, _ = gp
        for k in (1, 2, 3):
            parts = partitioners.linear_partition(n, k)
            assert parts.shape == (n,)
            assert parts.min() >= 0 and parts.max() == k - 1
            counts = np.bincount(parts, minlength=k)
            assert counts.max() - counts.min() <= (n % k) + 1


class TestGraphInvariants:
    @given(graph_params)
    @example((4, 379, 4))  # many duplicate pairs: summation order must not matter
    @settings(max_examples=25, deadline=None)
    def test_symmetrize_involution(self, gp):
        n, e, seed = gp
        g = _random_graph(n, e, seed)
        s, r, w = g.undirected
        # symmetric: for every (u,v,w) there is (v,u,w)
        fwd = {(int(a), int(b)): float(c) for a, b, c in zip(s, r, w)}
        for (a, b), c in fwd.items():
            assert (b, a) in fwd
            assert abs(fwd[(b, a)] - c) < 1e-5
        # total weighted degree = 2 × total undirected weight
        assert abs(g.weighted_degree.sum() - w.sum()) < 1e-2 * max(w.sum(), 1)

    @given(graph_params)
    @settings(max_examples=20, deadline=None)
    def test_coalesce_conserves_weight(self, gp):
        n, e, seed = gp
        rng = np.random.default_rng(seed)
        s = rng.integers(0, n, size=e)
        r = rng.integers(0, n, size=e)
        w = rng.random(e).astype(np.float32)
        s2, r2, w2 = coalesce_edges(s, r, w, n)
        np.testing.assert_allclose(w2.sum(), w.sum(), rtol=1e-4)

    @given(graph_params, st.sampled_from([16, 32]))
    @settings(max_examples=10, deadline=None)
    def test_bell_preserves_matrix(self, gp, bs):
        n, e, seed = gp
        g = _random_graph(n, e, seed)
        bell = g.to_block_ell(block_size=bs)
        s, r, w = g.undirected
        ref = np.zeros((bell.padded_rows, bell.padded_rows), np.float32)
        ref[s, r] = w
        np.testing.assert_allclose(bell.to_dense(), ref[:n, :n], rtol=1e-5, atol=1e-6)


class TestDidicInvariants:
    @given(st.integers(min_value=0, max_value=1000), st.integers(min_value=2, max_value=4))
    @settings(max_examples=8, deadline=None)
    def test_secondary_mass_conserved_and_loads_nonneg(self, seed, k):
        """The secondary diffusion system conserves Σ_v l_v(c) exactly and
        all loads stay non-negative (didic.py module invariants)."""
        g = _random_graph(40, 140, seed)
        cfg = DidicConfig(k=k, iterations=1)
        spmm, degc = make_spmm(g, cfg)
        parts0 = partitioners.random_partition(g.n_nodes, k, seed)
        state = _init_state(g.n_nodes, k, jnp.asarray(parts0))
        step = _make_step(spmm, degc, cfg)
        w, l, parts, beta = step(
            state.w, state.l, state.parts, state.beta, jax.random.PRNGKey(0), jnp.int32(1)
        )
        # fresh per-iteration seed: 100 per member + the 0.01 ε-floor on all
        l0 = 100.0 * np.eye(k)[parts0].sum(axis=0) + 0.01 * g.n_nodes
        np.testing.assert_allclose(np.asarray(l).sum(axis=0), l0, rtol=1e-3)
        assert float(np.asarray(w).min()) >= -1e-4
        assert float(np.asarray(l).min()) >= -1e-4

    @given(st.integers(min_value=0, max_value=100))
    @settings(max_examples=5, deadline=None)
    def test_assignment_in_range(self, seed):
        g = _random_graph(30, 80, seed)
        from repro.core.didic import didic_partition
        parts, _ = didic_partition(g, DidicConfig(k=3, iterations=3), seed=seed)
        assert set(np.unique(parts)).issubset({0, 1, 2})


class TestDynamismInvariants:
    @given(
        st.integers(min_value=10, max_value=300),
        st.floats(min_value=0.0, max_value=0.5),
        st.sampled_from(["random", "fewest_vertices"]),
        st.integers(min_value=0, max_value=99),
    )
    @settings(max_examples=25, deadline=None)
    def test_dynamism_preserves_structure(self, n, amount, method, seed):
        """Dynamism never changes the graph, only the partition map; unit
        count matches Eq. 6.1."""
        parts = partitioners.random_partition(n, 4, seed)
        log = generate_dynamism(parts, amount, method, k=4, seed=seed)
        assert log.units == int(round(amount * n))
        out = apply_dynamism(parts, log)
        assert out.shape == parts.shape
        assert out.min() >= 0 and out.max() < 4


class TestEmbeddingBagProperty:
    @given(
        st.integers(min_value=2, max_value=64),
        st.integers(min_value=1, max_value=12),
        st.integers(min_value=1, max_value=8),
        st.integers(min_value=0, max_value=50),
    )
    @settings(max_examples=20, deadline=None)
    def test_matches_loop(self, v, b, l, seed):
        from repro.kernels.embedding_bag.ref import embedding_bag_ref
        rng = np.random.default_rng(seed)
        table = rng.normal(size=(v, 6)).astype(np.float32)
        idx = rng.integers(0, v, size=(b, l)).astype(np.int32)
        w = rng.random((b, l)).astype(np.float32)
        out = np.asarray(embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(w)))
        for i in range(b):
            expected = sum(w[i, j] * table[idx[i, j]] for j in range(l))
            np.testing.assert_allclose(out[i], expected, rtol=1e-4, atol=1e-5)
