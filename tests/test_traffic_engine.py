"""Batched traffic engine: exact equivalence vs the scalar oracle.

The acceptance bar (ISSUE 1) is *bit-exact* agreement on all four traffic
counters — total, global, per-partition, per-vertex — across every access
pattern, including the GIS A*-expansion-set semantics with float32
distance ties and the max_expansions truncation.
"""

import numpy as np
import pytest

from repro.core import partitioners
from repro.core.didic import DidicConfig, didic_partition
from repro.core.traffic import OpLog, execute_ops, generate_ops
from repro.core.traffic_batched import BatchedTrafficEngine, get_engine
from repro.graphs import datasets


@pytest.fixture(scope="module")
def fs():
    return datasets.load("filesystem", scale=0.004)


@pytest.fixture(scope="module")
def gis():
    return datasets.load("gis", scale=0.004)


@pytest.fixture(scope="module")
def tw():
    return datasets.load("twitter", scale=0.004)


def _assert_exact(graph, ops, parts, k, **batched_kw):
    ref = execute_ops(graph, ops, parts, k, engine="scalar")
    if batched_kw:
        eng = BatchedTrafficEngine(graph, ops.pattern, **batched_kw)
        got = eng.run(ops, parts, k, t_l=ops.t_l, t_pg=ops.t_pg)
    else:
        got = execute_ops(graph, ops, parts, k, engine="batched")
    np.testing.assert_array_equal(got.per_op_total, ref.per_op_total)
    np.testing.assert_array_equal(got.per_op_global, ref.per_op_global)
    np.testing.assert_array_equal(got.per_partition, ref.per_partition)
    np.testing.assert_array_equal(got.per_vertex, ref.per_vertex)
    assert got.per_partition.sum() == got.total
    return got


class TestEquivalence:
    def test_filesystem_random_parts(self, fs):
        ops = generate_ops(fs, n_ops=400, seed=1)
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        _assert_exact(fs, ops, parts, 4)

    def test_filesystem_hardcoded_parts(self, fs):
        ops = generate_ops(fs, n_ops=300, seed=2)
        parts = partitioners.hardcoded_filesystem(fs, 2)
        _assert_exact(fs, ops, parts, 2)

    def test_twitter(self, tw):
        ops = generate_ops(tw, n_ops=400, seed=1)
        parts = partitioners.random_partition(tw.n_nodes, 4, seed=3)
        _assert_exact(tw, ops, parts, 4)

    def test_gis_short(self, gis):
        ops = generate_ops(gis, n_ops=200, seed=1, pattern="gis_short")
        parts = partitioners.hardcoded_gis(gis, 4)
        _assert_exact(gis, ops, parts, 4)

    def test_gis_long(self, gis):
        ops = generate_ops(gis, n_ops=60, seed=1, pattern="gis_long")
        parts = partitioners.random_partition(gis.n_nodes, 4, seed=0)
        _assert_exact(gis, ops, parts, 4)

    def test_gis_didic_parts(self, gis):
        """Exactness must not depend on the partitioning's shape."""
        ops = generate_ops(gis, n_ops=80, seed=4, pattern="gis_short")
        parts, _ = didic_partition(gis, DidicConfig(k=2, iterations=5), seed=0)
        _assert_exact(gis, ops, parts, 2)

    def test_gis_degenerate_src_eq_dst(self, gis):
        """src == dst ops contribute exactly zero traffic in both engines."""
        v = np.array([7, 7, 123], dtype=np.int64)
        ops = OpLog("gis_short", v, v.copy(), t_l=8, t_pg=1)
        parts = partitioners.random_partition(gis.n_nodes, 2, seed=0)
        got = _assert_exact(gis, ops, parts, 2)
        assert got.total == 0

    def test_gis_max_expansions_truncation(self, gis):
        """The lex-(f, id) truncation must agree between the engines even
        when it actively clips the expansion set."""
        ops = generate_ops(gis, n_ops=40, seed=5, pattern="gis_long")
        parts = partitioners.random_partition(gis.n_nodes, 2, seed=1)
        ref = execute_ops(gis, ops, parts, 2, engine="scalar")

        from repro.core import traffic as t

        clipped_ref = t._execute_gis_scalar(gis, ops, parts, 2, max_expansions=64)
        assert clipped_ref.total < ref.total  # the cap binds
        eng = BatchedTrafficEngine(gis, "gis_long", max_expansions=64)
        got = eng.run(ops, parts, 2, t_l=ops.t_l, t_pg=ops.t_pg)
        np.testing.assert_array_equal(got.per_op_total, clipped_ref.per_op_total)
        np.testing.assert_array_equal(got.per_op_global, clipped_ref.per_op_global)
        np.testing.assert_array_equal(got.per_vertex, clipped_ref.per_vertex)

    def test_gis_bucketed_variant(self, gis):
        """The finite-Δ delta-stepping path is exactly equivalent too."""
        ops = generate_ops(gis, n_ops=100, seed=6, pattern="gis_short")
        parts = partitioners.random_partition(gis.n_nodes, 4, seed=2)
        _assert_exact(gis, ops, parts, 4, delta_scale=4.0)

    def test_max_expansions_default_normalized(self, gis):
        """ISSUE 4 satellite: ``None`` and the explicit default resolve to
        the *same* cached engine — the engine's value is authoritative, so
        a default-capped replay can never sit beside a differently-capped
        engine for the same configuration."""
        from repro.core.traffic_batched import (
            _DEFAULT_MAX_EXPANSIONS, resolve_max_expansions,
        )

        assert resolve_max_expansions(None) == _DEFAULT_MAX_EXPANSIONS
        assert get_engine(gis, "gis_short") is get_engine(
            gis, "gis_short", max_expansions=_DEFAULT_MAX_EXPANSIONS
        )
        assert get_engine(gis, "gis_short").max_expansions == _DEFAULT_MAX_EXPANSIONS
        eng = get_engine(gis, "gis_short", max_expansions=64)
        assert eng.max_expansions == 64
        assert eng is not get_engine(gis, "gis_short")

    def test_small_chunk_padding(self, gis):
        """n_ops far below / not divisible by the chunk size."""
        ops = generate_ops(gis, n_ops=13, seed=7, pattern="gis_short")
        parts = partitioners.random_partition(gis.n_nodes, 3, seed=0)
        _assert_exact(gis, ops, parts, 3, chunk=8)

    def test_batched_deterministic(self, fs):
        ops = generate_ops(fs, n_ops=200, seed=9)
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        a = execute_ops(fs, ops, parts, 4, engine="batched")
        b = execute_ops(fs, ops, parts, 4, engine="batched")
        np.testing.assert_array_equal(a.per_op_total, b.per_op_total)
        np.testing.assert_array_equal(a.per_vertex, b.per_vertex)

    def test_engine_cache_reused(self, fs):
        ops = generate_ops(fs, n_ops=50, seed=0)
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        execute_ops(fs, ops, parts, 4, engine="batched")
        e1 = get_engine(fs, "filesystem")
        execute_ops(fs, ops, parts, 4, engine="batched")
        assert get_engine(fs, "filesystem") is e1

    def test_env_override(self, fs, monkeypatch):
        ops = generate_ops(fs, n_ops=30, seed=0)
        parts = partitioners.random_partition(fs.n_nodes, 2, seed=0)
        monkeypatch.setenv("REPRO_TRAFFIC_ENGINE", "scalar")
        a = execute_ops(fs, ops, parts, 2, engine="auto")
        b = execute_ops(fs, ops, parts, 2, engine="scalar")
        np.testing.assert_array_equal(a.per_op_total, b.per_op_total)


class TestFrontierKernel:
    def test_pallas_interpret_matches_ref(self):
        import jax.numpy as jnp

        from repro.graphs.structure import padded_neighbors
        from repro.kernels.frontier import frontier_gather, frontier_gather_ref

        rng = np.random.default_rng(0)
        n, e, c = 41, 150, 10
        s = rng.integers(0, n, e)
        r = rng.integers(0, n, e)
        w = rng.random(e).astype(np.float32)
        pn = padded_neighbors(s, r, w, n)
        x = rng.normal(size=(n, c)).astype(np.float32)

        ref_sum = frontier_gather_ref(
            jnp.asarray(x), jnp.asarray(pn.nbr), jnp.asarray(pn.w),
            jnp.asarray(pn.mask), mode="sum",
        )
        k_sum = frontier_gather(
            jnp.asarray(x), jnp.asarray(pn.nbr), jnp.asarray(pn.w * pn.mask),
            mode="sum", interpret=True,
        )
        np.testing.assert_allclose(np.asarray(k_sum), np.asarray(ref_sum), rtol=1e-5, atol=1e-5)

        w_inf = np.where(pn.mask > 0, pn.w, np.float32(np.inf))
        ref_min = frontier_gather_ref(
            jnp.asarray(x), jnp.asarray(pn.nbr), jnp.asarray(pn.w),
            jnp.asarray(pn.mask), mode="min",
        )
        k_min = frontier_gather(
            jnp.asarray(x), jnp.asarray(pn.nbr), jnp.asarray(w_inf),
            mode="min", interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(k_min), np.asarray(ref_min))

        # C spanning several c_tile output tiles (ct > 1): the grid keeps
        # the reduction axis innermost, so every output tile must still
        # see its full accumulation.
        x_wide = rng.normal(size=(n, 300)).astype(np.float32)
        ref_wide = frontier_gather_ref(
            jnp.asarray(x_wide), jnp.asarray(pn.nbr), jnp.asarray(pn.w),
            jnp.asarray(pn.mask), mode="min",
        )
        k_wide = frontier_gather(
            jnp.asarray(x_wide), jnp.asarray(pn.nbr), jnp.asarray(w_inf),
            mode="min", c_tile=128, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(k_wide), np.asarray(ref_wide))

    def test_make_frontier_gather_dispatch(self):
        """The ops-layer closure (both kernel and ref paths) agrees with a
        dense oracle — including capped layouts, whose over-cap edges are
        folded in by the scatter epilogue rather than silently dropped."""
        import jax.numpy as jnp

        from repro.graphs.structure import padded_neighbors
        from repro.kernels.frontier import make_frontier_gather

        rng = np.random.default_rng(3)
        n, e, c = 29, 90, 7
        s = rng.integers(0, n, e)
        r = rng.integers(0, n, e)
        w = rng.random(e).astype(np.float32)
        x = rng.normal(size=(n, c)).astype(np.float32)
        dense = np.zeros((n, n), np.float32)
        np.add.at(dense, (r, s), w)
        for cap in (None, 1, 2):
            pn = padded_neighbors(s, r, w, n, cap=cap)
            if cap is not None:
                assert pn.n_spill > 0  # the cap binds, epilogue exercised
            for use_kernel in (False, True):
                gather = make_frontier_gather(pn, mode="sum", use_kernel=use_kernel)
                np.testing.assert_allclose(
                    np.asarray(gather(jnp.asarray(x))), dense @ x, rtol=1e-5, atol=1e-5
                )

    def test_make_frontier_gather_min_capped(self):
        """min-mode epilogue: capped layout == uncapped layout bit-for-bit
        (min is exact, so cap placement must not change results)."""
        import jax.numpy as jnp

        from repro.graphs.structure import padded_neighbors
        from repro.kernels.frontier import make_frontier_gather

        rng = np.random.default_rng(5)
        n, e, c = 23, 120, 6
        s = rng.integers(0, n, e)
        r = rng.integers(0, n, e)
        w = rng.random(e).astype(np.float32)
        x = rng.random(size=(n, c)).astype(np.float32)
        full = make_frontier_gather(padded_neighbors(s, r, w, n), mode="min")
        want = np.asarray(full(jnp.asarray(x)))
        for use_kernel in (False, True):
            capped = padded_neighbors(s, r, w, n, cap=2)
            assert capped.n_spill > 0
            gather = make_frontier_gather(capped, mode="min", use_kernel=use_kernel)
            np.testing.assert_array_equal(np.asarray(gather(jnp.asarray(x))), want)

    def test_engine_kernel_relaxation_path_exact(self):
        """The Pallas frontier-gather relaxation path (interpret mode on
        CPU) reproduces the scalar oracle bit-for-bit, like the inline
        XLA path it replaces (ISSUE 2 tentpole acceptance). Small graph:
        interpret mode pays per-grid-step emulation cost."""
        g = datasets.load("gis", scale=0.0012)
        ops = generate_ops(g, n_ops=10, seed=2, pattern="gis_short")
        parts = partitioners.random_partition(g.n_nodes, 3, seed=1)
        _assert_exact(g, ops, parts, 3, use_kernel=True, chunk=10)

    def test_sssp_tiny_bucket_width_still_exact(self, gis):
        """A pathologically small Δ stresses the bucket-advance machinery
        (T jumps to min_need + Δ, so rounds stay O(settled) rather than
        O(range/Δ)); results must stay exact — and if the round cap were
        ever hit, the engine raises rather than returning wrong counters."""
        ops = generate_ops(gis, n_ops=8, seed=0, pattern="gis_long")
        parts = partitioners.random_partition(gis.n_nodes, 2, seed=0)
        ref = execute_ops(gis, ops, parts, 2, engine="scalar")
        eng = BatchedTrafficEngine(gis, "gis_long", delta_scale=1e-7)
        got = eng.run(ops, parts, 2, t_l=ops.t_l, t_pg=ops.t_pg)
        np.testing.assert_array_equal(got.per_op_total, ref.per_op_total)
        np.testing.assert_array_equal(got.per_vertex, ref.per_vertex)

    def test_padded_neighbors_layout(self):
        from repro.graphs.structure import padded_neighbors

        s = np.array([0, 1, 2, 0])
        r = np.array([1, 2, 1, 1])
        w = np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)
        pn = padded_neighbors(s, r, w, 3)
        assert pn.max_deg == 3          # vertex 1 has in-neighbors {0, 2, 0}
        assert pn.mask.sum() == 4
        np.testing.assert_allclose(np.sort(pn.w[1][pn.mask[1] > 0]), [1.0, 3.0, 4.0])


class TestHeuristicRows:
    """The A* heuristic rows are computed inside the solve, on the device,
    and must equal NumPy's float32 ``sqrt(dx*dx + dy*dy)`` bit for bit."""

    @staticmethod
    def _values():
        rng = np.random.default_rng(11)
        return np.concatenate([
            rng.uniform(0.0, 200.0, 1_000_000),           # the GIS range of dx² + dy²
            [0.0],
            2.0 ** np.arange(-40, 8),                     # powers of two
            (np.arange(1, 4097) / 64.0) ** 2,             # exact squares
            np.arange(0, 15, dtype=np.float64) ** 2,
        ]).astype(np.float32)

    @pytest.mark.parametrize("nudge", [-4, -3, -1, 0, 1, 3, 4])
    def test_round_sqrt_recovers_numpy(self, nudge):
        """From NumPy's ``sqrt`` one ulp down, unchanged and one ulp up,
        three ulps off as a TPU v5e's ``sqrt`` can be, and at the edge of
        the corrected range, the rounding step returns NumPy's ``sqrt``
        exactly."""
        import jax

        from repro.core.traffic_batched import _SQRT_ULPS, _round_sqrt

        assert abs(nudge) <= _SQRT_ULPS
        x = self._values()
        want = np.sqrt(x)
        start = (want.view(np.int32) + nudge).view(np.float32)
        start = np.where(x > 0, start, np.float32(0)).astype(np.float32)
        got = np.asarray(jax.jit(_round_sqrt)(x, start))
        assert np.array_equal(got.view(np.int32), want.view(np.int32))

    def test_device_rows_equal_host_rows_on_gis(self, gis):
        """The solve's heuristic rows (whole graph to 128 destinations) are
        NumPy's, and need no rounding step where ``sqrt`` rounds correctly."""
        import jax.numpy as jnp

        from repro.core.traffic_batched import _device_h

        eng = BatchedTrafficEngine(gis, "gis_short")
        window = np.arange(gis.n_nodes, dtype=np.int64)
        ends = np.random.default_rng(0).choice(gis.n_nodes, 128, replace=False)
        h, moved = _device_h(jnp.asarray(eng._lon), jnp.asarray(eng._lat),
                             jnp.asarray(eng._lon[ends]), jnp.asarray(eng._lat[ends]))
        host = eng._host_h(window, ends)
        assert np.array_equal(np.asarray(h).view(np.int32), host.view(np.int32))
        assert not np.asarray(moved).any()

    @pytest.mark.parametrize("full", [True, False])
    def test_stacked_problem_grows_with_rows_plus_ops(self, gis, full):
        """A chunk's stacked solve inputs hold no ``[W, C]`` array: from 32
        to 128 ops they grow by a few bytes per op, not by a row per op."""
        from repro.core.traffic_sharded import get_replayer
        from repro.launch.mesh import make_replay_mesh

        rep = get_replayer(gis, "gis_short", make_replay_mesh(1))
        eng = rep.engine
        rng = np.random.default_rng(1)
        v = rng.choice(gis.n_nodes, 2, replace=False)
        cross = np.zeros(gis.n_nodes, np.int32)
        sizes = {}
        for c in (32, 128):
            # Every op runs the same route, so the window is the same.
            srcs, dsts = np.full(c, v[0]), np.full(c, v[1])
            args, _, w_real, _, _ = eng.build_sssp_problem(
                srcs, dsts, np.ones(c, bool), cross, full)
            sizes[c] = (sum(a.nbytes for a in rep._stack_problems([args])), w_real)
        assert sizes[32][1] == sizes[128][1] > 100
        assert 0 < sizes[128][0] - sizes[32][0] <= 96 * 32
