"""Core tests: DiDiC, metrics, partitioners, dynamism, traffic simulator."""

import numpy as np
import pytest

from repro.core import metrics, partitioners
from repro.core.didic import DidicConfig, didic_partition, didic_refine
from repro.core.dynamism import DynamismLog, apply_dynamism, generate_dynamism
from repro.core.traffic import execute_ops, generate_ops
from repro.graphs import datasets, generators


@pytest.fixture(scope="module")
def fs():
    return datasets.load("filesystem", scale=0.005)


@pytest.fixture(scope="module")
def planted():
    return generators.two_cluster(n_per=100, p_in=0.15, p_out=0.01, seed=1)


class TestMetrics:
    def test_edge_cut_hand_computed(self):
        g = generators.grid_graph(2, 2)  # square: edges (0-1),(2-3),(0-2),(1-3)
        parts = np.array([0, 0, 1, 1], dtype=np.int32)
        assert metrics.edge_cut(g, parts) == 2.0
        assert metrics.edge_cut_fraction(g, parts) == 0.5

    def test_random_edge_cut_expectation(self, fs):
        """Paper §7.2: random partitioning ec ≈ 1 − 1/k."""
        for k in (2, 4):
            parts = partitioners.random_partition(fs.n_nodes, k, seed=0)
            ec = metrics.edge_cut_fraction(fs, parts)
            assert abs(ec - (1 - 1 / k)) < 0.02

    def test_modularity_bounds(self, planted):
        block = planted.node_attrs["block"].astype(np.int32)
        m_good = metrics.modularity(planted, block)
        m_rand = metrics.modularity(planted, partitioners.random_partition(planted.n_nodes, 2, 1))
        assert m_good > m_rand
        assert m_good <= 1.0

    def test_cv(self):
        assert metrics.coefficient_of_variation(np.array([5, 5, 5, 5])) == 0.0
        assert metrics.coefficient_of_variation(np.array([0, 10])) == pytest.approx(1.0)

    def test_conductance_range(self, planted):
        block = planted.node_attrs["block"].astype(np.int32)
        phi = metrics.conductance(planted, block)
        assert 0.0 <= phi["min"] <= phi["max"] <= 1.0


class TestDidic:
    def test_recovers_planted_communities(self, planted):
        parts, _ = didic_partition(planted, DidicConfig(k=2, iterations=30), seed=0)
        block = planted.node_attrs["block"]
        agree = max((parts == block).mean(), (parts != block).mean())
        assert agree > 0.95
        assert metrics.edge_cut_fraction(planted, parts) < 0.15

    def test_beats_random_on_filesystem(self, fs):
        parts, _ = didic_partition(
            fs, DidicConfig(k=2, iterations=60, smooth_cap=256), seed=0
        )
        ec = metrics.edge_cut_fraction(fs, parts)
        assert ec < 0.15, f"DiDiC edge cut {ec} not far below random 0.5"

    def test_partition_invariants(self, planted):
        parts, state = didic_partition(planted, DidicConfig(k=4, iterations=10), seed=0)
        assert parts.shape == (planted.n_nodes,)
        assert parts.min() >= 0 and parts.max() < 4
        assert not np.isnan(np.asarray(state.w)).any()
        assert np.asarray(state.w).min() >= 0  # loads stay non-negative

    def test_refine_repairs_damage(self, planted):
        cfg = DidicConfig(k=2, iterations=30)
        parts, state = didic_partition(planted, cfg, seed=0)
        ec0 = metrics.edge_cut_fraction(planted, parts)
        rng = np.random.default_rng(0)
        damaged = parts.copy()
        idx = rng.choice(planted.n_nodes, size=planted.n_nodes // 4, replace=False)
        damaged[idx] = rng.integers(0, 2, size=idx.shape[0])
        ec_damaged = metrics.edge_cut_fraction(planted, damaged)
        repaired, _ = didic_refine(planted, damaged, cfg, iterations=1)
        ec_repaired = metrics.edge_cut_fraction(planted, repaired)
        assert ec_damaged > ec0 * 1.5
        assert ec_repaired < ec_damaged * 0.5

    @pytest.mark.parametrize("spmm_kind", ["segment", "halo"])
    def test_step_takes_graph_tables_as_arguments(self, fs, spmm_kind):
        """The compiled step receives the edge tables as inputs. Baked in
        as constants they made each compiled step, and its persistent-cache
        entry, tens of MB at paper scale."""
        import re

        import jax
        import jax.numpy as jnp

        from repro.core.didic import _init_state, _make_step, make_spmm
        from repro.core.didic_distributed import _mesh_program
        from repro.launch.mesh import make_replay_mesh

        cfg = DidicConfig(k=4, iterations=1)
        if spmm_kind == "segment":
            spmm, degc = make_spmm(fs, cfg)
        else:
            _, spmm, degc = _mesh_program(fs, make_replay_mesh(1), ("data",))
        state = _init_state(degc.shape[0], cfg.k, jnp.zeros(degc.shape[0], jnp.int32))
        step = _make_step(spmm, degc, cfg)
        hlo = step.func.lower(
            state.w, state.l, state.parts, state.beta, jax.random.PRNGKey(0),
            jnp.int32(1), **step.keywords,
        ).as_text()
        edges = spmm.args[0].shape[-1]
        assert f"{edges}x" in hlo
        assert re.search(rf"constant.*tensor<(\d+x)?{edges}x", hlo) is None

    def test_mesh_refine_equals_single_device_refine(self, fs):
        """A mesh layout pads each shard's block to a multiple of 8 rows
        (3,705 vertices: 3 padding rows). Padding rows are no vertices:
        counted in ScaleBalance's sizes and target and in the rescale,
        they biased β, and the one-device mesh repair differed from the
        single-device one in most vertices."""
        from repro.core.didic_distributed import didic_refine_distributed
        from repro.launch.mesh import make_replay_mesh

        assert fs.n_nodes % 8
        cfg = DidicConfig(k=4, smooth_cap=256)
        rng = np.random.default_rng(0)
        parts = rng.integers(0, 4, fs.n_nodes).astype(np.int32)
        mesh_state = shared_state = None
        for _ in range(2):
            moved = rng.integers(0, fs.n_nodes, fs.n_nodes // 20)
            parts[moved] = rng.integers(0, 4, moved.shape[0])
            shared, shared_state = didic_refine(fs, parts, cfg, state=shared_state)
            mesh, mesh_state = didic_refine_distributed(fs, parts, cfg, make_replay_mesh(1),
                                                        state=mesh_state)
            np.testing.assert_array_equal(mesh, shared)
            parts = shared.copy()


class TestPartitioners:
    def test_hardcoded_filesystem_subtrees(self, fs):
        parts = partitioners.hardcoded_filesystem(fs, 4)
        ec = metrics.edge_cut_fraction(fs, parts)
        counts = np.bincount(parts, minlength=4)
        assert ec < 0.05, "subtree packing should nearly eliminate cut"
        assert metrics.coefficient_of_variation(counts) < 0.25

    def test_hardcoded_gis_longitude(self):
        g = datasets.load("gis", scale=0.005)
        parts = partitioners.hardcoded_gis(g, 4)
        counts = np.bincount(parts, minlength=4)
        assert counts.max() - counts.min() <= 4  # equal-|V| chunks
        lon = g.node_attrs["lon"]
        # partitions are longitude-ordered
        assert lon[parts == 0].max() <= lon[parts == 3].min() + 1e-5

    def test_hardcoded_for_dispatch(self, fs):
        assert partitioners.hardcoded_for(fs, 2) is not None
        tw = datasets.load("twitter", scale=0.005)
        assert partitioners.hardcoded_for(tw, 2) is None  # paper: none for Twitter


class TestPlacement:
    """ISSUE 10 tentpole: ownership + fixed-capacity exception table."""

    def _placement(self, n=10, capacity=4):
        from repro.core.placement import Placement
        return Placement(owner=np.arange(n, dtype=np.int32) % 3,
                         capacity=capacity)

    def test_table_is_static_sorted_and_padded(self):
        p = self._placement()
        assert p.hot.shape == (4,) and (p.hot == -1).all()
        assert p.replicated_mask() is None       # empty → engine fast path
        p.set_hot([7, 2, 2])
        assert list(p.hot) == [2, 7, -1, -1]     # unique, sorted, padded
        assert p.n_hot == 2 and p.is_replicated(7)
        mask = p.replicated_mask()
        assert mask.dtype == bool and mask.sum() == 2 and mask[2] and mask[7]
        with pytest.raises(ValueError, match="capacity"):
            p.set_hot([0, 1, 2, 3, 4])

    def test_epoch_bumps_only_on_change(self):
        p = self._placement()
        e0 = p.replica_epoch
        p.set_hot([3, 5])
        assert p.replica_epoch == e0 + 1
        p.set_hot([5, 3])                        # same set — no bump
        assert p.replica_epoch == e0 + 1

    def test_invalidate_repacks_and_counts(self):
        p = self._placement()
        p.set_hot([1, 4, 8])
        e = p.replica_epoch
        assert p.invalidate([4, 9]) == 1         # 9 not in the table
        assert list(p.hot_vertices()) == [1, 8]
        assert list(p.hot) == [1, 8, -1, -1]     # repacked, still padded
        assert p.replica_epoch == e + 1
        assert p.invalidate([9]) == 0
        assert p.replica_epoch == e + 1          # no-op → no bump

    def test_replace_owner_evicts_out_of_range(self):
        p = self._placement(n=10)
        p.set_hot([2, 9])
        p.replace_owner(np.zeros(5, dtype=np.int32))   # shrink: 9 invalid
        assert list(p.hot_vertices()) == [2]
        assert p.owner.shape == (5,)

    def test_capacity_zero_is_inert(self):
        p = self._placement(capacity=0)
        assert p.hot.shape == (0,)
        assert p.replicated_mask() is None
        assert p.invalidate([1, 2]) == 0

    def test_snapshot_meta_roundtrip(self):
        from repro.core.placement import Placement
        p = self._placement()
        p.set_hot([3])
        q = Placement(owner=p.owner.copy(), capacity=p.to_meta()["capacity"],
                      hot=p.hot.copy(),
                      replica_epoch=p.to_meta()["replica_epoch"])
        assert np.array_equal(q.hot, p.hot)
        assert q.replica_epoch == p.replica_epoch


class TestSelectHotVertices:
    def test_top_k_by_traffic_deterministic_ties(self):
        traffic = np.array([5, 0, 9, 9, 1, 3])
        got = partitioners.select_hot_vertices(traffic, 3)
        assert list(got) == [0, 2, 3]            # ties break by lowest id
        assert partitioners.select_hot_vertices(traffic, 0).size == 0
        # zero-traffic vertices never promoted even with room
        assert list(partitioners.select_hot_vertices(traffic, 6)) == [0, 2, 3, 4, 5]

    def test_hysteresis_keeps_incumbents(self):
        traffic = np.array([10, 11, 0, 0])
        hot = partitioners.select_hot_vertices(traffic, 2)
        assert list(hot) == [0, 1]
        # challenger at 12 < 1.25 * weakest incumbent (10): no churn
        traffic2 = np.array([10, 11, 12, 0])
        assert list(partitioners.select_hot_vertices(
            traffic2, 2, current_hot=hot)) == [0, 1]
        # challenger at 13 > 12.5: displaces the weakest incumbent
        traffic3 = np.array([10, 11, 13, 0])
        assert list(partitioners.select_hot_vertices(
            traffic3, 2, current_hot=hot)) == [1, 2]

    def test_free_capacity_admits_without_hysteresis(self):
        traffic = np.array([10, 0, 4, 0])
        hot = partitioners.select_hot_vertices(traffic, 3, current_hot=[0])
        assert list(hot) == [0, 2]               # room left → plain admit

    def test_stale_incumbents_dropped(self):
        traffic = np.array([1, 2, 3])
        got = partitioners.select_hot_vertices(traffic, 2,
                                               current_hot=[7, -1, 1])
        assert list(got) == [1, 2]               # 7 out of range, -1 pad


class TestDynamism:
    def test_units_and_replay(self, fs):
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        log = generate_dynamism(parts, 0.05, "random", k=4, seed=1)
        assert log.units == int(round(0.05 * fs.n_nodes))
        out1 = apply_dynamism(parts, log)
        out2 = apply_dynamism(parts, log)
        assert np.array_equal(out1, out2)  # replayable
        assert (out1 != parts).sum() > 0

    def test_fewest_vertices_balances(self, fs):
        parts = np.zeros(fs.n_nodes, dtype=np.int32)  # all on partition 0
        log = generate_dynamism(parts, 0.2, "fewest_vertices", k=4, seed=0)
        out = apply_dynamism(parts, log)
        counts = np.bincount(out, minlength=4)
        assert counts[1:].min() > 0.8 * (0.2 * fs.n_nodes / 3)

    def test_least_traffic_requires_traffic(self, fs):
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        with pytest.raises(ValueError):
            generate_dynamism(parts, 0.01, "least_traffic", k=4)

    def test_slices_compose(self, fs):
        parts = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        log = generate_dynamism(parts, 0.1, "random", k=4, seed=1)
        half1 = apply_dynamism(parts, log.slice(0.0, 0.5))
        full_via_halves = apply_dynamism(half1, log.slice(0.5, 1.0))
        full = apply_dynamism(parts, log)
        assert np.array_equal(full_via_halves, full)

    def test_insert_rate_grows_vertices(self, fs):
        """ISSUE 5 tentpole: insert units allocate new vertices with
        incident edges + metadata, and the policies target them with the
        same sequential scan (a pure addition, no source decrement)."""
        parts = np.zeros(fs.n_nodes, dtype=np.int32)  # all on partition 0
        log = generate_dynamism(parts, 0.1, "fewest_vertices", k=4, seed=0,
                                insert_rate=0.5, graph=fs)
        n_new = log.n_new_vertices
        assert 0 < n_new < log.units
        # new ids are contiguous from the base and recorded per unit
        np.testing.assert_array_equal(
            log.new_vertices(), fs.n_nodes + np.arange(n_new))
        assert log.base_nodes == fs.n_nodes
        # every insert wrote one folder->file edge, attributed to its unit
        assert log.insert_senders.shape == log.insert_unit.shape
        assert np.all(np.asarray(log.unit_is_insert)[log.insert_unit])
        assert log.insert_attrs["node_type"].shape[0] == n_new
        # the grown partition map holds every new vertex's allocation
        out = apply_dynamism(parts, log)
        assert out.shape[0] == fs.n_nodes + n_new
        ins = np.asarray(log.unit_is_insert)
        np.testing.assert_array_equal(
            out[log.vertices[ins]], log.targets[ins])
        # fewest_vertices sends the early allocations off partition 0
        assert (out[fs.n_nodes:] != 0).any()
        # the graph applies the same payload
        g2 = fs.with_vertices(n_new, log.insert_attrs, log.insert_senders,
                              log.insert_receivers, log.insert_weights)
        assert g2.n_nodes == out.shape[0]

    def test_insert_rate_requires_graph(self, fs):
        parts = np.zeros(fs.n_nodes, dtype=np.int32)
        with pytest.raises(ValueError, match="requires the graph"):
            generate_dynamism(parts, 0.1, "random", k=4, insert_rate=0.5)

    def test_structural_slices_roundtrip(self, fs):
        """ISSUE 5: per-unit insert attribution makes structural logs
        sliceable — concatenated slices ≡ the whole log, and applying the
        slices in sequence reproduces the whole log's map and graph."""
        parts = np.arange(fs.n_nodes, dtype=np.int32) % 4
        log = generate_dynamism(parts, 0.2, "random", k=4, seed=2,
                                insert_rate=0.4, graph=fs)
        pieces, f = [], 0.0
        while f < 1.0 - 1e-12:
            nf = f + 0.05
            pieces.append(log.slice(f, min(nf, 1.0)))
            f = nf
        np.testing.assert_array_equal(
            np.concatenate([p.vertices for p in pieces]), log.vertices)
        np.testing.assert_array_equal(
            np.concatenate([p.insert_senders for p in pieces]),
            log.insert_senders)
        for key in log.insert_attrs:
            np.testing.assert_array_equal(
                np.concatenate([p.insert_attrs[key] for p in pieces]),
                log.insert_attrs[key])
        # slices apply in sequence: base_nodes advances past earlier inserts
        cur, g = parts, fs
        for p in pieces:
            assert p.base_nodes == cur.shape[0]
            cur = apply_dynamism(cur, p)
            g = g.with_vertices(p.n_new_vertices, p.insert_attrs,
                                p.insert_senders, p.insert_receivers,
                                p.insert_weights)
        np.testing.assert_array_equal(cur, apply_dynamism(parts, log))
        g_whole = fs.with_vertices(log.n_new_vertices, log.insert_attrs,
                                   log.insert_senders, log.insert_receivers,
                                   log.insert_weights)
        assert g.n_nodes == g_whole.n_nodes
        np.testing.assert_array_equal(g.senders, g_whole.senders)
        np.testing.assert_array_equal(g.edge_weight, g_whole.edge_weight)

    def test_structural_slices_roundtrip_plain_graph(self):
        """Plain-graph (twitter-flavor) inserts write *two* edges per unit;
        the payload must be unit-major so slice concatenation preserves
        edge order exactly — the graph built from slices and from the
        whole log must be identical arrays (CSR layouts are
        edge-order-dependent), not merely equal sets."""
        g = generators.random_graph(60, avg_degree=3.0, seed=0)
        parts = np.arange(g.n_nodes, dtype=np.int32) % 3
        log = generate_dynamism(parts, 0.5, "random", k=3, seed=1,
                                insert_rate=0.5, graph=g)
        assert log.insert_senders.shape[0] == 2 * log.n_new_vertices
        halves = [log.slice(0.0, 0.5), log.slice(0.5, 1.0)]
        np.testing.assert_array_equal(
            np.concatenate([p.insert_senders for p in halves]),
            log.insert_senders)
        np.testing.assert_array_equal(
            np.concatenate([p.insert_receivers for p in halves]),
            log.insert_receivers)
        g_seq = g
        for p in halves:
            g_seq = g_seq.with_vertices(p.n_new_vertices, p.insert_attrs,
                                        p.insert_senders, p.insert_receivers,
                                        p.insert_weights)
        g_whole = g.with_vertices(log.n_new_vertices, log.insert_attrs,
                                  log.insert_senders, log.insert_receivers,
                                  log.insert_weights)
        np.testing.assert_array_equal(g_seq.senders, g_whole.senders)
        np.testing.assert_array_equal(g_seq.receivers, g_whole.receivers)

    def test_unattributed_structural_log_refuses_slice(self):
        log = DynamismLog(
            vertices=np.arange(10), targets=np.zeros(10, np.int32),
            method="random", k=2,
            insert_senders=np.array([0]), insert_receivers=np.array([1]),
        )
        with pytest.raises(ValueError, match="attribution"):
            log.slice(0.0, 0.5)

    def test_growth_log_rejects_mismatched_base(self, fs):
        parts = np.zeros(fs.n_nodes, dtype=np.int32)
        log = generate_dynamism(parts, 0.05, "random", k=4, seed=0,
                                insert_rate=1.0, graph=fs)
        with pytest.raises(ValueError, match="base"):
            apply_dynamism(parts[:-1], log)

    def test_consecutive_slices_partition_exactly(self):
        """Regression (ISSUE 2): the Dynamic experiment walks the log in
        5 % slices with *accumulated* float boundaries (0.05 + 0.05 + ...),
        which are not bit-equal to the literal fractions — the old
        truncating endpoints dropped or double-applied a move at e.g.
        0.05·8 = 0.39999999999999997 vs 0.4. Consecutive slices must
        partition the log exactly for any unit count."""
        for units in (7, 20, 33, 100, 997, 1000):
            log = DynamismLog(
                np.arange(units, dtype=np.int64),
                np.zeros(units, dtype=np.int32), "random", 2,
            )
            pieces, f = [], 0.0
            while f < 1.0 - 1e-12:
                nf = f + 0.05
                pieces.append(log.slice(f, min(nf, 1.0)))
                f = nf
            got = np.concatenate([p.vertices for p in pieces])
            np.testing.assert_array_equal(got, log.vertices)
            # and accumulated boundaries agree with the literal ones
            for i in range(1, 20):
                acc = sum([0.05] * i)
                assert log.slice(0.0, acc).units == log.slice(0.0, i * 0.05).units


class TestTraffic:
    def test_filesystem_correlation_formula(self, fs):
        """Paper Eq. 7.3: measured T_G% ≈ T_PG·ec/(T_L+T_PG) for random."""
        ops = generate_ops(fs, n_ops=800, seed=0)
        for k in (2, 4):
            parts = partitioners.random_partition(fs.n_nodes, k, seed=0)
            ec = metrics.edge_cut_fraction(fs, parts)
            res = execute_ops(fs, ops, parts, k)
            predicted = metrics.expected_global_traffic(ops.t_pg, ops.t_l, ec)
            assert res.percent_global == pytest.approx(predicted, rel=0.08)

    def test_didic_reduces_traffic(self, fs):
        """Paper headline: DiDiC cuts inter-partition traffic 40–90+ %."""
        ops = generate_ops(fs, n_ops=500, seed=0)
        rand = partitioners.random_partition(fs.n_nodes, 4, seed=0)
        did, _ = didic_partition(fs, DidicConfig(k=4, iterations=60, smooth_cap=256), seed=0)
        pg_rand = execute_ops(fs, ops, rand, 4).percent_global
        pg_did = execute_ops(fs, ops, did, 4).percent_global
        assert pg_did < 0.6 * pg_rand

    def test_oplog_deterministic(self, fs):
        a = generate_ops(fs, n_ops=100, seed=3)
        b = generate_ops(fs, n_ops=100, seed=3)
        assert np.array_equal(a.starts, b.starts) and np.array_equal(a.ends, b.ends)

    def test_twitter_two_hops(self):
        tw = datasets.load("twitter", scale=0.005)
        ops = generate_ops(tw, n_ops=200, seed=0)
        parts = partitioners.random_partition(tw.n_nodes, 2, seed=0)
        res = execute_ops(tw, ops, parts, 2)
        assert res.total > 0
        assert res.per_partition.sum() == res.total

    def test_gis_astar_runs(self):
        g = datasets.load("gis", scale=0.005)
        ops = generate_ops(g, n_ops=30, seed=0)
        parts = partitioners.hardcoded_gis(g, 2)
        res = execute_ops(g, ops, parts, 2)
        assert res.total > 0
        # hardcoded longitude split: most short ops stay within a partition
        assert res.percent_global < 0.1
