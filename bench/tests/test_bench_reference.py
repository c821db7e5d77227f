"""The benchmark's reference copies still agree with the program's originals.

The benchmark keeps its own generators and reference semantics so that a
later change to the program cannot change the yardstick; these tests show,
at a small scale on the CPU, where the two have drifted apart.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench.reference import didic as ref_didic
from bench.reference import graphs, oplogs, oracle

SCALE = 0.003


def _program_graph(el):
    from repro.graphs.structure import Graph

    return Graph(n_nodes=el.n_nodes, senders=el.senders, receivers=el.receivers,
                 edge_weight=el.weights, node_attrs=dict(el.attrs))


@pytest.fixture(scope="module")
def twitter():
    el = graphs.twitter(int(611_643 * SCALE), 3)
    return el, _program_graph(el)


@pytest.fixture(scope="module")
def gis():
    el = graphs.gis(int(785_891 * SCALE), 4)
    return el, _program_graph(el)


@pytest.mark.parametrize("dataset", ["twitter", "gis"])
def test_graph_copies_equal_program_generators(dataset):
    from repro.graphs import generators

    if dataset == "twitter":
        mine, theirs = graphs.twitter(int(611_643 * SCALE), 11), generators.twitter_social(SCALE, 11)
    else:
        mine, theirs = graphs.gis(int(785_891 * SCALE), 11), generators.gis_romania(SCALE, 11)
    assert mine.n_nodes == theirs.n_nodes
    np.testing.assert_array_equal(mine.senders, theirs.senders)
    np.testing.assert_array_equal(mine.receivers, theirs.receivers)
    np.testing.assert_array_equal(mine.weights, theirs.edge_weight)
    assert set(mine.attrs) == set(theirs.node_attrs)
    for key, val in mine.attrs.items():
        np.testing.assert_array_equal(val, theirs.node_attrs[key])


@pytest.mark.parametrize("dataset", ["twitter", "gis"])
def test_symmetrize_and_csr_equal_program_views(dataset, twitter, gis):
    el, g = twitter if dataset == "twitter" else gis
    for mine, theirs in zip(graphs.symmetrize(el), g.undirected):
        np.testing.assert_array_equal(mine, theirs)
    s, r, w = graphs.symmetrize(el)
    for mine, theirs in zip(graphs.csr(s, r, w, el.n_nodes), g.undirected_csr):
        np.testing.assert_array_equal(mine, theirs)
    for mine, theirs in zip(graphs.csr(el.senders, el.receivers, el.weights, el.n_nodes), g.csr):
        np.testing.assert_array_equal(mine, theirs)


def test_twitter_starts_equal_program_generator(twitter):
    from repro.core.traffic import generate_ops

    el, g = twitter
    cdf = oplogs.twitter_cdf(np.bincount(el.senders, minlength=el.n_nodes))
    mine = oplogs.twitter_starts(cdf, 300, np.random.default_rng(21))
    np.testing.assert_array_equal(mine, generate_ops(g, 300, seed=21, pattern="twitter").starts)


@pytest.mark.parametrize("variant", ["short", "long"])
def test_gis_routes_equal_program_generator(variant, gis):
    from repro.core.traffic import generate_ops

    el, g = gis
    s, r, w = graphs.symmetrize(el)
    indptr, indices, _ = graphs.csr(s, r, w, el.n_nodes)
    starts, ends = oplogs.gis_routes(el.attrs["lon"], el.attrs["lat"], indptr, indices, 64,
                                     np.random.default_rng(5), variant)
    log = generate_ops(g, 64, seed=5, pattern=f"gis_{variant}")
    np.testing.assert_array_equal(starts, log.starts)
    np.testing.assert_array_equal(ends, log.ends)


def test_random_moves_equal_program_dynamism():
    from repro.core.dynamism import generate_dynamism

    parts = np.random.default_rng(0).integers(0, 4, 5000).astype(np.int32)
    v, t = oplogs.random_moves(5000, 0.05, 4, np.random.default_rng(9))
    log = generate_dynamism(parts, 0.05, "random", k=4, seed=9)
    np.testing.assert_array_equal(v, log.vertices)
    np.testing.assert_array_equal(t, log.targets)
    from repro.core.dynamism import apply_dynamism

    np.testing.assert_array_equal(oplogs.apply_moves(parts, v, t), apply_dynamism(parts, log))


def test_twitter_counters_equal_scalar_oracle(twitter):
    from repro.core.traffic import OpLog, execute_ops

    el, g = twitter
    parts = np.random.default_rng(1).integers(0, 4, el.n_nodes)
    cdf = oplogs.twitter_cdf(np.bincount(el.senders, minlength=el.n_nodes))
    starts = oplogs.twitter_starts(cdf, 200, np.random.default_rng(2))
    indptr, indices, _ = graphs.csr(el.senders, el.receivers, el.weights, el.n_nodes)
    mine = oracle.twitter_counters(indptr, indices, parts, 4, starts)
    assert oracle.mismatches(mine, oracle.twitter_counters_scalar(indptr, indices, parts, 4,
                                                                  starts)) == (0, 400 + 4 + el.n_nodes)
    theirs = execute_ops(g, OpLog("twitter", starts, np.full(200, -1), 2, 1), parts, 4,
                         engine="scalar")
    assert oracle.mismatches(mine, {n: getattr(theirs, n) for n in oracle.COUNTERS})[0] == 0


def test_gis_counters_equal_scalar_oracle(gis):
    from repro.core.traffic import OpLog, execute_ops

    el, g = gis
    parts = np.random.default_rng(1).integers(0, 4, el.n_nodes)
    s, r, w = graphs.symmetrize(el)
    indptr, indices, wts = graphs.csr(s, r, w, el.n_nodes)
    starts, ends = oplogs.gis_routes(el.attrs["lon"], el.attrs["lat"], indptr, indices, 24,
                                     np.random.default_rng(8), "short")
    routes = oracle.GisRoutes(indptr, indices, wts, el.attrs["lon"], el.attrs["lat"])
    mine = routes.counters(parts, 4, starts, ends)
    theirs = execute_ops(g, OpLog("gis_short", starts, ends, 8, 1), parts, 4, engine="scalar")
    assert oracle.mismatches(mine, {n: getattr(theirs, n) for n in oracle.COUNTERS})[0] == 0
    assert mine["per_op_total"].sum() > 0


def test_didic_repair_equals_program_refine():
    """Three chained repairs, each from a map with 5 % of its vertices moved.

    On graphs of a few thousand vertices, smoothing 64 steps deep leaves
    many near-ties, so float32 rounding moves a few vertices; this graph's
    repairs agree exactly on the CPU."""
    from repro.core.didic import DidicConfig
    from repro.core.didic_distributed import didic_refine_distributed
    from repro.launch.mesh import make_replay_mesh

    el = graphs.twitter(5000, 3)
    g = _program_graph(el)
    rng = np.random.default_rng(0)
    parts = rng.integers(0, 4, el.n_nodes).astype(np.int32)
    cfg = DidicConfig(k=4, smooth_cap=64)
    repair = ref_didic.DidicRepair(*graphs.symmetrize(el), el.n_nodes, ref_didic.DidicParams(k=4))
    state, ref_state = None, None
    for _ in range(3):
        v, t = oplogs.random_moves(el.n_nodes, 0.05, 4, rng)
        parts = oplogs.apply_moves(parts, v, t)
        mine, ref_state = repair.iterate(parts, ref_state)
        theirs, state = didic_refine_distributed(g, parts, cfg, make_replay_mesh(1), state=state)
        np.testing.assert_array_equal(mine, theirs)
        parts = theirs


@pytest.mark.parametrize("dataset", ["twitter", "gis"])
def test_n_edges_keeps_that_many_of_the_generated_edges(dataset):
    make = graphs.twitter if dataset == "twitter" else graphs.gis
    n = int((611_643 if dataset == "twitter" else 785_891) * SCALE)
    full = make(n, 11)
    want = int(full.senders.shape[0] * 0.8)
    cut = make(n, 11, n_edges=want)
    assert cut.senders.shape[0] == want
    key = lambda e: set(zip(e.senders.tolist(), e.receivers.tolist(), e.weights.tolist()))
    assert key(cut) <= key(full)
    for name, val in full.attrs.items():
        np.testing.assert_array_equal(val, cut.attrs[name])
    with pytest.raises(ValueError):
        make(n, 11, n_edges=full.senders.shape[0] + 1)
    with pytest.raises(ValueError):
        graphs.check_size(cut, {"n_nodes": n, "n_edges": want + 1})


def test_gis_keeps_each_vertex_shortest_road_first():
    full = graphs.gis(3000, 4)
    out = np.bincount(full.senders, minlength=3000)
    cut = graphs.gis(3000, 4, n_edges=int(full.senders.shape[0] * 0.6))
    assert set(np.nonzero(out)[0]) == set(np.unique(cut.senders))


def test_gis_logs_are_fresh_with_walk_lengths_fixed_per_log(monkeypatch):
    from bench.datasets import gis as gis_data

    config = {"n_nodes": 3000, "graph_seed": 4, "pattern": "gis_short", "log_ops": 32}
    edges = gis_data.build(config)
    seen = []
    orig = oplogs.gis_routes

    def spy(*a, lengths=None, **kw):
        seen.append(np.array(lengths))
        return orig(*a, lengths=lengths, **kw)

    monkeypatch.setattr(gis_data.oplogs, "gis_routes", spy)
    runs = {}
    for seed in (1, 2, 1):
        it = gis_data.logs(config, edges, {"sizes_seed": 0}, np.random.default_rng(seed))
        runs.setdefault(seed, []).append([next(it) for _ in range(3)])
    a, b = runs[1][0], runs[2][0]
    assert len({s.tobytes() for s, _ in a}) == 3
    assert not any(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    for x, y in zip(a, runs[1][1]):
        np.testing.assert_array_equal(x[0], y[0])
        np.testing.assert_array_equal(x[1], y[1])
    np.testing.assert_array_equal(seen[0], seen[3])
    assert not np.array_equal(seen[0], seen[1])
