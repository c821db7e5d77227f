"""The file-system configuration's plain reference against the program, on
the CPU: the tree copy, the target-search logs and oracle, the width-256
DiDiC repair, and the soundness of the initial partition at the
configuration's number of iterations."""

from __future__ import annotations

import numpy as np
import pytest

from bench import harness
from bench.datasets import filesystem as fs_data
from bench.reference import didic as ref_didic
from bench.reference import filesystem as fs
from bench.reference import graphs, oplogs, oracle

CELL = "filesystem_k4.dynamic"
CONFIG = harness.load_cell(CELL)[1]
PER_VERTEX = CONFIG["n_edges"] / CONFIG["n_nodes"]


def _sizes(n: int) -> dict:
    return {"n_nodes": n, "n_edges": round(n * PER_VERTEX)}


def _program_graph(el):
    from repro.graphs.structure import Graph

    return Graph(n_nodes=el.n_nodes, senders=el.senders, receivers=el.receivers,
                 edge_weight=el.weights, node_attrs=dict(el.attrs), name="filesystem")


@pytest.fixture(scope="module")
def small():
    el = fs_data.build({**CONFIG, **fs_data.TINY, "graph_seed": 3})
    return el, _program_graph(el)


# -- the tree -----------------------------------------------------------------

@pytest.mark.parametrize("sizes", [fs_data.TINY, _sizes(74_445)], ids=["tiny", "mid"])
def test_tree_has_the_stated_counts_and_is_a_tree(sizes):
    from repro.graphs.generators import filesystem_tree

    el = fs_data.build({**CONFIG, **sizes})
    n = el.n_nodes
    assert (n, el.senders.shape[0]) == (sizes["n_nodes"], sizes["n_edges"])
    assert fs.distinct_pairs(el) == sizes["n_edges"]
    nt, parent, depth = el.attrs["node_type"], el.attrs["parent"], el.attrs["depth"]
    orgs = nt == fs.ORG
    assert (parent[orgs] == -1).all() and (parent[~orgs] >= 0).all()
    child = np.nonzero(~orgs)[0]
    assert (parent[child] < child).all()
    np.testing.assert_array_equal(depth[child], depth[parent[child]] + 1)
    assert (depth[orgs] == 0).all()
    kinds = {fs.USER: {fs.ORG}, fs.FOLDER: {fs.USER, fs.FOLDER}, fs.FILE: {fs.FOLDER},
             fs.EVENT: {fs.FILE, fs.FOLDER}}
    for t, parents in kinds.items():
        assert set(np.unique(nt[parent[nt == t]]).tolist()) <= parents, t
    edges = set(zip(el.senders.tolist(), el.receivers.tolist()))
    assert all((int(parent[v]), int(v)) in edges for v in child)

    # The same vertices as the program's generator up to the last level's
    # file events, where the program adds whole levels and this copy stops.
    theirs = filesystem_tree((n + 0.5) / 730_027, CONFIG["graph_seed"])
    shared = int(np.nonzero(nt == fs.FILE)[0].max()) + 1
    assert theirs.n_nodes >= n
    for key in ("node_type", "depth", "parent"):
        np.testing.assert_array_equal(el.attrs[key][:shared], theirs.node_attrs[key][:shared])


@pytest.mark.parametrize("seed", [0, 3])
def test_tree_is_the_program_generator_at_its_own_count(seed):
    """The same vertices and edges in the same order, but for the second
    references that the program's generator sends to a folder the event
    already references: here they go one level up, so no pair repeats."""
    from repro.graphs.generators import filesystem_tree

    theirs = filesystem_tree(0.01, seed)
    mine = fs.tree(theirs.n_nodes, seed)
    for key, val in mine.attrs.items():
        np.testing.assert_array_equal(val, theirs.node_attrs[key])
    np.testing.assert_array_equal(mine.senders, theirs.senders)
    parent, nt = mine.attrs["parent"], mine.attrs["node_type"]
    moved = mine.receivers != theirs.receivers
    assert (mine.receivers[moved] == parent[theirs.receivers[moved]]).all()
    assert (nt[mine.senders[moved]] == fs.EVENT).all()
    assert (nt[parent[mine.senders[moved]]] == fs.FILE).all()
    pairs = theirs.senders.astype(np.int64) * theirs.n_nodes + theirs.receivers
    _, first, count = np.unique(pairs, return_index=True, return_counts=True)
    repeats = np.setdiff1d(np.arange(pairs.shape[0]), first)
    np.testing.assert_array_equal(np.nonzero(moved)[0], repeats)
    assert repeats.shape[0] > 0.2 * theirs.n_nodes
    assert fs.distinct_pairs(mine) == mine.senders.shape[0]


def test_edge_count_changes_second_references_only():
    full = fs.tree(8425, 5)
    have = full.senders.shape[0]
    tree_edges = set(zip(full.attrs["parent"][5:].tolist(), range(5, 8425)))
    for want in (have - 300, have + 300):
        cut = fs.tree(8425, 5, n_edges=want)
        assert cut.senders.shape[0] == want
        for key, val in full.attrs.items():
            np.testing.assert_array_equal(val, cut.attrs[key])
        edges = list(zip(cut.senders.tolist(), cut.receivers.tolist()))
        assert tree_edges <= set(edges)
        extra = [(s, r) for s, r in edges[have:]] if want > have else []
        p = cut.attrs["parent"]
        assert all(r in (p[p[p[s]]], p[p[p[p[s]]]]) and cut.attrs["node_type"][p[s]] == fs.FILE
                   for s, r in extra)
        assert fs.distinct_pairs(cut) == want
    with pytest.raises(ValueError):
        fs.tree(8425, 5, n_edges=10 * have)
    with pytest.raises(ValueError):
        fs.tree(8425, 5, n_edges=8000)


# -- target searches ----------------------------------------------------------

def test_target_searches_equal_program_generator(small):
    from repro.core.traffic import generate_ops

    el, g = small
    a = el.attrs
    mine = fs.target_searches(a["node_type"], a["parent"], a["depth"], fs.degree(el), 300,
                              np.random.default_rng(21))
    log = generate_ops(g, 300, seed=21, pattern="filesystem")
    np.testing.assert_array_equal(mine[0], log.starts)
    np.testing.assert_array_equal(mine[1], log.ends)


def _mixed_log(el):
    """Seeded target searches, and as many whose end is a random file or
    folder, mostly outside the start's subtree."""
    from repro.core.traffic import OpLog

    a = el.attrs
    starts, ends = fs.target_searches(a["node_type"], a["parent"], a["depth"], fs.degree(el),
                                      200, np.random.default_rng(8))
    rng = np.random.default_rng(9)
    pool = np.nonzero((a["node_type"] == fs.FILE) | (a["node_type"] == fs.FOLDER))[0]
    ends = np.concatenate([ends, rng.choice(pool, 200)])
    starts = np.concatenate([starts, starts[rng.integers(0, 200, 200)]])
    return OpLog("filesystem", starts, ends, CONFIG["t_l"], CONFIG["t_pg"])


def test_target_search_counters_equal_every_engine(small):
    from repro.core.traffic import execute_ops
    from repro.core.traffic_batched import execute_ops_batched
    from repro.core.traffic_sharded import get_replayer
    from repro.launch.mesh import make_replay_mesh

    el, g = small
    log = _mixed_log(el)
    parts = np.random.default_rng(1).integers(0, 4, el.n_nodes)
    ref = fs_data.Reference({**CONFIG, **fs_data.TINY}, el)
    mine = ref.counters(parts, log.starts, log.ends)
    # Some ends lie outside their start's subtree: those ops run every level.
    depth, parent = el.attrs["depth"].astype(np.int64), el.attrs["parent"]
    outside = 0
    for s, e in zip(log.starts, log.ends):
        v = e
        while v >= 0 and depth[v] > depth[s]:
            v = parent[v]
        outside += v != s
    assert outside > 100

    rep = get_replayer(g, "filesystem", make_replay_mesh(1))
    results = {
        "scalar": execute_ops(g, log, parts, 4, engine="scalar"),
        "batched": execute_ops_batched(g, log, parts, 4),
        "sharded": rep.replay(log, parts, 4, resident=False),
        "resident": rep.replay(log, parts, 4, resident=True),
        "resident again": rep.replay(log, parts, 4, resident=True),
    }
    total = 2 * log.n_ops + 4 + el.n_nodes
    for name, got in results.items():
        theirs = {c: getattr(got, c) for c in oracle.COUNTERS}
        assert oracle.mismatches(mine, theirs) == (0, total), name
    assert mine["per_op_total"].sum() > 0

    control = fs_data.Reference({**CONFIG, **fs_data.TINY}, el, control=True)
    assert oracle.mismatches(control.counters(parts, log.starts, log.ends), mine)[0] > 0


# -- DiDiC at width 256 --------------------------------------------------------

def _didic_config():
    from repro.core.didic import DidicConfig

    d = CONFIG["didic"]
    return DidicConfig(k=CONFIG["k"], iterations=CONFIG["didic_iterations"],
                       primary_steps=d["primary_steps"], secondary_steps=d["secondary_steps"],
                       smooth_cap=d["smooth_cap"], smooth_double_every=d["smooth_double_every"],
                       commit_prob=d["commit_prob"], balance_iters=d["balance_iters"],
                       balance_exp=d["balance_exp"])


@pytest.mark.parametrize("n_nodes", [fs_data.TINY["n_nodes"], 8005])
@pytest.mark.parametrize("path", ["shared", "mesh"])
def test_width_256_repair_equals_reference(path, n_nodes):
    """The configuration's initial partition, then three repairs, each from
    the served map with 5 % of its vertices moved: the program's repair
    equals the reference's, vertex for vertex, also where the mesh layout
    pads rows (8,005 vertices; the cell's 730,027 pad 5)."""
    from repro.core.didic import didic_partition, didic_refine
    from repro.core.didic_distributed import (didic_partition_distributed,
                                              didic_refine_distributed)
    from repro.launch.mesh import make_replay_mesh

    el = fs_data.build({**CONFIG, **_sizes(n_nodes), "graph_seed": 3})
    g = _program_graph(el)
    cfg = _didic_config()
    assert cfg.smooth_cap == 256
    mesh = make_replay_mesh(1)
    if path == "mesh":
        parts, _ = didic_partition_distributed(g, cfg, mesh, seed=0)
        refine = lambda p, st: didic_refine_distributed(g, p, cfg, mesh, state=st)  # noqa: E731
    else:
        parts, _ = didic_partition(g, cfg, seed=0)
        refine = lambda p, st: didic_refine(g, p, cfg, state=st)  # noqa: E731
    d = CONFIG["didic"]
    repair = ref_didic.DidicRepair(
        *graphs.symmetrize(el), el.n_nodes,
        ref_didic.DidicParams(k=cfg.k, primary_steps=d["primary_steps"],
                              secondary_steps=d["secondary_steps"],
                              smoothing_steps=d["smooth_cap"],
                              balance_iters=d["balance_iters"], balance_exp=d["balance_exp"]))
    rng = np.random.default_rng(4)
    state, ref_state = None, None
    for _ in range(3):
        v, t = oplogs.random_moves(el.n_nodes, 0.05, cfg.k, rng)
        parts = oplogs.apply_moves(parts, v, t)
        mine, ref_state = repair.iterate(parts, ref_state)
        theirs, state = refine(parts, state)
        np.testing.assert_array_equal(mine, theirs)
        parts = theirs
    assert repair.spmm_count == 3 * (d["primary_steps"] * (d["secondary_steps"] + 1) + 256)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_initial_partition_is_sound_at_the_configured_iterations(seed):
    """The reason the cell exists: at the configuration's ``didic_iterations``
    the cell's initial partition is a partition (largest part at most
    1.10 × N/k), and every slice's repair keeps it one; at 2 the initial
    partition is none."""
    import jax

    from bench.tools.partition_balance import balance

    devices = jax.devices()[:1]
    out = balance(CELL, CONFIG["didic_iterations"], seed, devices, fs_data.TINY)
    ratios = [max(s) * CONFIG["k"] / out["n_nodes"] for s in out["sizes"]]
    assert len(ratios) == 4
    assert max(ratios) <= 1.10, out["sizes"]
    assert max(balance(CELL, 2, seed, devices, fs_data.TINY)["sizes"][0]) * CONFIG["k"] \
        > 1.10 * out["n_nodes"]
