"""The tracing registry: spans, counters, profiler annotations, compile
booking, and the counters the GIS engines book while they replay."""

from __future__ import annotations

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partitioners, tracing
from repro.core.traffic import generate_ops
from repro.graphs import datasets
from repro.launch.mesh import make_replay_mesh

COUNTERS = ("per_op_total", "per_op_global", "per_partition", "per_vertex")


@pytest.fixture(autouse=True)
def fresh_registry():
    tracing.reset()
    yield
    tracing.reset()


def test_nesting_parent_and_self_time():
    with tracing.span("t.outer"):
        time.sleep(0.01)
        for _ in range(2):
            with tracing.span("t.inner"):
                time.sleep(0.01)
    spans = tracing.snapshot()["spans"]
    outer, inner = spans["t.outer"], spans["t.inner"]
    assert (outer["calls"], inner["calls"]) == (1, 2)
    assert (outer["parent"], inner["parent"]) == (None, "t.outer")
    assert inner["self_s"] == pytest.approx(inner["total_s"])
    assert outer["self_s"] == pytest.approx(outer["total_s"] - inner["total_s"])
    assert outer["self_s"] >= 0.01 and inner["total_s"] >= 0.02


def test_counters_reset_and_snapshot():
    tracing.count("t.ops", 5)
    tracing.count("t.ops")
    tracing.count("t.other", 0)
    snap = tracing.snapshot()
    assert snap["counters"]["t.ops"] == 6 and snap["counters"]["t.other"] == 0
    snap["counters"]["t.ops"] = 100  # a snapshot is a copy
    assert tracing.snapshot()["counters"]["t.ops"] == 6
    tracing.reset()
    snap = tracing.snapshot()
    assert "t.ops" not in snap["counters"] and not snap["spans"]


def test_threads_keep_separate_stacks():
    opened, done = threading.Event(), threading.Event()

    def serve():
        with tracing.span("t.request"):
            opened.set()
            done.wait(5)

    th = threading.Thread(target=serve)
    th.start()
    opened.wait(5)
    with tracing.span("t.maintenance"):
        time.sleep(0.005)
    done.set()
    th.join()
    spans = tracing.snapshot()["spans"]
    assert spans["t.maintenance"]["parent"] is None
    assert spans["t.request"]["self_s"] == pytest.approx(spans["t.request"]["total_s"])


def test_concurrent_bookings_are_not_lost():
    import os
    import sys

    n_threads, n_each = 2 * (os.cpu_count() or 2), 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_each):
                with tracing.span("t.work"):
                    tracing.count("t.done")

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    snap = tracing.snapshot()
    assert snap["counters"]["t.done"] == n_threads * n_each
    assert snap["spans"]["t.work"]["calls"] == n_threads * n_each
    assert snap["spans"]["t.work"]["parent"] is None


def test_profiler_trace_gets_program_spans(tmp_path):
    tracing.count("t.before")
    with tracing.span("t.untraced"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("t.traced"):
            jnp.ones(8).block_until_ready()
        tracing.count("t.during", 3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "repro:t.traced" in names and "repro:t.untraced" not in names
    snap = tracing.snapshot()
    assert set(snap["traced"]["spans"]) == {"t.traced"}
    traced = {k: v for k, v in snap["traced"]["counters"].items() if k.startswith("t.")}
    assert traced == {"t.during": 3}
    assert {"t.traced", "t.untraced"} <= set(snap["spans"])
    assert snap["counters"]["t.before"] == 1

    # The next trace restarts the traced registry.
    jax.profiler.start_trace(str(tmp_path / "again"))
    try:
        tracing.count("t.second")
    finally:
        jax.profiler.stop_trace()
    assert tracing.snapshot()["traced"] == {"spans": {}, "counters": {"t.second": 1}}


def test_compile_is_booked_to_the_innermost_span():
    fresh = jax.jit(lambda x: x * 3.0 + 1.0)
    with tracing.span("t.step"):
        with tracing.span("t.compiling"):
            fresh(jnp.arange(5.0)).block_until_ready()
    snap = tracing.snapshot()
    inner, outer = snap["spans"]["t.compiling"], snap["spans"]["t.step"]
    assert inner["compiles"] >= 1 and 0 < inner["compile_s"] <= inner["total_s"]
    assert outer["compiles"] == 0
    assert snap["counters"]["jax.compiles"] >= 1 and snap["counters"]["jax.compile_ns"] > 0
    tracing.reset()
    with tracing.span("t.step"):
        fresh(jnp.arange(5.0)).block_until_ready()  # cached: no compile
    assert tracing.snapshot()["spans"]["t.step"]["compiles"] == 0


def _detour():
    """Source and destination close in space, the only route up a long
    chain: the windowed solve must reject, and the redo pass runs."""
    from repro.core.traffic import OpLog
    from repro.graphs.structure import Graph

    pts = np.array(
        [(0.0, float(y)) for y in range(0, 61)]
        + [(float(x), 60.0) for x in range(1, 3)]
        + [(2.0, float(y)) for y in range(59, -1, -1)]
        + [(0.1 * i, -0.5) for i in range(20)], dtype=np.float32)
    chain_len, blob0 = 63 + 60, 123
    es = list(range(chain_len - 1)) + list(range(blob0, blob0 + 19)) + [0]
    er = list(range(1, chain_len)) + list(range(blob0 + 1, blob0 + 20)) + [blob0]
    ew = np.hypot(*(pts[er] - pts[es]).T).astype(np.float32)
    g = Graph(n_nodes=pts.shape[0], senders=np.array(es, np.int64),
              receivers=np.array(er, np.int64), edge_weight=ew, name="detour")
    g.node_attrs["lon"] = pts[:, 0].astype(np.float64)
    g.node_attrs["lat"] = pts[:, 1].astype(np.float64)
    dst = chain_len - 1
    ops = OpLog("gis_short",
                np.array([0, blob0, blob0 + 2, 0, blob0 + 5, 1], np.int64),
                np.array([dst, blob0 + 10, blob0 + 4, blob0 + 19, dst, dst], np.int64),
                t_l=8, t_pg=1)
    return g, ops, (np.arange(g.n_nodes) % 4).astype(np.int64), 2


def _case(name):
    if name == "detour":
        return _detour()
    g = datasets.load("gis", scale=0.004)
    ops = generate_ops(g, n_ops=150, seed=3, pattern=name)
    return g, ops, partitioners.random_partition(g.n_nodes, 4, seed=0), None


def _sssp(snap) -> dict:
    return {k: v for k, v in snap["counters"].items() if k.startswith("sssp.")}


@pytest.mark.parametrize("case", ["gis_short", "gis_long", "detour"])
def test_gis_replay_counts_solves_and_rounds(case):
    from repro.core.traffic_batched import execute_ops_batched
    from repro.core.traffic_sharded import get_replayer

    g, ops, parts, chunk = _case(case)
    rep = get_replayer(g, ops.pattern, make_replay_mesh(1), chunk=chunk)
    rep.replay(ops, parts, 4, resident=False)  # compile outside the count
    tracing.reset()
    sharded = rep.replay(ops, parts, 4, resident=False)
    snap = tracing.snapshot()
    c = snap["counters"]
    assert c["sssp.op_solves"] == ops.n_ops + c["sssp.redo_ops"]
    assert c["sssp.relax_rounds"] > 0 and c["replay.ops"] == ops.n_ops
    assert c["sssp.chunks"] >= 2 and c["sssp.window_rows_padded"] >= c["sssp.window_rows"]
    assert c["fold.edges"] > 0
    spans = snap["spans"]
    for name in ("sssp.window_select", "sssp.heuristic", "sssp.stack", "sssp.solve",
                 "sssp.accept", "sssp.mass"):
        assert spans[name]["calls"] >= 1, name
    assert spans["sssp.window_build"]["parent"] == "replay"
    assert spans["sssp.heuristic"]["parent"] == "sssp.window_build"
    assert spans["sssp.solve"]["compiles"] == 0
    assert (c["sssp.redo_ops"] > 0) == (case == "detour") == ("sssp.redo" in spans)

    # The single-device engine solves the same chunks in the same rounds.
    tracing.reset()
    batched = execute_ops_batched(g, ops, parts, 4, chunk=chunk)
    assert _sssp(tracing.snapshot()) == _sssp(snap)
    for f in COUNTERS:
        np.testing.assert_array_equal(getattr(batched, f), getattr(sharded, f))


@pytest.mark.parametrize("case", ["gis_short", "detour"])
def test_gis_engines_match_oracle_with_equal_heuristic_corrections(case):
    """Single-device, sharded and resident GIS replays, with the heuristic
    rows computed inside the solve, match the scalar oracle on every
    counter, and book the same ``sssp.heuristic_corrected``."""
    from repro.core.traffic import execute_ops
    from repro.core.traffic_batched import execute_ops_batched
    from repro.core.traffic_sharded import get_replayer

    g, ops, parts, chunk = _case(case)
    ref = execute_ops(g, ops, parts, 4, engine="scalar")
    rep = get_replayer(g, ops.pattern, make_replay_mesh(1), chunk=chunk)
    corrected, results = {}, {}
    for name, run in [
        ("batched", lambda: execute_ops_batched(g, ops, parts, 4, chunk=chunk)),
        ("sharded", lambda: rep.replay(ops, parts, 4, resident=False)),
        ("resident", lambda: rep.replay(ops, parts, 4, resident=True)),
    ]:
        tracing.reset()
        results[name] = run()
        corrected[name] = tracing.snapshot()["counters"]["sssp.heuristic_corrected"]
    tracing.reset()
    results["resident again"] = rep.replay(ops, parts, 4, resident=True)
    assert "sssp.heuristic_corrected" not in tracing.snapshot()["counters"]  # no solve
    assert len(set(corrected.values())) == 1, corrected
    for name, got in results.items():
        for f in COUNTERS:
            np.testing.assert_array_equal(getattr(got, f), getattr(ref, f), err_msg=name)


# -- target searches and DiDiC repairs ---------------------------------------

def _fs_log():
    """A filesystem graph and a log of target searches, half of whose ends
    are random files and folders, mostly outside the start's subtree."""
    from repro.core.traffic import OpLog
    from repro.graphs.generators import FS_FILE, FS_FOLDER, filesystem_tree

    g = filesystem_tree(0.01, 0)
    log = generate_ops(g, n_ops=120, seed=6, pattern="filesystem")
    nt = g.node_attrs["node_type"]
    pool = np.nonzero((nt == FS_FILE) | (nt == FS_FOLDER))[0]
    ends = log.ends.copy()
    ends[::2] = np.random.default_rng(7).choice(pool, ends[::2].shape[0])
    return g, OpLog("filesystem", log.starts, ends, log.t_l, log.t_pg)


def _levels_of(g, ops, max_levels):
    """(Σ levels, ops that find their end), one op at a time up the parent
    pointers: an end in the start's subtree is found at its depth below the
    start; any other op expands all ``max_levels`` levels."""
    depth, parent = g.node_attrs["depth"], g.node_attrs["parent"]
    levels = found = 0
    for s, e in zip(ops.starts.tolist(), ops.ends.tolist()):
        v = e
        while parent[v] >= 0 and depth[v] > depth[s]:
            v = parent[v]
        if v == s and depth[e] > depth[s]:
            levels += min(int(depth[e] - depth[s]), max_levels)
            found += 1
        else:
            levels += max_levels
    return levels, found


@pytest.mark.parametrize("replay", ["batched", "cold", "resident"])
def test_bfs_replay_books_levels_and_targets(replay):
    from repro.core.traffic_batched import execute_ops_batched
    from repro.core.traffic_sharded import get_replayer

    g, ops = _fs_log()
    parts = partitioners.random_partition(g.n_nodes, 4, seed=0)
    rep = get_replayer(g, "filesystem", make_replay_mesh(1))
    if replay == "batched":
        run = lambda: execute_ops_batched(g, ops, parts, 4)  # noqa: E731
    else:
        run = lambda: rep.replay(ops, parts, 4, resident=replay == "resident")  # noqa: E731
    run()  # compiles; a resident replay solves and keeps its state here
    tracing.reset()
    run()
    snap = tracing.snapshot()
    levels, found = _levels_of(g, ops, rep.engine.max_levels)
    assert 0 < found < ops.n_ops
    assert snap["counters"]["bfs.levels"] == levels
    assert snap["counters"]["bfs.targets_found"] == found
    span = snap["spans"]["bfs.compile_log"]
    assert (span["calls"], span["parent"]) == (1, "replay")


def test_twitter_replay_books_two_levels_an_op_and_no_targets():
    from repro.core.traffic_sharded import get_replayer

    g = datasets.load("twitter", scale=0.003)
    ops = generate_ops(g, n_ops=90, seed=2, pattern="twitter")
    parts = partitioners.random_partition(g.n_nodes, 4, seed=0)
    rep = get_replayer(g, "twitter", make_replay_mesh(1))
    for resident in (False, True, True):
        tracing.reset()
        rep.replay(ops, parts, 4, resident=resident)
        c = tracing.snapshot()["counters"]
        assert (c["bfs.levels"], c["bfs.targets_found"]) == (2 * ops.n_ops, 0)


@pytest.mark.parametrize("smooth_cap", [64, 256])
def test_repair_books_its_smoothing_width(smooth_cap):
    from repro.core.didic import DidicConfig
    from repro.core.framework import PartitionedGraphService
    from repro.graphs.generators import filesystem_tree

    g = filesystem_tree(0.005, 0)
    cfg = DidicConfig(k=4, iterations=2, smooth_cap=smooth_cap)
    svc = PartitionedGraphService(g, 4, didic=cfg).partition_didic(seed=0)
    tracing.reset()
    svc.maintain(1)
    c = tracing.snapshot()["counters"]
    assert c["didic.smooth_steps"] == smooth_cap
    assert c["didic.spmms"] == cfg.primary_steps * (cfg.secondary_steps + 1) + smooth_cap
