"""The paper's file-system tree and its target searches (arXiv:1301.5121 §6.2.1).

* :func:`tree`: a copy of ``filesystem_tree`` from ``repro.graphs.generators``
  (org → user → folder hierarchy, 29 files and 2 subfolders a folder, an
  event vertex for every file and folder, event meta-edges up the tree),
  built to exactly ``n_nodes`` vertices. The program's generator adds
  whole levels and overshoots; here the last level's folders get their
  events and then files, with their events, only up to the vertex count.
  At the program's own vertex count the two graphs have the same vertices
  and the same edges in the same order, but for the second references.
* A file event's second reference (drawn for 58 % of them, as the
  program's) goes one level above its first. The program's generator sends
  it to the grandparent folder, which most events already reference, so
  there it is a parallel edge; here every edge is a distinct vertex pair.
* Given ``n_edges``, the meta-edges are brought to that count after every
  other draw: second references of file events are added (to events that
  lack one) or dropped, a uniform random set from the same stream. Tree
  edges are never touched, so ``parent`` and ``depth`` stay a tree.
* :func:`target_searches`: a copy of ``_gen_filesystem``
  (``repro.core.traffic``): ends in proportion to degree among files and
  folders, starts a random ancestor folder of the end.
* :func:`target_search_counters`: the four traffic counters of target
  searches, level by level over all ops at once: each op expands its whole
  frontier down folder → {folder, file} edges, with path multiplicity,
  and stops after the level at which its end first appears among the
  children, or when the frontier empties, or after ``max_levels`` levels.
  That is ``_execute_bfs_scalar``'s loop, one op at a time there.
"""

from __future__ import annotations

import numpy as np

from bench.reference.graphs import EdgeList
from bench.reference.oracle import _expand, _fold

ORG, USER, FOLDER, FILE, EVENT = 0, 1, 2, 3, 4
N_ORGS = 5
FILES_PER_FOLDER = 29  # folder fan-out 31, of which 2 subfolders
SUBFOLDERS = 2
MAX_LEVELS = 12


def tree(n_nodes: int, seed: int, n_edges: int = None) -> EdgeList:
    """The file-system tree at exactly ``n_nodes`` vertices (and, given,
    ``n_edges`` edges), from ``seed``; attrs ``node_type`` (int8),
    ``depth`` (int16) and ``parent`` (int64, -1 at the orgs)."""
    rng = np.random.default_rng(seed)
    n = int(n_nodes)
    n_users = max(N_ORGS * 4, int(n ** 0.33))
    node_type = []
    # (senders, receivers, kind): 0 tree edge, 1 meta-edge, 2 second
    # reference of a file event, whose receiver is first the event's first
    # reference and becomes that vertex's parent once the tree is whole.
    edges = []

    def new_nodes(count: int, t: int) -> np.ndarray:
        start = len(node_type)
        node_type.extend([t] * count)
        return np.arange(start, start + count, dtype=np.int64)

    def add(s, r, kind: int) -> None:
        edges.append((np.asarray(s), np.asarray(r), kind))

    orgs = new_nodes(N_ORGS, ORG)
    users = new_nodes(n_users, USER)
    add(orgs[rng.integers(0, N_ORGS, size=n_users)], users, 0)
    frontier = new_nodes(n_users, FOLDER)
    add(users, frontier, 0)
    parent_of_frontier = users.copy()

    level = 0
    while len(node_type) < n and frontier.shape[0] > 0 and level < MAX_LEVELS:
        nf = frontier.shape[0]
        room = n - len(node_type)
        full = room > (2 * FILES_PER_FOLDER + 1) * nf
        n_dev = nf if full else min(nf, room)
        n_files = FILES_PER_FOLDER * nf if full else (room - n_dev + 1) // 2
        n_fev = n_files if full else room - n_dev - n_files
        files = new_nodes(n_files, FILE)
        file_parent = np.repeat(frontier, FILES_PER_FOLDER)[:n_files]
        add(file_parent, files, 0)
        ev_f = new_nodes(n_fev, EVENT)
        add(files[:n_fev], ev_f, 0)
        gp = np.repeat(parent_of_frontier, FILES_PER_FOLDER)[:n_fev]
        tri = rng.random(n_fev) < 0.15
        first = np.where(tri, file_parent[:n_fev], gp)
        add(ev_f[tri], first[tri], 1)
        add(ev_f[~tri], first[~tri], 1)
        second = rng.random(n_fev) < 0.58
        add(ev_f[second], first[second], 2)
        ev_d = new_nodes(n_dev, EVENT)
        add(frontier[:n_dev], ev_d, 0)
        add(ev_d, parent_of_frontier[:n_dev], 1)
        if not full:
            break
        # Subfolders, each with room left for its own event.
        n_sub = min(SUBFOLDERS * nf, max(1, (n - len(node_type)) // 2))
        subs = new_nodes(n_sub, FOLDER)
        add(np.repeat(frontier, SUBFOLDERS)[:n_sub], subs, 0)
        parent_of_frontier = np.repeat(frontier, SUBFOLDERS)[:n_sub]
        frontier = subs
        level += 1
    if len(node_type) != n:
        raise ValueError(f"the tree holds {len(node_type)} vertices, not {n}")

    nt = np.array(node_type, dtype=np.int8)
    s = np.concatenate([e[0] for e in edges]).astype(np.int64)
    r = np.concatenate([e[1] for e in edges]).astype(np.int64)
    kind = np.concatenate([np.full(e[0].shape[0], e[2], np.int8) for e in edges])
    is_tree = kind == 0
    parent = np.full(n, -1, dtype=np.int64)
    parent[r[is_tree]] = s[is_tree]
    depth = np.zeros(n, dtype=np.int16)
    for _ in range(level + 6):
        has = parent >= 0
        depth[has] = depth[parent[has]] + 1
    r = np.where(kind == 2, parent[r], r)
    if n_edges is not None:
        s, r = _to_edge_count(s, r, kind, nt, parent, int(n_edges), rng)
    return EdgeList(
        n_nodes=n,
        senders=s.astype(np.int32),
        receivers=r.astype(np.int32),
        weights=np.ones(s.shape[0], dtype=np.float32),
        attrs={"node_type": nt, "depth": depth, "parent": parent},
    )


def _to_edge_count(s, r, kind, nt, parent, n_edges: int, rng):
    """Drop or add second references until there are ``n_edges`` edges;
    added ones go to file events without one, one level above their first
    reference, in vertex order, after every other edge."""
    have = s.shape[0]
    seconds = np.nonzero(kind == 2)[0]
    if have >= n_edges:
        if have - n_edges > seconds.shape[0]:
            raise ValueError(f"{have} edges, {seconds.shape[0]} of them droppable, "
                             f"cannot come down to {n_edges}")
        drop = rng.choice(seconds, size=have - n_edges, replace=False)
        keep = np.ones(have, dtype=bool)
        keep[drop] = False
        return s[keep], r[keep]
    file_events = np.nonzero((nt == EVENT) & (parent >= 0))[0]
    file_events = file_events[nt[parent[file_events]] == FILE]
    lacking = np.setdiff1d(file_events, s[seconds])
    if n_edges - have > lacking.shape[0]:
        raise ValueError(f"{have} edges and {lacking.shape[0]} file events without a second "
                         f"reference, cannot reach {n_edges}")
    ev = np.sort(rng.choice(lacking, size=n_edges - have, replace=False))
    first = np.full(nt.shape[0], -1, dtype=np.int64)
    first[s[kind == 1]] = r[kind == 1]
    return np.concatenate([s, ev]), np.concatenate([r, parent[first[ev]]])


def distinct_pairs(edges: EdgeList) -> int:
    """Edges that join a vertex pair no other edge joins, in either
    direction."""
    lo = np.minimum(edges.senders, edges.receivers).astype(np.int64)
    hi = np.maximum(edges.senders, edges.receivers).astype(np.int64)
    return int(np.unique(lo * edges.n_nodes + hi).shape[0])


def degree(edges: EdgeList) -> np.ndarray:
    """In-degree plus out-degree over the directed edge list."""
    n = edges.n_nodes
    return np.bincount(edges.senders, minlength=n) + np.bincount(edges.receivers, minlength=n)


def target_searches(node_type, parent, depth, deg, n_ops: int, rng):
    """One log's (starts, ends): the draws of ``_gen_filesystem``."""
    depth = np.asarray(depth, dtype=np.int64)
    candidates = np.nonzero((node_type == FILE) | (node_type == FOLDER))[0]
    p = deg[candidates].astype(np.float64)
    p /= p.sum()
    ends = rng.choice(candidates, size=n_ops, p=p)
    max_up = np.maximum(depth[ends] - 2, 1)
    ups = (rng.integers(0, 1 << 30, size=n_ops) % max_up) + 1
    starts = ends.copy()
    remaining = ups.copy()
    for _ in range(int(depth.max()) + 1):
        step = remaining > 0
        starts = np.where(step & (parent[starts] >= 0), parent[starts], starts)
        remaining = np.maximum(remaining - 1, 0)
    bad = node_type[starts] != FOLDER
    starts[bad] = np.where(parent[starts[bad]] >= 0, parent[starts[bad]], starts[bad])
    return starts.astype(np.int64), ends.astype(np.int64)


def search_csr(edges: EdgeList):
    """(indptr, indices) of the search's edges, folder → {folder, file},
    grouped by sender."""
    nt = edges.attrs["node_type"]
    s = edges.senders.astype(np.int64)
    r = edges.receivers.astype(np.int64)
    keep = (nt[s] == FOLDER) & ((nt[r] == FOLDER) | (nt[r] == FILE))
    s, r = s[keep], r[keep]
    order = np.argsort(s, kind="stable")
    indptr = np.concatenate([[0], np.cumsum(np.bincount(s, minlength=edges.n_nodes))])
    return indptr.astype(np.int64), r[order]


def target_search_counters(indptr, indices, parts, k: int, starts, ends, max_levels: int,
                           t_l: int = 2, t_pg: int = 1):
    """The four counters of target searches ``starts[i] → ends[i]``; an
    end of -1 is never found, so that op runs every level."""
    parts = np.asarray(parts, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    ends = np.asarray(ends, dtype=np.int64)
    n_ops = starts.shape[0]
    ops, nodes = np.arange(n_ops), starts
    steps = []
    for _ in range(max_levels):
        if ops.shape[0] == 0:
            break
        op, u, v = _expand(indptr, indices, nodes, ops)
        steps.append((op, u, v))
        found = np.zeros(n_ops, dtype=bool)
        found[op[v == ends[op]]] = True
        go_on = ~found[op]
        ops, nodes = op[go_on], v[go_on]
    if steps:
        op, u, v = (np.concatenate(x) for x in zip(*steps))
    else:
        op = u = v = np.zeros(0, dtype=np.int64)
    return _fold(op, u, v, parts, k, n_ops, parts.shape[0], t_l, t_pg)
