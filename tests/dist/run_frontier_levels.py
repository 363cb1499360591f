"""The frontier each BFS level hands on, checked level by level.

    python tests/dist/run_frontier_levels.py R C

On an R x C grid (forced host devices when R * C > 1), for the `list` and
`bitmap` fold codecs and the top-down, adaptive and bottom-up directions,
steps a segmented search one level at a time and checks after every level
that each device's carried frontier is its owned rows at that level, as
local cols, ascending, with its count.  The graphs are a small R-MAT draw
and the long strip of `run_deep_bfs.py`, deeper than `RECORDED_LEVELS`.
At the end the levels equal the plain reference's, the parents pass
Graph500's rules, levels, parents and `n_levels` are the same for every
codec and direction, and `edges_scanned` for every codec.

Prints one JSON object (case -> levels checked), then OK.
`tests/test_frontier_levels.py` runs the 1x1 grid in its own process
through `check` and the others through this script.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import numpy as np

CODECS = ("list", "bitmap")
MODES = {"topdown": False, "adaptive": "adaptive", "bottomup": "bottomup"}
GRAPHS = ("rmat", "strip")
RMAT_SCALE, RMAT_EF = 8, 8


def make_graph(name: str):
    """(symmetrised (2, E) edges, n, roots, alpha, beta) of a test graph.
    alpha and beta make the adaptive mode switch both ways on it."""
    if name == "strip":
        here = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, here)
        from run_deep_bfs import strip_graph

        edges, n, roots = strip_graph()
        # bottom-up above n / 120 (3 vertices), back below n / 360 (1)
        return edges, n, roots, 120, 360
    import jax

    from repro.graphgen import rmat_edges

    n = 1 << RMAT_SCALE
    edges = np.asarray(rmat_edges(jax.random.key(11), RMAT_SCALE, RMAT_EF))
    edges = edges[:, edges[0] != edges[1]].astype(np.int32)
    deg = np.bincount(edges[0], minlength=n)
    roots = np.random.default_rng(5).choice(
        np.flatnonzero(deg > 0), 3, replace=False).astype(np.int32)
    return edges, n, roots, 8, 32


def owned_fronts(level, lvl, grid):
    """(R, C, B, S) expected frontiers and (R, C, B) counts: the owned rows
    at level lvl, as local cols, ascending, padded with -1."""
    R, C, S = grid.R, grid.C, grid.S
    B = level.shape[2]
    front = np.full((R, C, B, S), -1, np.int32)
    cnt = np.zeros((R, C, B), np.int32)
    for i in range(R):
        for j in range(C):
            for b in range(B):
                t = np.flatnonzero(level[i, j, b, j * S:(j + 1) * S]
                                   == lvl[i, j, b])
                front[i, j, b, :t.size] = i * S + t
                cnt[i, j, b] = t.size
    return front, cnt


def check(R: int, C: int) -> dict:
    from repro.algos.reference import multi_bfs_reference
    from repro.api import BFSConfig, DistGraph
    from repro.core.validate import edge_keys, validate_bfs
    from repro.dist import multihost

    checked = {}
    for gname in GRAPHS:
        edges, n, roots, alpha, beta = make_graph(gname)
        keys = edge_keys(edges, n)
        refs = [multi_bfs_reference(edges, n, [int(r)])[0] for r in roots]
        base = BFSConfig(grid=(R, C), edge_chunk=64, direction=True,
                         alpha=alpha, beta=beta, fault_tolerance=True,
                         ckpt_every=1)
        graph = DistGraph.from_edges(edges, base, n=n)
        grid = graph.grid
        arg = multihost.put_replicated(roots, graph.mesh)
        first, scanned = None, {}
        for codec in CODECS:
            for mode, direction in MODES.items():
                case = f"{gname}/{codec}/{mode}"
                sess = graph.session(dataclasses.replace(
                    base, fold_codec=codec, direction=direction))
                eng = sess.engine
                carry = eng.ft_start(graph.csc, arg, *sess._extra,
                                     batched=True)
                levels = 0
                while eng.ft_active(carry):
                    carry = eng.ft_segment(graph.csc, carry, *sess._extra,
                                           batched=True)
                    st = carry["st"] if mode == "topdown" \
                        else carry["st"].inner
                    level, front, cnt, lvl = (
                        np.asarray(multihost.fetch(a)) for a in
                        (st.level, st.front, st.front_cnt, st.lvl))
                    want, want_cnt = owned_fronts(level, lvl - 1, grid)
                    np.testing.assert_array_equal(cnt, want_cnt, case)
                    np.testing.assert_array_equal(front, want, case)
                    levels += 1
                out = eng.ft_finish(carry, B=len(roots))
                lv, pr = np.asarray(out.level), np.asarray(out.pred)
                for b, root in enumerate(roots):
                    np.testing.assert_array_equal(lv[b][:n], refs[b], case)
                    validate_bfs(edges, lv[b][:n], pr[b][:n], int(root),
                                 keys)
                # bit-identical across codecs and directions; the edges a
                # direction scans are its own
                got = (lv, pr, np.asarray(out.n_levels))
                first = got if first is None else first
                for a, w in zip(got, first):
                    np.testing.assert_array_equal(a, w, case)
                es = scanned.setdefault(mode, tuple(out.edges_scanned))
                assert tuple(out.edges_scanned) == es, case
                assert levels == max(int(r.max()) for r in refs) + 1, case
                checked[case] = levels
    return checked


if __name__ == "__main__":
    R, C = int(sys.argv[1]), int(sys.argv[2])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count"
                               f"={R * C}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "src"))
    import jax

    assert jax.device_count() >= R * C, jax.devices()
    print(json.dumps(check(R, C)))
    print("OK")
