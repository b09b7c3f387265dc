"""The plain references agree with the program's numpy oracle on a small
Graph500 graph, and the benchmark's generator with the program's."""

import numpy as np
import pytest

from bench import reference as ref
from bench.graphgen import rmat_edges, search_keys

SCALE = 10
N = 1 << SCALE
SEED = 20240607


@pytest.fixture(scope="module")
def graph():
    return rmat_edges(SCALE, 16, SEED, a=0.57, b=0.19, c=0.19, permute=True)


@pytest.fixture(scope="module")
def engine(graph, tmp_path_factory):
    from repro.core.graph import Graph
    from repro.core.vsw import VSWEngine

    src, dst = graph
    eng = VSWEngine.from_graph(Graph(N, src, dst),
                               str(tmp_path_factory.mktemp("store")),
                               num_shards=4, window=256, k=16,
                               backend="numpy")
    yield eng
    eng.close()


def test_generator_matches_program():
    from repro.core.graph import rmat_graph

    src, dst = rmat_edges(SCALE, 16, SEED, a=0.57, b=0.19, c=0.19,
                          permute=False)
    g = rmat_graph(N, 16 * N, seed=SEED, a=0.57, b=0.19, c=0.19)
    assert np.array_equal(g.src, src) and np.array_equal(g.dst, dst)


def test_permuted_graph_is_a_relabelled_shuffle(graph):
    """Graph500 permutes the vertex labels and shuffles the edges: the same
    edges under one relabelling, in another order, with the hubs moved off
    the lowest ids."""
    src, dst = rmat_edges(SCALE, 16, SEED, a=0.57, b=0.19, c=0.19,
                          permute=False)
    rng = np.random.default_rng(SEED)
    for _ in range(SCALE):
        rng.random(16 * N)
    label = rng.permutation(N)
    want = np.sort(label[src].astype(np.int64) * N + label[dst])
    got = graph[0].astype(np.int64) * N + graph[1]
    assert np.array_equal(np.sort(got), want)
    assert not np.array_equal(got, label[src].astype(np.int64) * N
                              + label[dst])
    hub = np.argmax(np.bincount(src, minlength=N))
    assert hub == 0 and np.argmax(np.bincount(graph[0], minlength=N)) \
        == label[0]


def _source(graph):
    return int(search_keys(graph[0], N, 1, np.random.default_rng(5))[0])


@pytest.mark.parametrize("program", ["pagerank", "bfs", "sssp", "ppr"])
def test_reference_matches_numpy_engine(program, graph, engine):
    from repro.core import apps

    src, dst = graph
    s = _source(graph)
    if program == "pagerank":
        got = engine.run(apps.pagerank(0.85), max_iters=10).values
        want = ref.pagerank(src, dst, N, damping=0.85, iterations=10)
        assert ref.rel_l1(got, want) < 1e-5
    elif program == "ppr":
        got = engine.run(apps.personalized_pagerank(s, 0.85),
                         max_iters=10).values
        want = ref.ppr(src, dst, N, s, damping=0.85, max_iters=10)
        assert ref.rel_l1(got, want) < 1e-5
    else:
        r = engine.run(apps.get_program(program, source=s), max_iters=100)
        want = ref.bfs_levels(src, dst, N, s, max_iters=100)
        assert ref.level_mismatch(r.values, want) == 0
        assert r.converged and r.num_iterations == ref.depth(want) + 1


def test_truncated_levels_match_engine_budget(graph, engine):
    from repro.core import apps

    src, dst = graph
    s = _source(graph)
    r = engine.run(apps.bfs(s), max_iters=2)
    want = ref.bfs_levels(src, dst, N, s, max_iters=2)
    assert ref.level_mismatch(r.values, want) == 0
    assert np.isfinite(want).sum() < np.isfinite(
        ref.bfs_levels(src, dst, N, s, max_iters=100)).sum()


def test_search_keys_have_out_edges(graph):
    keys = search_keys(graph[0], N, 64, np.random.default_rng(1))
    deg = np.bincount(graph[0], minlength=N)
    assert len(set(keys.tolist())) == 64 and (deg[keys] > 0).all()
