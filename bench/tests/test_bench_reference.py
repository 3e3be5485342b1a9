"""The plain reference against closed forms on tiny graphs, and its
comparison of answers."""

import numpy as np
import pytest

import bench_paths  # noqa: F401  (puts bench/ on the path)
import reference

C = 0.15


def exact(src, dst, n, sources, iterations=300):
    ref = reference.Reference(src, dst, n, c=C, iterations=iterations,
                              block=len(sources))
    return ref, np.asarray(ref.ppr(sources), np.float64)


def test_cycle():
    n = 7
    src = np.arange(n)
    _, x = exact(src, (src + 1) % n, n, [0, 3])
    k = np.arange(n)
    want = C * (1 - C) ** k / (1 - (1 - C) ** n)
    np.testing.assert_allclose(x[0], want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(x[1], np.roll(want, 3), rtol=1e-5, atol=1e-7)


def test_star_from_hub_and_spoke():
    n = 6
    spokes = np.arange(1, n)
    src = np.concatenate([np.zeros(n - 1, int), spokes])
    dst = np.concatenate([spokes, np.zeros(n - 1, int)])
    _, x = exact(src, dst, n, [0, 2])
    norm = 1 - (1 - C) ** 2
    np.testing.assert_allclose(x[0, 0], C / norm, rtol=1e-5)
    np.testing.assert_allclose(x[0, 1:], C * (1 - C) / norm / (n - 1),
                               rtol=1e-5)
    # from spoke j: a = c + (1-c) h / (n-1), o = (1-c) h / (n-1), and
    # h = (1-c)(a + (n-2) o), so h = (1-c) c / (1 - (1-c)^2)
    h = (1 - C) * C / norm
    o = (1 - C) * h / (n - 1)
    np.testing.assert_allclose(x[1, 0], h, rtol=1e-5)
    np.testing.assert_allclose(x[1, 2], C + o, rtol=1e-5)
    np.testing.assert_allclose(np.delete(x[1], [0, 2]), o, rtol=1e-5)


def test_dangling_vertex_returns_to_the_source():
    # 0 -> 1 and 1 has no out-edge: the walk jumps back to its source
    _, x = exact([0], [1], 3, [0, 1])
    norm = 1 - (1 - C) ** 2
    np.testing.assert_allclose(x[0], [C / norm, C * (1 - C) / norm, 0],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(x[1], [0, 1, 0], atol=1e-6)


def test_compare_exact_and_altered_answers():
    n = 7
    src = np.arange(n)
    dst = (src + 1) % n
    ref, x = exact(src, dst, n, [0, 4])
    np.testing.assert_allclose(
        ref.compare([0, 4], np.tile(np.arange(n), (2, 1)), x), 0, atol=1e-6)
    top = np.argsort(-x, axis=1)[:, :3]
    scores = np.take_along_axis(x, top, axis=1)
    # the rest of the vector is missing from a top-3 answer
    rest = np.sort(x, axis=1)[:, :-3]
    want = (rest ** 2).sum(1) / (x ** 2).sum(1)
    np.testing.assert_allclose(ref.compare([0, 4], top, scores), want,
                               rtol=1e-4)
    wrong = ref.compare([0, 4], (top + 1) % n, scores)
    assert (wrong > want + 0.1).all()


@pytest.mark.parametrize("seed", [0, 2**33 + 1])
def test_graph500_edges_are_simple_and_seeded(seed):
    import graphgen

    key, labels = graphgen.seed_key(7, 0), graphgen.seed_key(seed, 0)
    kwargs = dict(scale=8, edge_factor=16, a=0.57, b=0.19, c=0.19)
    src, dst, perm = graphgen.graph500_edges(key, labels, **kwargs)
    again = graphgen.graph500_edges(key, labels, **kwargs)
    for x, y in zip((src, dst, perm), again):
        np.testing.assert_array_equal(x, y)
    assert (src != dst).all()
    pairs = src.astype(np.int64) * 256 + dst
    assert 2000 < len(src) < 16 * 256
    assert src.max() < 256 and dst.max() < 256
    assert (np.diff(pairs) > 0).all()  # sorted by source, then destination
    # every edge that is not simple (a self-loop or a repeat) sorts last
    full_src, full_dst, simple, _ = graphgen.rmat_edges(key, labels, **kwargs)
    assert int(simple) == len(src) and len(full_src) == 16 * 256
    assert (np.asarray(full_src)[len(src):] == 256).all()
    # another label key: the same structure under other labels
    src2, dst2, perm2 = graphgen.graph500_edges(
        key, graphgen.seed_key(seed + 1, 0), **kwargs)
    assert not np.array_equal(perm, perm2)

    def structure(s, d, p):
        inv = np.argsort(p)
        return sorted(zip(inv[s].tolist(), inv[d].tolist()))

    assert structure(src, dst, perm) == structure(src2, dst2, perm2)


@pytest.mark.parametrize("edge_chunk", [3, 64, 1 << 22])
def test_random_graph_matches_linear_solve(edge_chunk):
    """x_s = c e_s (I - (1-c) P_s)^-1, with the dangling rows of P_s on s,
    for edges cut into running-sum slices of any width."""
    rng = np.random.default_rng(3)
    n = 40
    src, dst = rng.integers(0, n, 120), rng.integers(0, n, 120)
    sources = [0, 7, 13]
    ref = reference.Reference(src, dst, n, c=C, iterations=200,
                              block=len(sources), edge_chunk=edge_chunk)
    got = np.asarray(ref.ppr(sources), np.float64)
    deg = np.bincount(src, minlength=n)
    for row, s in enumerate(sources):
        p = np.zeros((n, n))
        np.add.at(p, (src, dst), 1.0 / deg[src])
        p[deg == 0, s] = 1.0
        e = np.zeros(n)
        e[s] = C
        want = np.linalg.solve((np.eye(n) - (1 - C) * p).T, e)
        np.testing.assert_allclose(got[row], want, rtol=1e-4, atol=1e-7)
