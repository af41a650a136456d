"""The seeded on-device generators, at small scale on the CPU."""

import numpy as np
import pytest

from perfbench.generators import kronecker, ratings, seed_key

KRON = {"generator": "kronecker", "scale": 12, "edgefactor": 16,
        "A": 0.57, "B": 0.19, "C": 0.19}
RATINGS = {"generator": "ratings", "users": 3000, "items": 120,
           "ratings": 40000,
           "rating_probs": [0.046, 0.101, 0.287, 0.336, 0.23],
           "user_exponent": 1.513, "item_exponent": 1.613}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("mod, cfg", [(kronecker, KRON), (ratings, RATINGS)])
def test_same_seed_same_bytes(mod, cfg):
    a, b = mod.generate(cfg, BIG_SEED), mod.generate(cfg, BIG_SEED)
    assert a.row_ptr.tobytes() == b.row_ptr.tobytes()
    assert a.col_src.tobytes() == b.col_src.tobytes()
    if a.weights is not None:
        assert a.weights.tobytes() == b.weights.tobytes()
    c = mod.generate(cfg, BIG_SEED + 1)
    assert c.col_src.tobytes() != a.col_src.tobytes()


def test_seed_key_uses_every_bit():
    import jax

    k = [jax.random.key_data(seed_key(s)).tolist()
         for s in (7, 2**32 + 7, 2**33 + 7)]
    assert len({tuple(x) for x in k}) == 3
    with pytest.raises(ValueError):
        seed_key(-1)


def test_kronecker_sizes_and_csc():
    g = kronecker.generate(KRON, 3)
    nv, ne = 1 << KRON["scale"], KRON["edgefactor"] << KRON["scale"]
    assert (g.nv, g.ne) == (nv, ne)
    assert g.row_ptr.shape == (nv + 1,) and g.row_ptr[0] == 0
    assert g.row_ptr[-1] == ne and np.all(np.diff(g.row_ptr) >= 0)
    assert g.col_src.min() >= 0 and g.col_src.max() < nv
    # sorted by (destination, source)
    key = g.col_dst.astype(np.int64) * nv + g.col_src
    assert np.all(np.diff(key) >= 0)
    assert g.weights is None


def test_kronecker_quadrant_probabilities():
    """Each bit level of the unpermuted edges picks quadrant (src bit,
    dst bit) with probability A, B, C, D within sampling tolerance."""
    import jax

    from perfbench.generators.kronecker import kronecker_edges

    scale, ne = 10, 1 << 16
    a, b, c = KRON["A"], KRON["B"], KRON["C"]
    src, dst = (np.asarray(x) for x in jax.jit(
        kronecker_edges, static_argnums=(1, 2, 3, 4, 5))(
            seed_key(5), scale, ne, a, b, c))
    want = np.array([a, b, c, 1 - a - b - c])
    tol = 4 * np.sqrt(want * (1 - want) / ne)
    for bit in range(scale):
        q = ((src >> bit) & 1) * 2 + ((dst >> bit) & 1)
        got = np.bincount(q, minlength=4) / ne
        assert np.all(np.abs(got - want) < tol), (bit, got, want)


def test_kronecker_in_degree_skew():
    """Unpermuted, a destination's expected in-degree is
    ne * (A+C)^(zero bits) * (B+D)^(one bits): the in-degree of the
    all-zero destination, the largest, matches that within tolerance."""
    import jax

    from perfbench.generators.kronecker import kronecker_edges

    scale, ne = 8, 1 << 18
    a, b, c = KRON["A"], KRON["B"], KRON["C"]
    _, dst = (np.asarray(x) for x in jax.jit(
        kronecker_edges, static_argnums=(1, 2, 3, 4, 5))(
            seed_key(9), scale, ne, a, b, c))
    deg = np.bincount(dst, minlength=1 << scale)
    ones = np.array([bin(v).count("1") for v in range(1 << scale)])
    expect = ne * (a + c) ** (scale - ones) * (1 - a - c) ** ones
    top = expect.argmax()
    assert top == 0 and deg.argmax() == 0
    assert abs(deg[0] - expect[0]) < 5 * np.sqrt(expect[0])
    for k in range(scale + 1):   # grouped by the number of one bits
        got, want = deg[ones == k].sum(), expect[ones == k].sum()
        assert abs(got - want) < 5 * np.sqrt(want) + 1, (k, got, want)


def test_ratings_shape():
    g = ratings.generate(RATINGS, 11)
    users, items, n = RATINGS["users"], RATINGS["items"], RATINGS["ratings"]
    assert (g.nv, g.ne) == (users + items, 2 * n)
    assert g.weights.min() >= 1 and g.weights.max() <= 5
    dst = g.col_dst
    src = g.col_src
    is_item_dst = dst >= users
    # every edge joins a user and an item; each rating appears both ways
    assert np.all((src >= users) != is_item_dst)
    assert is_item_dst.sum() == n
    fwd = sorted(zip(src[is_item_dst], dst[is_item_dst],
                     g.weights[is_item_dst]))
    bwd = sorted(zip(dst[~is_item_dst], src[~is_item_dst],
                     g.weights[~is_item_dst]))
    assert fwd == bwd
    share = np.bincount(g.weights, minlength=6)[1:] / g.ne
    assert np.allclose(share, RATINGS["rating_probs"], atol=0.01)
