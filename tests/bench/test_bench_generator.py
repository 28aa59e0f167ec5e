"""The benchmark's Kronecker generator: seeded, exact, Graph500's initiator."""
import hashlib

import numpy as np
import pytest

from bench import kronecker


def _edges(scale, seed, edgefactor=16):
    src, dst, has = kronecker.kronecker_edges(scale, edgefactor, 0.57, 0.19, 0.19, seed)
    return np.asarray(src), np.asarray(dst), np.asarray(has)


def test_same_seed_same_edges_other_seed_other_edges():
    a, b = _edges(9, 123), _edges(9, 123)
    c = _edges(9, 124)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])


def test_seeds_beyond_32_bits_differ_in_their_high_word():
    lo, hi = _edges(8, 5), _edges(8, 5 + (1 << 32))
    assert not np.array_equal(lo[0], hi[0])
    np.testing.assert_array_equal(_edges(8, 2**31 + 11)[0], _edges(8, 2**31 + 11)[0])


def test_quadrant_frequencies_match_the_initiator():
    # before the relabeling, at scale 1 an edge's quadrant is 2*src + dst
    bounds = kronecker.thresholds(0.57, 0.19, 0.19)
    src, dst = kronecker.levels(kronecker.seed_key(99), bounds, 1, 1 << 17)
    src, dst = np.asarray(src), np.asarray(dst)
    freq = np.bincount(2 * src + dst, minlength=4) / src.size
    np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.005)


def test_labels_are_a_random_permutation_applied_to_every_endpoint():
    key, bounds = kronecker.seed_key(31), kronecker.thresholds(0.57, 0.19, 0.19)
    perm = np.asarray(kronecker.labels(key, 9))
    assert sorted(perm.tolist()) == list(range(1 << 9))
    assert (perm != np.arange(1 << 9)).mean() > 0.9
    raw_src, raw_dst = (np.asarray(x) for x in kronecker.levels(key, bounds, 9, 16 << 9))
    src, dst, _ = _edges(9, 31)
    np.testing.assert_array_equal(src, perm[raw_src])
    np.testing.assert_array_equal(dst, perm[raw_dst])
    # the relabeling hides the generator's order: the highest-degree vertex
    # is no longer vertex 0
    assert np.bincount(src, minlength=1 << 9).argmax() == perm[0]


def test_has_edge_marks_endpoints_of_non_loop_tuples():
    src, dst, has = _edges(9, 3)
    want = np.zeros(1 << 9, bool)
    keep = src != dst
    want[src[keep]] = True
    want[dst[keep]] = True
    np.testing.assert_array_equal(has, want)


def test_thresholds_are_the_cumulative_probabilities():
    t = kronecker.thresholds(0.57, 0.19, 0.19)
    np.testing.assert_allclose(t / 2.0**32, [0.57, 0.76, 0.95], atol=1e-9)


# sha256 of (src, dst, has_edge) bytes, as one v5e computed them
CHIP_DIGESTS = {
    (10, 500): "9771f6adfa3dad8de492e9769e16f342332f427dc9a3780f26633699747d25bd",
    (12, 2**33 + 5): "ea67f2ca67df94e4fcbb8437706f9bf8c1589a255652f0619f52ad97049bfd9e",
}


@pytest.mark.parametrize("scale,seed", sorted(CHIP_DIGESTS))
def test_the_cpu_draws_the_edges_the_chip_drew(scale, seed):
    src, dst, has = _edges(scale, seed)
    digest = hashlib.sha256(src.tobytes() + dst.tobytes() + has.tobytes()).hexdigest()
    assert digest == CHIP_DIGESTS[(scale, seed)]
