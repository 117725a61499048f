"""SELL-C-sigma format: construction, round-trip, special cases,
permutation handling, storage efficiency.  Includes hypothesis property
tests over random sparsity patterns."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import (SellCS, from_callback, from_coo, from_csr,
                        from_dense, to_dense, spmv_ref)


def random_sparse(rng, n, m, density=0.1):
    a = (rng.random((n, m)) < density) * rng.standard_normal((n, m))
    return a.astype(np.float32)


class TestConstruction:
    @pytest.mark.parametrize("C,sigma,w_align", [
        (1, 1, 1), (2, 4, 1), (4, 8, 2), (8, 16, 4), (16, 1, 8), (32, 64, 8),
    ])
    def test_roundtrip(self, rng, C, sigma, w_align):
        a = random_sparse(rng, 57, 57)
        m = from_dense(a, C=C, sigma=sigma, w_align=w_align)
        assert np.allclose(to_dense(m), a)
        assert m.nnz == (a != 0).sum()

    def test_rectangular(self, rng):
        a = random_sparse(rng, 40, 23)
        m = from_dense(a, C=8, sigma=1)
        assert not m.permuted_cols
        assert np.allclose(to_dense(m), a)

    def test_crs_is_sell_1_1(self, rng):
        """Paper section 3.1: CRS == SELL-1-1 (no padding at all)."""
        a = random_sparse(rng, 30, 30, 0.2)
        m = from_dense(a, C=1, sigma=1)
        # beta = nnz / cap can only be < 1 because empty rows take 1 slot
        nempty = int((np.count_nonzero(a, axis=1) == 0).sum())
        assert m.cap == m.nnz + nempty

    def test_sigma_sorting_improves_beta(self, rng):
        # strongly varying row lengths: sigma-sorting must reduce padding
        n = 256
        a = np.zeros((n, n), np.float32)
        for i in range(n):
            k = 1 + (i * 7) % 32
            cols = rng.choice(n, size=k, replace=False)
            a[i, cols] = 1.0
        m1 = from_dense(a, C=16, sigma=1)
        m2 = from_dense(a, C=16, sigma=256)
        assert m2.beta > m1.beta

    def test_from_csr(self, rng):
        a = random_sparse(rng, 25, 25)
        indptr = np.concatenate([[0], np.cumsum((a != 0).sum(1))])
        indices = np.concatenate([np.nonzero(a[i])[0] for i in range(25)])
        data = np.concatenate([a[i][a[i] != 0] for i in range(25)])
        m = from_csr(indptr, indices, data, (25, 25), C=4, sigma=8)
        assert np.allclose(to_dense(m), a)

    def test_from_callback(self):
        """Paper's preferred construction: per-row callback."""
        def row(i):
            cols = [i, (i + 1) % 10]
            vals = [2.0, -1.0]
            return np.array(cols), np.array(vals)

        m = from_callback(row, 10, C=2, sigma=4)
        d = to_dense(m)
        assert np.allclose(np.diag(d), 2.0)
        assert m.nnz == 20

    def test_duplicate_entries_summed(self):
        m = from_coo([0, 0], [1, 1], [2.0, 3.0], (2, 2), C=1)
        assert to_dense(m)[0, 1] == 5.0

    def test_permute_unpermute_identity(self, rng):
        a = random_sparse(rng, 37, 37)
        m = from_dense(a, C=8, sigma=16)
        v = rng.standard_normal((37, 3)).astype(np.float32)
        assert np.allclose(m.unpermute(m.permute(v)), v)

    def test_bad_inputs(self):
        with pytest.raises(ValueError):
            from_coo([5], [0], [1.0], (3, 3), C=2)
        with pytest.raises(ValueError):
            from_coo([0], [0], [1.0], (3, 3), C=4, sigma=6)  # sigma % C != 0


class TestUniformWidth:
    """Chunks of one common width take the scatter-free row sums."""

    def test_stencil_is_uniform(self):
        from repro.matrices import laplace3d
        r, c, v, n = laplace3d(6)
        assert from_coo(r, c, v, (n, n), C=8, w_align=8).uniform_width == 8
        assert from_coo(r, c, v, (n, n), C=8, w_align=1).uniform_width == 0

    @pytest.mark.parametrize("b", [1, 3])
    def test_width_sum_matches_segment_sum(self, rng, b):
        import dataclasses
        a = random_sparse(rng, 64, 64, 0.1)
        m = from_dense(a, C=8, sigma=1, w_align=64)    # every chunk 64 wide
        assert m.uniform_width == 64
        ragged = dataclasses.replace(m, uniform_width=0)
        x = m.permute(rng.standard_normal((64, b)).astype(np.float32))
        y, _, _ = spmv_ref(m, x)
        y_seg, _, _ = spmv_ref(ragged, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_seg),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(m.unpermute(y)),
                                   a @ np.asarray(m.unpermute(x)),
                                   rtol=1e-4, atol=1e-4)


class TestStoredZeros:
    """Slot validity comes from construction-recorded row lengths, so
    explicitly stored zeros are structure, not padding."""

    def test_explicit_zero_counted(self):
        m = from_coo([0, 0, 1], [0, 1, 1], [0.0, 2.0, 3.0], (2, 2), C=2)
        assert m.nnz == 3
        rl = m.nnz_per_row()
        assert rl[0] == 2 and rl[1] == 1     # old vals!=0 logic said 1, 1
        assert int(m.valid_slots().sum()) == 3

    def test_duplicates_summing_to_zero_counted(self):
        m = from_coo([0, 0, 1], [1, 1, 0], [2.0, -2.0, 4.0], (2, 2), C=1)
        assert m.nnz == 2                    # deduplicated, zero-sum kept
        rl = m.nnz_per_row()
        assert rl[0] == 1 and rl[1] == 1

    def test_zero_slot_column_remapped(self, rng):
        """The permuted-column remap must include stored-zero slots; with
        sigma sorting active an unremapped column would alias another row
        after to_dense's perm mapping."""
        n = 8
        a = np.zeros((n, n), np.float32)
        # ragged row lengths to force a non-trivial sigma permutation
        for i in range(n):
            a[i, : (i % 4) + 1] = i + 1.0
        r, c = np.nonzero(a)
        v = a[r, c]
        # explicit zero stored at (0, 5)
        r = np.concatenate([r, [0]])
        c = np.concatenate([c, [5]])
        v = np.concatenate([v, [0.0]]).astype(np.float32)
        m = from_coo(r, c, v, (n, n), C=4, sigma=8)
        assert m.permuted_cols
        np.testing.assert_allclose(to_dense(m), a)
        # the zero keeps its row slot in the counts
        iperm = np.asarray(m.iperm)
        assert m.nnz_per_row()[iperm[0]] == 2

    def test_nnz_per_row_matches_dense_structure(self, rng):
        a = random_sparse(rng, 40, 40, 0.2)
        m = from_dense(a, C=8, sigma=16, w_align=2)
        perm = np.asarray(m.perm)
        want = np.zeros(m.nrows_pad, np.int64)
        counts = (a != 0).sum(axis=1)
        want[: len(perm)] = np.where(perm < m.nrows, counts[np.minimum(perm, m.nrows - 1)], 0)
        np.testing.assert_array_equal(m.nnz_per_row(), want)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(5, 80), seed=st.integers(0, 2**31 - 1),
       C=st.sampled_from([1, 2, 4, 8]), sigma_f=st.sampled_from([1, 2, 4]))
def test_property_spmv_matches_dense(n, seed, C, sigma_f):
    """Property: for any random pattern, SELL-C-sigma SpMV == dense @."""
    rng = np.random.default_rng(seed)
    a = ((rng.random((n, n)) < 0.2) * rng.standard_normal((n, n))
         ).astype(np.float32)
    sigma = 1 if sigma_f == 1 else C * sigma_f
    m = from_dense(a, C=C, sigma=sigma)
    x = rng.standard_normal(n).astype(np.float32)
    y, _, _ = spmv_ref(m, m.permute(x))
    np.testing.assert_allclose(m.unpermute(y), a @ x, atol=1e-4, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 60), seed=st.integers(0, 2**31 - 1))
def test_property_beta_bounds(n, seed):
    """Property: 0 < beta <= 1 and cap >= nnz."""
    rng = np.random.default_rng(seed)
    a = ((rng.random((n, n)) < 0.3) * 1.0).astype(np.float32)
    m = from_dense(a, C=4, sigma=8)
    assert 0 < m.beta <= 1.0
    assert m.cap >= m.nnz
