"""The windowed SELL-128 SpMV kernel (``kernels/sellcs_window.py``).

Parity with the jnp oracle ``spmv_ref`` runs the kernel in interpret
mode; the ``auto`` resolution tests patch the kernel's backend check
(``sellcs_window.compiled``), since off a TPU ``auto`` always resolves
to the jnp product.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sellcs import from_coo
from repro.core.spmv import SpmvOpts, spmv, spmv_ref
from repro.kernels import sellcs_window as sw
from repro.matrices.generators import laplace3d
from repro.runtime import MatrixRegistry
from repro.solvers.cg import cg
from repro.solvers.operator import DistOperator, GhostOperator


@pytest.fixture
def on_tpu(monkeypatch):
    """Let ``plan`` take the backend for one that compiles the kernel."""
    monkeypatch.setattr(sw, "compiled", lambda interpret=None: True)


def _coo(rows, cols, n, rng):
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    return np.asarray(rows), np.asarray(cols), vals, n


def _one_block(rng):
    """Every chunk's columns lie in its own 128 rows."""
    n = 512
    r, c = np.nonzero(rng.random((n, 128)) < 0.05)
    return _coo(r, (r // 128) * 128 + c, n, rng)


def _many_blocks(rng):
    """Eight columns per row, anywhere: up to 8 blocks a chunk."""
    n = 1024
    c = np.stack([rng.choice(n, 8, replace=False) for _ in range(n)])
    return _coo(np.repeat(np.arange(n), 8), c.ravel(), n, rng)


def _empty_rows(rng):
    """A fifth of the rows empty, and all of chunk 1."""
    n = 640
    r, c = np.nonzero(rng.random((n, n)) < 0.008)
    keep = (rng.random(r.size) < 0.8) & ((r < 128) | (r >= 256))
    keep &= np.isin(r, np.nonzero(rng.random(n) < 0.8)[0])
    return _coo(r[keep], c[keep], n, rng)


def _laplace(rng):
    """laplace3d(8): rows of 4 to 7 entries in slots 8 wide."""
    r, c, v, n = laplace3d(8)
    return np.asarray(r), np.asarray(c), np.asarray(v, np.float32), n


def _ragged_group(rng):
    """2,500 rows (20 chunks, the last part padding rows) 32 slots wide,
    so the 16 chunks of a grid step do not divide the chunks."""
    n = 2500
    k = rng.integers(1, 33, n)
    r = np.repeat(np.arange(n), k)
    c = np.concatenate([rng.choice(n, m, replace=False) for m in k])
    return _coo(r, c, n, rng)


def _wide_few(rng):
    """64 chunks 8 slots wide: chunk 0's slot rows each span 16 column
    blocks, every other chunk's rows lie in its own block.  Every chunk
    would pay chunk 0's 16 blocks a slot row (128 steps) on the slot
    rows; on block rounds only chunk 0 takes more than one round."""
    n = 64 * 128
    r = np.repeat(np.arange(n), 8)
    own = (r // 128) * 128 + np.concatenate(
        [rng.choice(128, 8, replace=False) for _ in range(n)])
    # row l of chunk 0: one column in each of blocks l % 16 ... l % 16 + 7
    spread = (r % 16 + np.tile(np.arange(8), n)) * 128 + r % 128
    return _coo(r, np.where(r < 128, spread, own), n, rng)


MATRICES = {"one_block": (_one_block, 16), "many_blocks": (_many_blocks, 8),
            "empty_rows": (_empty_rows, 16), "padding": (_laplace, 8),
            "ragged_group": (_ragged_group, 32), "wide_few": (_wide_few, 8)}

#: the layout ``plan`` takes for each matrix at b > 1, and why
LAYOUTS = {"one_block": "block_rounds",       # 16 steps a chunk either way
           "many_blocks": "slot_rows",        # 51 steps against 64
           "empty_rows": "slot_rows",         # 44 against 64
           "padding": "slot_rows",            # 11 against 24
           "ragged_group": "block_rounds",    # 415 steps: too long to unroll
           "wide_few": "block_rounds"}        # 128 against 66


def _matrix(name, seed=0):
    gen, w_align = MATRICES[name]
    r, c, v, n = gen(np.random.default_rng(seed))
    A = from_coo(r, c, v, (n, n), C=128, w_align=w_align, dtype=np.float32)
    assert A.uniform_width
    return A


def _opts(name, b, rng):
    g = jnp.asarray(rng.standard_normal(b), jnp.float32)
    return {
        "plain": SpmvOpts(),
        "cg_dot_xy": SpmvOpts(dot_xy=True),
        "precond_alpha_beta": SpmvOpts(alpha=-1.0, beta=1.0),
        "kpm_shift_dots": SpmvOpts(alpha=0.5, gamma=g, dot_xx=True,
                                   dot_xy=True, dot_yy=True),
        "chain_axpby": SpmvOpts(alpha=2.0, beta=-1.0, delta=0.5, eta=1.5,
                                dot_yy=True),
    }[name]


# (matrix, b, opts, layout): layout None is the one plan chooses; the
# slot-rows cases build that layout where plan would not take it
PARITY = ([(m, b, "plain", None) for m in MATRICES for b in (1, 8)]
          + [("padding", 8, o, None)
             for o in ("cg_dot_xy", "precond_alpha_beta", "kpm_shift_dots",
                       "chain_axpby")]
          + [("padding", 1, "kpm_shift_dots", None)]
          + [(m, 8, "plain", "slot_rows") for m in MATRICES
             if LAYOUTS[m] != "slot_rows"])


@pytest.mark.parametrize("matrix,b,opts,layout", [
    pytest.param(*case, id="-".join(str(a) for a in case if a is not None))
    for case in PARITY])
def test_window_matches_ref(on_tpu, monkeypatch, matrix, b, opts, layout):
    A = _matrix(matrix)
    p = sw.plan(A)
    assert p is not None and p.layout == LAYOUTS[matrix]
    if layout is not None:
        monkeypatch.setattr(sw, "_layout", lambda *steps: layout)
        p = sw.plan(A)
        assert p.layout == layout
    walks, kernel = set(), sw._kernel

    def spy(*args, **kw):
        walks.add(kw["layout"])
        return kernel(*args, **kw)
    monkeypatch.setattr(sw, "_kernel", spy)
    rounds = np.asarray(p.lists)[:, 0, ::p.row].ravel()[:p.nchunks]
    if matrix == "one_block":
        assert p.maxb == 1
    if matrix == "many_blocks":
        assert p.maxb == 8
    if matrix == "empty_rows":
        assert rounds[1] == 0
    if matrix == "ragged_group":
        assert p.nchunks % p.group
    if matrix == "wide_few":
        assert p.maxb == 23 and p.unroll == 8
        assert rounds[0] == 3 and (rounds[1:] == 1).all()
    rng = np.random.default_rng(1)
    o = _opts(opts, b, rng)
    n = A.nrows_pad
    x = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    if b == 1:
        x, y, z = x[:, 0], y[:, 0], z[:, 0]
    yin = y if o.beta else None
    zin = z if o.chain_axpby else None
    got = spmv(A, x, yin, zin, o, impl="window", window=p)
    want = spmv_ref(A, x, yin, zin, o)
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        if w is not None:
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)
    # b = 1 walks block rounds whatever the plan
    assert walks == {"block_rounds" if b == 1 else p.layout}


def test_block_lists_leave_out_padding_slots():
    """Padding slots point at column 0; only stored entries list blocks,
    so laplace3d(32)'s chunks list at most their 5 stencil blocks."""
    r, c, v, n = laplace3d(32)
    A = from_coo(r, c, v, (n, n), C=128, w_align=8, dtype=np.float32)
    counts, blocks = sw.block_lists(np.asarray(A.cols),
                                    np.asarray(A.row_len), A.uniform_width)
    # a chunk is 4 z-lines of 32: +-32 reaches blocks c+-1, +-1024 c+-8
    assert counts.max() == 5 and blocks.shape == (A.nchunks, 5)
    assert (np.diff(blocks, axis=1)[blocks[:, 1:] >= 0] > 0).all()
    last = np.asarray(blocks)[-1]
    assert 0 not in last                       # no padding slot listed


def test_block_lists_build_is_fast_at_full_size():
    """The cell's 16,777,216 slots: lists in well under a second."""
    import time
    nch, W = 16384, 8
    rng = np.random.default_rng(0)
    cols = ((np.arange(nch)[:, None, None] + rng.integers(-2, 3, (nch, W, 1)))
            % nch) * 128 + rng.integers(0, 128, (nch, W, 128))
    row_len = rng.integers(4, 8, nch * 128)
    t = time.perf_counter()
    counts, blocks = sw.block_lists(cols.ravel(), row_len, W)
    assert time.perf_counter() - t < 3.0
    assert counts.max() <= 5


def test_plan_of_the_cells_matrix_walks_slot_rows(on_tpu):
    """laplace3d(128) in SELL-128-1, 8 slots wide: slot rows 4 and 5
    span two blocks only at the lanes whose x-neighbour is missing, and
    slot row 7 is all padding, so a chunk takes 11 steps against the
    block rounds' 40; the plan is built within the block lists' limit."""
    import time
    r, c, v, n = laplace3d(128)
    A = from_coo(r, c, v, (n, n), C=128, w_align=8, dtype=np.float32)
    del r, c, v
    t = time.perf_counter()
    p = sw.plan(A)
    assert time.perf_counter() - t < 3.0
    assert p.layout == "slot_rows" and p.maxb == 5
    assert p.row_unroll == (1, 1, 2, 2, 2, 2, 1, 0)
    assert p.steps == 11.0 and p.rounds_steps == 40.0
    counts, _ = sw.row_block_lists(np.asarray(A.cols),
                                   np.asarray(A.row_len), A.uniform_width)
    assert counts.sum() == 146_688
    assert p.row_lists.nbytes <= 2 << 20


# --------------------------------------------------------- auto resolution
def test_auto_resolves_to_window_for_a_qualifying_matrix(on_tpu):
    A = _matrix("padding")
    op = GhostOperator(A)
    assert op.impl == "window" and op.window is not None
    x = jnp.ones((A.nrows_pad, 8), jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(op.mv)(x))
    np.testing.assert_allclose(np.asarray(op.mv(x)),
                               np.asarray(spmv_ref(A, x)[0]), rtol=1e-6)


def test_auto_is_ref_off_the_chip():
    op = GhostOperator(_matrix("padding"))
    assert op.impl == "ref" and op.window is None


@pytest.mark.parametrize("case", ["ragged", "C64", "complex", "bf16_store",
                                  "above_break_even", "explicit_ref"])
def test_auto_resolves_to_ref(on_tpu, monkeypatch, case):
    r, c, v, n = laplace3d(8)
    kw = dict(C=128, w_align=8, dtype=np.float32)
    impl = "auto"
    if case == "ragged":                  # chunk 1 is empty: width 1
        r, c, v, n = _empty_rows(np.random.default_rng(0))
        kw["w_align"] = 1
    elif case == "C64":
        kw["C"] = 64
    elif case == "complex":
        kw["dtype"] = np.complex64
    elif case == "bf16_store":
        kw["store_dtype"] = jnp.bfloat16
    elif case == "above_break_even":
        monkeypatch.setattr(sw, "BREAK_EVEN_STEPS", 1)
    elif case == "explicit_ref":
        impl = "ref"
    A = from_coo(r, c, v, (n, n), **kw)
    if case == "ragged":
        assert not A.uniform_width
    op = GhostOperator(A, impl=impl)
    assert op.impl == "ref" and op.window is None


def test_x_beyond_the_vmem_budget_runs_ref(on_tpu, monkeypatch):
    A = _matrix("padding")
    op = GhostOperator(A)
    assert op.impl == "window"
    n = A.nrows_pad
    assert sw.fits(n, 8, jnp.float32)
    monkeypatch.setattr(sw, "X_VMEM_BUDGET", n * 4 * 4)
    assert sw.fits(n, 4, jnp.float32) and not sw.fits(n, 8, jnp.float32)
    assert not sw.fits(n, 1, jnp.bfloat16)
    for b, kernel in ((4, True), (8, False)):
        x = jnp.ones((n, b), jnp.float32)
        assert ("pallas_call" in str(jax.make_jaxpr(op.mv)(x))) == kernel
    x = jnp.ones((n, 8), jnp.float32)
    assert (np.asarray(op.mv(x)) == np.asarray(spmv_ref(A, x)[0])).all()


def test_registry_records_the_resolved_path(on_tpu):
    r, c, v, n = laplace3d(8)
    reg = MatrixRegistry()
    reg.register("win", rows=r, cols=c, vals=v, shape=(n, n), C=128,
                 w_align=8, dtype=np.float32)
    reg.register("ragged", rows=r, cols=c, vals=v, shape=(n, n), C=128,
                 w_align=1, dtype=np.float32, sigma=512)
    reg.register("ref", rows=r, cols=c, vals=v, shape=(n, n), C=128,
                 w_align=8, dtype=np.float32, impl="ref")
    assert reg.stats["spmv_window"] == 1
    assert reg.stats["spmv_window_slot_rows"] == 1
    e = reg.entry("win")
    assert e.spmv_path == "window"
    mean, mx = e.blocks_per_chunk
    assert mx == 3 and 1 <= mean <= 3          # 512 rows: 4 chunks
    # slot rows (2,1,1,1,2,2,2,0) against one round of 3 blocks x 8
    assert e.steps_per_chunk == (11.0, 24.0)
    for name in ("ragged", "ref"):
        assert reg.entry(name).spmv_path == "ref"
        assert reg.entry(name).blocks_per_chunk is None
        assert reg.entry(name).steps_per_chunk is None


def test_dist_operator_auto_is_ref():
    class _Engine:
        pass
    assert DistOperator(_Engine(), impl="auto").impl == "ref"


def test_cg_on_the_window_kernel_matches_ref(on_tpu):
    """CG's iterates are the reference's: same iterations to tolerance."""
    r, c, v, n = laplace3d(8)
    A = from_coo(r, c, v, (n, n), C=128, w_align=8, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(3).standard_normal((n, 4)),
                    jnp.float32)
    res = {}
    for impl in ("auto", "ref"):
        op = GhostOperator(A, impl=impl)
        res[op.impl] = cg(op, op.to_op_space(b), tol=1e-6, maxiter=200)
    w, f = res["window"], res["ref"]
    assert int(np.max(w.iters)) == int(np.max(f.iters))
    np.testing.assert_allclose(np.asarray(w.x), np.asarray(f.x),
                               rtol=1e-4, atol=1e-5)
