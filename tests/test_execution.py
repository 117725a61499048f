"""Execution-policy subsystem: backend auto-detection, env/context
overrides, the hardened specialization cascade, tile knobs + autotune,
and the correctness regressions that hid behind the always-interpret
defaults (tail-drop raise, f64 dot accumulation)."""
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import SpmvOpts, execution, from_dense
from repro.core.spmv import compensated_sum0, dot_acc_dtype, spmv_ref
from repro.kernels import ops
from repro.kernels.sellcs_spmv import sellcs_spmv_pallas


@pytest.fixture(autouse=True)
def _fresh_policy():
    """Each test sees (and leaves behind) pristine policy caches."""
    execution.reset()
    yield
    execution.reset()


def random_sparse(rng, n, m, density=0.15, dtype=np.float32):
    return ((rng.random((n, m)) < density)
            * rng.standard_normal((n, m))).astype(dtype)


# ------------------------------------------------------------------ policy
class TestPolicyResolution:
    def test_auto_detection(self):
        pol = execution.current_policy()
        assert pol.backend == jax.default_backend()
        assert pol.source == "auto"
        # CI/test machines run CPU: auto policy must pick interpret there,
        # and compiled iff the backend is in the trusted set
        assert pol.interpret == (pol.backend not in execution.COMPILED_BACKENDS)

    def test_explicit_argument_wins(self):
        assert execution.resolve_interpret(True) is True
        assert execution.resolve_interpret(False) is False
        assert execution.resolve_interpret(None) == \
            execution.current_policy().interpret

    def test_force_context_nests_and_restores(self):
        base = execution.current_policy()
        with execution.force(interpret=False) as outer:
            assert outer.source == "forced"
            assert execution.resolve_interpret(None) is False
            with execution.force(interpret=True):
                assert execution.resolve_interpret(None) is True
            assert execution.resolve_interpret(None) is False
        assert execution.current_policy() == base

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(execution.ENV_INTERPRET, "0")
        execution.reset()
        pol = execution.current_policy()
        assert pol.interpret is False and pol.source == "env"
        monkeypatch.setenv(execution.ENV_INTERPRET, "true")
        execution.reset()
        assert execution.current_policy().interpret is True

    def test_env_tile_knobs(self, monkeypatch):
        monkeypatch.setenv(execution.ENV_ROW_TILE, "128")
        monkeypatch.setenv(execution.ENV_S_BLK, "16")
        monkeypatch.setenv(execution.ENV_W_TILE, "2")
        execution.reset()
        assert execution.resolve_row_tile() == 128
        assert execution.resolve_s_blk() == 16
        assert execution.resolve_w_tile(None, w_align=4) == 2
        # explicit call-site argument still wins
        assert execution.resolve_row_tile(256) == 256

    def test_w_tile_knob_degrades_when_incompatible(self):
        with execution.force(w_tile=4):
            assert execution.resolve_w_tile(None, w_align=8) == 4
            assert execution.resolve_w_tile(None, w_align=3) == 3  # hint dropped
        assert execution.resolve_w_tile(None, w_align=8) == 8

    def test_describe_names_the_mode(self):
        assert "mode=interpret" in execution.describe(
            execution.ExecutionPolicy(interpret=True, backend="cpu"))
        assert "mode=compiled" in execution.describe(
            execution.ExecutionPolicy(interpret=False, backend="tpu"))


# ----------------------------------------------------------------- cascade
class TestCascade:
    def test_compiled_failure_falls_back_to_ref(self, rng):
        """Forcing compiled mode on a Pallas-less backend must degrade to
        the jnp reference (with a warning), not crash."""
        a = random_sparse(rng, 64, 64)
        m = from_dense(a, C=8, sigma=16, w_align=4)
        x = m.permute(rng.standard_normal((64, 2)).astype(np.float32))
        y_ref, _, _ = spmv_ref(m, x)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            with execution.force(interpret=False):
                y, _, _ = ops.sellcs_spmv(m, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        if jax.default_backend() in execution.COMPILED_BACKENDS:
            assert not rec                       # genuinely compiled: no warning
        else:
            assert any(issubclass(w.category, RuntimeWarning) for w in rec)

    @pytest.mark.skipif(jax.default_backend() in execution.COMPILED_BACKENDS,
                        reason="backend compiles Pallas natively")
    def test_warns_once_per_kernel(self, rng):
        a = random_sparse(rng, 40, 40)
        m = from_dense(a, C=8, sigma=8)
        x = m.permute(rng.standard_normal(40).astype(np.float32))
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            with execution.force(interpret=False):
                ops.sellcs_spmv(m, x)
                ops.sellcs_spmv(m, x)
        assert sum(issubclass(w.category, RuntimeWarning) for w in rec) == 1

    @pytest.mark.skipif(jax.default_backend() in execution.COMPILED_BACKENDS,
                        reason="backend compiles Pallas natively")
    def test_fallback_disabled_raises(self, rng):
        V = jnp.asarray(rng.standard_normal((64, 3)), jnp.float32)
        X = jnp.asarray(rng.standard_normal((3, 2)), jnp.float32)
        with execution.force(interpret=False, fallback=False):
            with pytest.raises(Exception):
                jax.block_until_ready(ops.tsmm(V, X))

    def test_tpu_backend_never_falls_back(self, monkeypatch):
        """On a TPU backend a compiled failure raises: the fallback
        default is off (REPRO_FALLBACK cannot turn it on there), and a
        failed compiled-Pallas probe raises instead of degrading."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv(execution.ENV_FALLBACK, "1")
        execution.reset()
        pol = execution.current_policy()
        assert pol.backend == "tpu" and not pol.interpret
        assert pol.fallback is False
        assert "mode=compiled;backend=tpu" in execution.describe()

        def boom():
            raise NotImplementedError("no Mosaic lowering")
        with pytest.raises(NotImplementedError, match="no Mosaic lowering"):
            execution.cascade("k", boom, lambda: 1)
        assert execution.degrade_to_reference("k") is False
        if jax.devices()[0].platform not in execution.COMPILED_BACKENDS:
            # the probe really compiles on this (Pallas-less) host
            with pytest.raises(RuntimeError, match="probe failed"):
                execution.compiled_available()

    def test_compiled_sellcs_spmv_raises_with_reason(self, rng):
        from repro.kernels.sellcs_spmv import NO_LOWERING
        m = from_dense(random_sparse(rng, 64, 64), C=8, sigma=1, w_align=4)
        x = m.permute(rng.standard_normal((64, 1)).astype(np.float32))
        with pytest.raises(NotImplementedError) as info:
            sellcs_spmv_pallas(m.vals, m.cols, m.chunk_off, m.chunk_len, x,
                               C=m.C, w_tile=4, interpret=False)
        assert str(info.value) == NO_LOWERING

    def test_interpret_failures_propagate(self):
        """Interpret-mode bugs are not swallowed by the cascade."""
        def boom():
            raise RuntimeError("logic bug")
        with pytest.raises(RuntimeError):
            execution.cascade("k", boom, lambda: 1, interpret=True)

    def test_every_wrapper_cascades(self, rng):
        """All five ops wrappers survive a forced-compiled run on any
        backend and match their references."""
        n = 96
        V = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
        W = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
        X = jnp.asarray(rng.standard_normal((3, 3)), jnp.float32)
        dt = jnp.full((1, 8, 4), 0.1, jnp.float32)
        A = -jnp.ones((4, 2), jnp.float32)
        B = jnp.ones((1, 8, 2), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            with execution.force(interpret=False):
                np.testing.assert_allclose(
                    np.asarray(ops.tsmttsm(V, W)),
                    np.asarray(V).T @ np.asarray(W), atol=1e-4, rtol=1e-4)
                np.testing.assert_allclose(
                    np.asarray(ops.tsmm(V, X)),
                    np.asarray(V) @ np.asarray(X), atol=1e-4, rtol=1e-4)
                # kahan fallback must still honor alpha/beta/X
                X0 = jnp.asarray(np.eye(3, dtype=np.float32))
                np.testing.assert_allclose(
                    np.asarray(ops.tsmttsm(V, W, X0, alpha=2.0, beta=1.0,
                                           kahan=True)),
                    2.0 * (np.asarray(V).T @ np.asarray(W)) + np.eye(3),
                    atol=1e-3, rtol=1e-4)
                out, dots = ops.fused_axpby_dots(V[:, 0], W[:, 0], 2.0, 1.0,
                                                 dot_xy=True)
                np.testing.assert_allclose(
                    np.asarray(out),
                    2 * np.asarray(V[:, 0]) + np.asarray(W[:, 0]),
                    atol=1e-5, rtol=1e-5)
                y = ops.mamba_scan(dt, dt, B, B, A)
                assert y.shape == (1, 8, 4)


# ------------------------------------------------------------ compile cache
class TestCompileCache:
    @pytest.fixture
    def cache_dir_config(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_is_honoured(self, monkeypatch, tmp_path,
                                 cache_dir_config):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert execution.use_compile_cache("/some/checkout") == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads env

    def test_fixed_dir_in_checkout(self, monkeypatch, tmp_path,
                                   cache_dir_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path / ".jax_cache")
        assert execution.use_compile_cache(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want


# ---------------------------------------------------------------- autotune
class TestAutotune:
    def test_caches_winner(self):
        calls = []

        def run(c):
            calls.append(c)
            return jnp.zeros(4)

        first = execution.autotune("k", ("shape",), (1, 2), run, iters=1)
        assert first in (1, 2) and set(calls) == {1, 2}
        # second lookup must not re-measure
        def explode(c):
            raise AssertionError("re-measured despite cache")
        assert execution.autotune("k", ("shape",), (1, 2), explode) == first
        execution.reset()
        with pytest.raises(AssertionError):
            execution.autotune("k", ("shape",), (1,), explode)


# -------------------------------------------------- tail-drop regression
class TestTailDropValidation:
    def test_incompatible_w_tile_raises(self, rng):
        """chunk_len % w_tile != 0 used to silently drop tail nonzeros;
        now the kernel refuses host-side."""
        a = random_sparse(rng, 64, 64, density=0.3)
        m = from_dense(a, C=8, sigma=1, w_align=1)    # ragged widths
        assert (np.asarray(m.chunk_len) % 4 != 0).any()
        x = m.permute(rng.standard_normal((64, 1)).astype(np.float32))
        with pytest.raises(ValueError, match="tail nonzeros"):
            sellcs_spmv_pallas(m.vals, m.cols, m.chunk_off, m.chunk_len,
                               x, C=m.C, w_tile=4)

    def test_aligned_build_passes(self, rng):
        a = random_sparse(rng, 64, 64, density=0.3)
        m = from_dense(a, C=8, sigma=1, w_align=4)
        x = m.permute(rng.standard_normal((64, 1)).astype(np.float32))
        y, _, _ = sellcs_spmv_pallas(m.vals, m.cols, m.chunk_off,
                                     m.chunk_len, x, C=m.C, w_tile=4,
                                     interpret=True)
        y_ref, _, _ = spmv_ref(m, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)


# ------------------------------------------------------- f64 dot accumulation
class TestDotAccumulation:
    def test_dots_exact_in_f64(self):
        """Fused dots accumulate in f64: one huge chunk partial must not
        swallow the small chunks' mass (exact powers of two throughout,
        so both paths reproduce the true sum bit-for-bit)."""
        n, C = 256, 32
        with jax.enable_x64(True):
            m = from_dense(np.eye(n, dtype=np.float32), C=C, sigma=1)
            x = np.full(n, 8.0, np.float32)
            x[:C] = 0.0
            x[0] = 2.0 ** 30
            x2 = jnp.asarray(x[:, None])
            expected = 2.0 ** 60 + (n - C) * 64.0        # exact in f64
            opts = SpmvOpts(dot_xx=True, dot_yy=True)

            _, _, dr = spmv_ref(m, x2, opts=opts)
            assert dr.dtype == jnp.float64
            assert float(dr[2, 0]) == expected
            assert float(dr[0, 0]) == expected           # y == x (identity A)

            _, _, dk = ops.sellcs_spmv(m, x2, opts=opts)
            assert dk.dtype == jnp.float64
            assert float(dk[2, 0]) == expected
            assert float(dk[0, 0]) == expected

    def test_solvers_stable_with_wide_dots(self):
        """f64 dot accumulation under x64 must not poison the solvers'
        f32 while_loop/scan carries (cg casts the recurrence scalar back,
        kpm casts at the moment boundary)."""
        from repro.solvers import cg, make_operator
        from repro.solvers.kpm import kpm_dos_moments
        rng = np.random.default_rng(7)
        n = 64
        with jax.enable_x64(True):
            a = random_sparse(rng, n, n, density=0.2)
            spd = (a @ a.T + n * np.eye(n)).astype(np.float32)
            m = from_dense(spd, C=8, sigma=16)
            op = make_operator(m)
            b = m.permute(rng.standard_normal(n).astype(np.float32))
            res = cg(op, b, tol=1e-5, maxiter=200)
            assert float(res.resnorm) < 1e-3
            mus = kpm_dos_moments(op, 16, n_probes=2, spectrum=(0.0, 2 * n))
            assert np.isfinite(np.asarray(mus)).all()

    def test_acc_dtype_without_x64(self):
        # x64 off (the tier-1 default): f32 stays f32, bf16 widens to f32,
        # integer inputs accumulate in float (norms are analytic, and
        # jnp.finfo on an int accumulator would crash)
        assert dot_acc_dtype(jnp.float32) == jnp.dtype(jnp.float32)
        assert dot_acc_dtype(jnp.bfloat16) == jnp.dtype(jnp.float32)
        assert dot_acc_dtype(jnp.int32) == jnp.dtype(jnp.float32)

    def test_integer_inputs_dont_crash_dots(self):
        from repro.core import from_coo
        m = from_coo([0, 1], [0, 1], np.array([2, 3], np.int32), (2, 2), C=2)
        x = jnp.asarray(np.array([[1], [1]], np.int32))
        _, _, dots = spmv_ref(m, x, opts=SpmvOpts(dot_xx=True, dot_yy=True))
        assert jnp.issubdtype(dots.dtype, jnp.floating)
        assert float(dots[2, 0]) == 2.0 and float(dots[0, 0]) == 13.0

    def test_pallas_chunk_reduce_compensated_without_x64(self):
        """x64 off: the cross-chunk dot reduction must Kahan-compensate —
        a spike chunk partial (2^30) must not swallow the other chunks'
        sub-ulp mass (63 chunks x 32, all below the f32 spacing of 128)."""
        n, C = 2048, 32
        diag = np.ones(n, np.float32)
        m = from_dense(np.diag(diag), C=C, sigma=1)
        x = np.ones(n, np.float32)
        x[:C] = 0.0
        x[0] = np.float32(2.0 ** 15)                  # square: 2^30
        x2 = jnp.asarray(x[:, None])
        _, _, dk = ops.sellcs_spmv(m, x2, opts=SpmvOpts(dot_xx=True))
        want = 2.0 ** 30 + (n - C)                    # exact in f64
        # Kahan bound: only the spike's 8-partial block can round (±128);
        # the old plain f32 running sum could lose all 2016
        assert abs(float(dk[2, 0]) - want) <= 128.0

    def test_compensated_sum_matches_f64(self, rng):
        p = jnp.asarray(rng.standard_normal((4097, 3)), jnp.float32)
        got = np.asarray(compensated_sum0(p))
        want = np.asarray(p, np.float64).sum(axis=0)
        np.testing.assert_allclose(got, want, rtol=2e-6)

    def test_compensated_sum_beats_naive_worst_case(self):
        # one spike block, then 64 blocks whose 64.0 partials each sit
        # *below* the f32 spacing at 2^30 (128): a plain running sum
        # rounds every one of them away, the Kahan carry recovers them
        # exactly (all quantities are exact f32, so equality is exact)
        p = np.zeros(256 + 64 * 256, np.float32)
        p[0] = 2.0 ** 30
        p[256:] = 0.25
        got = float(compensated_sum0(jnp.asarray(p[:, None]))[0])
        assert got == 2.0 ** 30 + 4096.0


# ----------------------------------------------- engine inherits the policy
class TestEnginePolicy:
    def test_make_matvec_cache_keys_on_resolved_mode(self, rng):
        from jax.sharding import Mesh
        from repro.runtime import DevicePool, HeterogeneousEngine

        r, c = np.arange(64), np.arange(64)
        v = np.ones(64, np.float32)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        eng = HeterogeneousEngine(r, c, v, 64, mesh=mesh,
                                  pool=DevicePool.from_bandwidths([1.0]),
                                  C=8, dtype=np.float32)
        fn_default = eng.make_matvec(nvecs=1)
        with execution.force(interpret=False):
            fn_compiled = eng.make_matvec(nvecs=1)
        with execution.force(interpret=True):
            fn_interp = eng.make_matvec(nvecs=1)
        base_interpret = execution.current_policy().interpret
        assert (fn_default is fn_interp) == (base_interpret is True)
        assert fn_compiled is not fn_interp
        # same policy twice -> cache hit
        assert eng.make_matvec(nvecs=1) is fn_default

    def test_forced_compiled_engine_degrades_inside_shard_map(self, rng):
        """The pipeline calls the Pallas kernel inside shard_map, where a
        lowering failure cannot be caught — the trace-time degrade leg of
        the cascade must kick in instead of crashing."""
        from jax.sharding import Mesh
        from repro.runtime import DevicePool, HeterogeneousEngine

        n = 64
        a = random_sparse(rng, n, n, density=0.3)
        r, c = np.nonzero(a)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        eng = HeterogeneousEngine(r, c, a[r, c], n, mesh=mesh,
                                  pool=DevicePool.from_bandwidths([1.0]),
                                  C=8, dtype=np.float32)
        x = rng.standard_normal((n, 1)).astype(np.float32)
        y_ref, _ = eng.spmv(x, impl="ref")
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            with execution.force(interpret=False):
                y, _ = eng.spmv(x, impl="pallas")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        if jax.default_backend() not in execution.COMPILED_BACKENDS:
            assert any(issubclass(w.category, RuntimeWarning) for w in rec)
