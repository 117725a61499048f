"""ghostsan: seeded-bug fixtures per analyzer, engine machinery, CLI,
and the self-check that the sanitizer passes over the repo's own tree.

Mirrors tests/test_ghostlint.py: each GS rule gets *positive* fixtures —
minimal seeded bugs the analyzer must flag (an overlapping output index
map, an uncovered tail chunk, an out-of-bounds tile, an accumulator
downcast, a storage round-trip, a cache-key churn loop) — plus clean
negatives proving the legal patterns (reduction outputs, boundary casts,
cached jits) never fire, and a src/-clean-beyond-baseline self-check.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from tools.ghostsan import load_baseline
from tools.ghostsan.cli import main as cli_main
from tools.ghostsan.engine import (DEFAULT_BASELINE, Finding,
                                   apply_suppressions, suppressed_lines)
from tools.ghostsan.gs101_grid import (analyze_capture, audit_callable,
                                       capture_pallas_calls, run_grid_audit)
from tools.ghostsan.gs102_dtype import audit_function, run_dtype_audit
from tools.ghostsan.gs103_recompile import audit_workload, run_recompile_audit


def rules_of(findings):
    return {f.rule for f in findings}


def _fake_pallas(out_specs, out_shape, grid):
    """A minimal wrapper issuing one pallas_call with the given specs."""
    def thunk():
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=grid,
            in_specs=[pl.BlockSpec((2, 8), lambda i: (i, 0))],
            out_specs=out_specs,
            out_shape=out_shape,
        )(jnp.zeros((8, 8), jnp.float32))
    return thunk


# ---------------------------------------------------------------- GS101
class TestGS101Grid:
    def test_overlapping_output_map_is_race(self):
        # i -> (i//2, 0): grid points 0 and 1 both write tile (0, 0)
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((2, 8), lambda i: (i // 2, 0)),
            jax.ShapeDtypeStruct((8, 8), jnp.float32), (4,)))
        assert "GS101" in rules_of(fs)
        assert any("write race" in f.message for f in fs)

    def test_uncovered_tail_chunk(self):
        # grid 3 over a 4-block output: tile (3, 0) never written
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((2, 8), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((8, 8), jnp.float32), (3,)))
        assert any("uncovered" in f.message for f in fs)
        assert rules_of(fs) == {"GS101"}

    def test_out_of_bounds_tile(self):
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((2, 8), lambda i: (i + 1, 0)),
            jax.ShapeDtypeStruct((8, 8), jnp.float32), (4,)))
        assert any("out of bounds" in f.message for f in fs)

    def test_identity_map_clean(self):
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((2, 8), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((8, 8), jnp.float32), (4,)))
        assert fs == []

    def test_reduction_output_is_not_a_race(self):
        # constant map over the whole grid = accumulator tile (the
        # tsmttsm pattern); the map depends on no axis, so revisiting
        # the tile is deliberate
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((4, 4), lambda i: (0, 0)),
            jax.ShapeDtypeStruct((4, 4), jnp.float32), (4,)))
        assert fs == []

    def test_multi_output_only_bad_one_flagged(self):
        def thunk():
            pl.pallas_call(
                lambda x_ref, a_ref, b_ref: None,
                grid=(4,),
                in_specs=[pl.BlockSpec((2, 8), lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((2, 8), lambda i: (i, 0)),
                           pl.BlockSpec((2, 8), lambda i: (i // 2, 0))],
                out_shape=[jax.ShapeDtypeStruct((8, 8), jnp.float32),
                           jax.ShapeDtypeStruct((8, 8), jnp.float32)],
            )(jnp.zeros((8, 8), jnp.float32))
        fs = audit_callable(thunk)
        assert all("out[1]" in f.message for f in fs) and fs

    def test_capture_shim_records_and_restores(self):
        caps = []
        real = pl.pallas_call
        with capture_pallas_calls(caps):
            _fake_pallas(pl.BlockSpec((2, 8), lambda i: (i, 0)),
                         jax.ShapeDtypeStruct((8, 8), jnp.float32),
                         (4,))()
        assert pl.pallas_call is real
        assert len(caps) == 1
        assert caps[0].grid == (4,) and len(caps[0].out_specs) == 1
        assert analyze_capture(caps[0]) == []

    def test_findings_anchor_in_this_repo(self):
        fs = audit_callable(_fake_pallas(
            pl.BlockSpec((2, 8), lambda i: (i // 2, 0)),
            jax.ShapeDtypeStruct((8, 8), jnp.float32), (4,)))
        assert fs and all(f.path.endswith(".py") for f in fs)
        assert all(f.line > 0 for f in fs)


# ---------------------------------------------------------------- GS102
class TestGS102Dtype:
    def test_accumulator_downcast_narrow_dot(self):
        def bf16_dot(a, b):
            return jnp.dot(a, b)        # bf16 x bf16 -> bf16 reduction
        a = jnp.ones((8, 8), jnp.bfloat16)
        fs = audit_function(bf16_dot, a, a, compute_bits=32)
        assert rules_of(fs) == {"GS102"}
        assert any("narrow accumulation" in f.message for f in fs)

    def test_widened_dot_clean(self):
        def widened(a, b):
            return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
        a = jnp.ones((8, 8), jnp.bfloat16)
        assert audit_function(widened, a, a, compute_bits=32) == []

    def test_downcast_below_compute(self):
        def drop(x):
            return (x * 2.0).astype(jnp.bfloat16)
        fs = audit_function(drop, jnp.ones((4,), jnp.float32),
                            compute_bits=32)
        assert any("downcast below compute" in f.message for f in fs)

    def test_storage_roundtrip(self):
        def rt(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0
        fs = audit_function(rt, jnp.ones((4,), jnp.float32),
                            compute_bits=32)
        assert any("storage round-trip" in f.message for f in fs)

    def test_boundary_cast_to_compute_dtype_clean(self):
        # an f64 Kahan/dot result folding back into f32 solver state is
        # the contract's sanctioned boundary, not a violation
        def legal(x):
            return (x * 2.0).astype(jnp.float32)
        with jax.enable_x64(True):
            fs = audit_function(legal, jnp.ones((4,), jnp.float64),
                                compute_bits=32)
        assert fs == []

    def test_x64_roundtrip_through_f32_flagged(self):
        def rt64(x):
            return x.astype(jnp.float32).astype(jnp.float64) * 2.0
        with jax.enable_x64(True):
            fs = audit_function(rt64, jnp.ones((4,), jnp.float64),
                                compute_bits=64)
        assert any("storage round-trip" in f.message for f in fs)
        assert any("downcast below compute" in f.message for f in fs)

    def test_audit_recurses_into_while_loop(self):
        def looped(x):
            def body(c):
                return (c.astype(jnp.bfloat16).astype(jnp.float32)
                        * 1.5)
            return jax.lax.while_loop(lambda c: c[0] < 5.0, body, x)
        fs = audit_function(looped, jnp.ones((4,), jnp.float32),
                            compute_bits=32)
        assert any("storage round-trip" in f.message for f in fs)


# ---------------------------------------------------------------- GS103
class TestGS103Recompile:
    def test_cache_key_churn_loop_flagged(self):
        def churn():
            # a fresh function object per round = a fresh jit cache key:
            # the armed identical replay must re-trace
            fn = jax.jit(lambda x: x * 2 + 1)
            fn(jnp.ones((4,), jnp.float32)).block_until_ready()
        fs = audit_workload(churn, name="churn-fixture")
        assert rules_of(fs) == {"GS103"}
        assert any("churn-fixture" in f.message for f in fs)

    def test_cached_jit_clean(self):
        cached = jax.jit(lambda x: x * 3 - 1)

        def steady():
            cached(jnp.ones((4,), jnp.float32)).block_until_ready()
        assert audit_workload(steady, name="steady") == []

    def test_varying_shape_churn_flagged(self):
        cached = jax.jit(lambda x: x.sum())
        state = {"n": 3}

        def grow():
            # shape changes every round — a retrace per call even with
            # one function object (the varying-gather refill bug class)
            state["n"] += 1
            cached(jnp.ones((state["n"],), jnp.float32)).block_until_ready()
        fs = audit_workload(grow, name="grow")
        assert rules_of(fs) == {"GS103"}


# ------------------------------------------------------------ machinery
class TestEngine:
    def test_ghostsan_prefix_own_suppressions(self):
        per_line, file_level = suppressed_lines(
            "x = 1  # ghostsan: disable=GS101\n"
            "# ghostlint: disable=GS102\n"
            "y = 2\n")
        assert per_line == {1: {"GS101"}}       # ghostlint prefix inert
        assert file_level is None

    def test_apply_suppressions_filters_at_anchor(self, tmp_path,
                                                  monkeypatch):
        mod = tmp_path / "anchored.py"
        mod.write_text("# ghostsan: disable=GS101\n"
                       "def wrapper():\n"
                       "    pass\n")
        import tools.ghostsan.engine as eng
        monkeypatch.setattr(eng, "REPO", str(tmp_path))
        kept = Finding("GS102", "anchored.py", 2, "m", "def wrapper():")
        dropped = Finding("GS101", "anchored.py", 2, "m",
                          "def wrapper():")
        out = apply_suppressions([kept, dropped])
        assert out == [kept]

    def test_shared_fingerprint_semantics(self):
        a = Finding("GS101", "x.py", 3, "msg", "def f():")
        b = Finding("GS101", "x.py", 33, "other msg", "def f():")
        assert a.fingerprint == b.fingerprint

    def test_default_baseline_is_committed_empty(self):
        assert load_baseline(DEFAULT_BASELINE) == set()
        with open(DEFAULT_BASELINE, encoding="utf-8") as f:
            assert json.load(f)["findings"] == []


# ------------------------------------------------------------------- CLI
class TestCLI:
    def test_list_rules_exit_zero(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("GS101", "GS102", "GS103"):
            assert rid in out

    def test_unknown_rule_usage_error(self, capsys):
        assert cli_main(["--select", "GS999"]) == 2

    def test_select_gs101_json_clean_tree(self, capsys):
        rc = cli_main(["--select", "GS101", "--format=json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["findings"] == [] and data["analyzers"] == ["GS101"]


# ------------------------------------------------------------- self-check
class TestSelfCheck:
    def test_grid_audit_clean_beyond_baseline(self):
        """The sanitizer's reason to exist: every in-tree kernel's grid
        is race-free and covering, with the committed baseline empty."""
        fresh = [f for f in apply_suppressions(run_grid_audit())
                 if f.fingerprint not in load_baseline()]
        assert fresh == [], "\n".join(f.format() for f in fresh)

    def test_dtype_audit_clean_beyond_baseline(self):
        fresh = [f for f in apply_suppressions(run_dtype_audit())
                 if f.fingerprint not in load_baseline()]
        assert fresh == [], "\n".join(f.format() for f in fresh)

    def test_recompile_audit_clean_beyond_baseline(self):
        fresh = [f for f in apply_suppressions(run_recompile_audit())
                 if f.fingerprint not in load_baseline()]
        assert fresh == [], "\n".join(f.format() for f in fresh)


# ----------------------------------------------- parity auto-discovery
class TestParityDiscovery:
    def test_discovers_every_kernel_file(self):
        from tools.ghostlint.parity import SWEEPS, discover_kernel_bases
        bases = discover_kernel_bases()
        # the six shipped kernels, by construction of the scan
        for base in ("sellcs_spmv", "tsmm", "tsmttsm", "fused_axpby_dots",
                     "block_diag_matmul", "mamba_scan"):
            assert base in bases, base
        assert set(bases) <= set(SWEEPS)

    def test_unregistered_kernel_fails_coverage(self, tmp_path,
                                                monkeypatch):
        import tools.ghostlint.parity as parity
        (tmp_path / "newkern.py").write_text(
            "def shiny_new_pallas(x):\n    return x\n")
        monkeypatch.setattr(parity, "KERNELS_DIR", str(tmp_path))
        problems = parity.check_sweep_coverage()
        assert any("shiny_new" in p and "no sweep driver" in p
                   for p in problems)
        # and the stale direction: drivers for kernels that vanished
        assert any("stale entry" in p for p in problems)

    def test_sweep_cases_feed_gs101(self):
        from tools.ghostlint.parity import iter_sweep_cases
        cases = list(iter_sweep_cases())
        assert len(cases) >= 21          # 16 sellcs configs + 5 dense
        names = {c.name for c in cases}
        assert "sellcs_spmv" in names and "tsmttsm" in names
