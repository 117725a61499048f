"""The work functions against hand counts, and the peaks table."""
import pytest

from benchtiny import harness  # noqa: F401  (puts the benchmark on the path)
import work

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_spmv_work_by_hand():
    # 10 nonzeros on 4 rows, 2 columns: 10 * (4 + 4) bytes of matrix,
    # 4 * 2 * 4 read and 4 * 2 * 4 written; 2 operations per nonzero
    # and column
    assert work.spmv(10, 4, 2) == work.Work(bytes=80 + 32 + 32, flops=40)


def test_spmv_work_of_poisson7_128_at_width_8():
    w = work.spmv(14_581_760, 2_097_152, 8)
    assert w.bytes == 116_654_080 + 134_217_728
    assert w.flops == 233_308_160


def test_block_diag_work_by_hand():
    # 32 rows in blocks of 16: 2 blocks of 16 * 16 values = 32 * 16
    # values; x and y at width 8; 2 * 16 operations per row and column
    assert work.block_diag(32, 16, 8) == work.Work(
        bytes=32 * 16 * 4 + 2 * 32 * 8 * 4, flops=2 * 32 * 16 * 8)


def test_roofline_takes_the_larger_bound():
    mem = work.Work(bytes=819e9, flops=1.0)
    assert work.roofline_pct(mem, 2.0, V5E) == pytest.approx(50.0)
    ops = work.Work(bytes=1.0, flops=197e12)
    assert work.roofline_pct(ops, 4.0, V5E) == pytest.approx(25.0)


def test_peaks_know_the_v5e_and_nothing_else():
    assert work.peaks("TPU v5 lite") == {"hbm_bytes_per_s": 819e9,
                                         "bf16_flops_per_s": 197e12,
                                         "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="cpu"):
        work.peaks("cpu")
