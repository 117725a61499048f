"""The harness's cell loop at a tiny size on the CPU, called as a
function (the command itself refuses a platform other than the TPU):
each cell proves correct, its control in the next lower precision does
not, and a run with the timed path broken underneath does not either."""
import numpy as np
import pytest

from benchtiny import CELLS, TINY_NX, harness, run, tiny


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_proves_correct(name, trace):
    c = tiny(name)
    r = run(c, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 8 and r["failed"] == 0
    want = c.per_layer if trace else c.end_to_end
    names = [m.name for m in want]
    # the device metrics need the chip: the CPU has no memory peak and no
    # device plane in its trace, so their readers find nothing to read
    expect = {m.name for m in want if m.source == "device_trace"}
    assert "peak_hbm_gb" in expect or trace
    assert set(r["metrics"]) == set(names) - expect
    assert r["checks"]["worst_relres"]["value"] <= c.traffic["tol"] * 2
    for m in r["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_the_same_seed_gives_the_same_answers(name):
    c = tiny(name)
    a, b = run(c), run(c)
    assert a["attempted"] > 0
    assert (a["checks"]["worst_relres"]["value"]
            == b["checks"]["worst_relres"]["value"])


@pytest.mark.parametrize("name", CELLS)
def test_the_right_hand_sides_come_from_the_seed(name):
    cfg = tiny(name).config
    n = TINY_NX[cfg["matrix"]["generator"]] ** 3
    a = harness.rhs(cfg, 2 ** 31 + 5, 0, n)
    assert a.dtype == np.float32 and a.shape == (n,)
    assert np.array_equal(a, harness.rhs(cfg, 2 ** 31 + 5, 0, n))
    assert not np.array_equal(a, harness.rhs(cfg, 2 ** 31 + 6, 0, n))
    assert not np.array_equal(a, harness.rhs(cfg, 2 ** 31 + 5, 1, n))
    assert not np.array_equal(a, harness.rhs(cfg, 2 ** 40 + 5, 0, n))


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_is_not_correct(name):
    c = tiny(name)
    control = tiny(name, storage=dict(c.config["storage"], dtype="bfloat16"))
    r = run(control)
    assert not r["correct"]
    worst = r["checks"]["worst_relres"]
    assert worst["value"] > worst["limit"]


def _stuck(step):
    def stuck(op, state, k, M=None):
        return state
    return stuck


def _half(step):
    """Advance only the first half of the batch's columns."""
    from repro.solvers.stepper import merge_columns_masked

    def half(op, state, k, M=None):
        new = step(op, state, k, M=M)
        width = new.done.shape[0]
        frozen = np.arange(width) >= width // 2
        return merge_columns_masked(new, state, frozen)
    return half


@pytest.mark.parametrize("fault", [_stuck, _half])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    from repro.runtime import service

    init, step, fin = service.SOLVERS["cg"]
    monkeypatch.setitem(service.SOLVERS, "cg", (init, fault(step), fin))
    r = run(tiny(CELLS[0]))
    # frozen columns never converge: requests due by the window's close
    # never come back, or come back unconverged at the iteration cap
    assert not r["correct"]
    checks = r["checks"]
    assert checks["unresolved"]["value"] + checks["unconverged"]["value"] > 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.solvers.operator import GhostOperator

    unpermute = GhostOperator.from_op_space
    monkeypatch.setattr(GhostOperator, "from_op_space",
                        lambda self, v: unpermute(self, v) * 1.01)
    r = run(tiny(CELLS[1]))
    assert not r["correct"]
    assert r["checks"]["worst_relres"]["value"] > 1e-3
