"""Device time by the program's scopes (``scoped``), on hand-written
events, on a slice of a chip trace and on a tiny run on the CPU, and the
three metrics that read it."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from benchtiny import harness, tiny
import scoped
from scoped import Op

NAMES = {
    "jit_chunk": {
        "while.1": "jit(chunk)/ghost.chunk/while",
        "fusion.2": "jit(chunk)/ghost.chunk/while/body/ghost.spmv/gather",
        "reduce.3": "jit(chunk)/ghost.chunk/while/body/ghost.spmv/"
                    "ghost.dots/reduce_sum",
        "mul.6": "jit(chunk)/ghost.chunk/while/body/ghost.dots/mul",
    },
    "jit__init": {"add.5": "jit(_init)/ghost.spmv/add"},
}


def op(name, module, start, end):
    return Op(name, module, start, end - start)


EVENTS = [
    op("while.1", "jit_chunk", 0, 1000),
    op("fusion.2", "jit_chunk", 100, 400),
    op("reduce.3", "jit_chunk", 450, 500),
    op("copy.4", "jit_chunk", 600, 700),        # no metadata
    op("mul.6", "jit_chunk", 800, 830),
    op("add.5", "jit__init", 1200, 1300),
    op("copy.7", "jit_other", 1900, 1950),     # a program not mapped
]
METRICS = {"chunk_iter_ms": 1000, "chunk_spmv_ms": 300, "chunk_dots_ms": 80}


def test_self_time_leaves_out_the_operations_a_loop_covers():
    s = scoped.reduce(EVENTS, NAMES)
    # the loop's 1000 ns less its body's 300 + 50 + 100 + 30; the copy
    # without metadata takes the loop's scope
    assert s["ghost.chunk"] == pytest.approx((520 + 100) / 1e9)
    assert s["ghost.chunk/ghost.spmv"] == pytest.approx(300 / 1e9)
    assert s["ghost.chunk/ghost.spmv/ghost.dots"] == pytest.approx(50 / 1e9)
    assert s["ghost.chunk/ghost.dots"] == pytest.approx(30 / 1e9)
    assert s["ghost.spmv"] == pytest.approx(100 / 1e9)   # outside the chunk
    assert s[""] == pytest.approx(50 / 1e9)
    assert sum(s.values()) == pytest.approx(1150 / 1e9)  # the busy time


def test_chunk_seconds_select_by_innermost_scope_under_the_chunk():
    s = scoped.reduce(EVENTS, NAMES)
    assert scoped.chunk_seconds(s) == pytest.approx(1000 / 1e9)
    assert scoped.chunk_seconds(s, "ghost.spmv") == pytest.approx(300 / 1e9)
    assert scoped.chunk_seconds(s, "ghost.dots") == pytest.approx(80 / 1e9)


def test_overlapping_operations_count_once():
    ops = [op("while.1", "jit_chunk", 0, 100),
           op("fusion.2", "jit_chunk", 50, 150)]
    s = scoped.self_times(ops, lambda o: "a")
    assert s["a"] == pytest.approx(150 / 1e9)


def test_each_distinct_operation_is_looked_up_once():
    looked = []

    class Names(dict):
        def get(self, key, default=None):
            looked.append(key)
            return super().get(key, default)

    names = {m: Names(v) for m, v in NAMES.items()}
    scoped.reduce(EVENTS * 50, names)
    assert sorted(looked) == sorted(o.name for o in EVENTS
                                    if o.module in names)


def test_op_names_are_read_from_compiled_hlo_text():
    text = "\n".join([
        "HloModule jit_chunk, is_scheduled=true",
        '  %fusion.12 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fc.12, metadata={op_name="jit(chunk)/ghost.chunk/while/'
        'body/ghost.spmv/gather" stack_frame_id=6}',
        '  ROOT %while.37 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(chunk)/ghost.chunk/while" '
        'stack_frame_id=6}, backend_config={"a":[]}',
        "  %copy.1 = f32[8]{0} copy(%p)",
    ])
    assert scoped.op_names(text) == {
        "fusion.12": "jit(chunk)/ghost.chunk/while/body/ghost.spmv/gather",
        "while.37": "jit(chunk)/ghost.chunk/while"}
    assert scoped.ghost_path(
        "jit(chunk)/ghost.chunk/while/body/ghost.spmv/ghost.dots/x") \
        == "ghost.chunk/ghost.spmv/ghost.dots"
    assert scoped.ghost_path("jit(f)/mul") == ""
    assert scoped.module_name("jit_chunk(42)") == "jit_chunk"


def _measured(scopes, iters):
    """A run whose chunk was already traced."""
    return SimpleNamespace(_scoped={"scopes": scopes, "iters": iters})


def _metric(name, run):
    return harness.load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metrics_read_the_traced_chunk(name):
    run = _measured(scoped.reduce(EVENTS, NAMES), 16)
    assert _metric(name, run) == pytest.approx(METRICS[name] * 1e-6 / 16)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_without_scopes_the_metrics_read_nothing(name, monkeypatch):
    # the program before it named itself: no stretch is run at all
    from repro.solvers import stepper
    monkeypatch.delattr(stepper, "chunk_programs")
    assert _metric(name, SimpleNamespace(requests=[object()])) is None
    # a chunk whose operations carry no scope, or that ran no iteration
    assert _metric(name, _measured(scoped.reduce(EVENTS, {}), 16)) is None
    assert _metric(name, _measured(scoped.reduce(EVENTS, NAMES), 0)) is None


def test_a_recorded_trace_slice():
    # the end of a chunk of poisson7_128.cg_w8 on one v5e and the eager
    # programs after it: the compensated dot's loop and its body, under
    # the SpMV's fused dots, then the residual norm
    data = json.loads((Path(__file__).parent / "data"
                       / "poisson7_scoped_slice.json").read_text())
    ops = [Op(*o) for o in data["ops"]]
    s = scoped.reduce(ops, data["names"])
    assert s["ghost.chunk/ghost.spmv/ghost.dots"] == pytest.approx(
        0.00031676, abs=1e-8)
    assert s["ghost.chunk/ghost.dots"] == pytest.approx(0.000294243,
                                                        abs=1e-9)
    assert scoped.chunk_seconds(s, "ghost.dots") == pytest.approx(
        0.00031676 + 0.000294243, abs=1e-8)
    # the eager programs run outside any scope
    assert s[""] == pytest.approx(0.0108897, abs=1e-6)


@pytest.fixture(scope="module")
def tiny_run():
    """A served tiny run of the Poisson cell, as the metrics find it."""
    c = tiny("poisson7_128.cg_w8")
    p = harness.prepare(c, jax.devices()[:1], time.perf_counter())
    served = harness.serve(p, 2 ** 31 + 5, 1.0)
    return harness.Run(p, served, 0.0, None, None)


def _state(run):
    svc = run.prepared.svc
    return ([(r.chunks, r.ticket.status, r.ticket.result is not None)
             for r in run.requests],
            dict(svc.stats), svc.pending, run.converged_iters(),
            _metric("iters_to_tol", run), _metric("slot_fill_pct", run))


def test_the_traced_chunk_leaves_the_run_as_the_window_left_it(tiny_run):
    before = _state(tiny_run)
    assert tiny_run.prepared.svc.pending > 0     # requests still open
    m = scoped.measure(tiny_run)
    assert m["iters"] == tiny_run.chunk_iters
    assert _state(tiny_run) == before
    assert scoped.measure(tiny_run) is m          # once per run


def test_the_traced_chunk_is_the_services_own_program(tiny_run):
    from repro.solvers import stepper
    programs = stepper.chunk_programs(tiny_run.prepared.op)
    sizes = {k: fn._cache_size() for k, fn in programs.items()}
    tiny_run.__dict__.pop("_scoped", None)
    scoped.measure(tiny_run)
    after = stepper.chunk_programs(tiny_run.prepared.op)
    assert {k: fn._cache_size() for k, fn in after.items()} == sizes
