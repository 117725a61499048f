"""The solve path names itself: ``ghost.*`` scopes in the compiled chunk
program's metadata, ``ghost.*`` spans of the service in a profiler
trace, and the service's iteration counters."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.matrices import laplace3d
from repro.runtime import MatrixRegistry, service
from repro.solvers import stepper
from repro.solvers.cg import cg_init, cg_step
from service_harness import ServiceHarness, assert_consistent


@pytest.fixture(scope="module")
def lap():
    return laplace3d(6)


@pytest.fixture()
def reg(lap):
    r, c, v, n = lap
    registry = MatrixRegistry()
    registry.register("lap", rows=r, cols=c, vals=v, shape=(n, n), C=16,
                      sigma=32, w_align=4, dtype=np.float32)
    return registry


def _b(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def test_names_share_one_prefix():
    assert trace.PREFIX == "ghost."
    with trace.span("probe", k=1) as sp:
        sp.set_metadata(advanced=1)     # a no-op while no profiler runs


def test_the_chunk_program_names_its_scopes(reg, lap):
    *_, n = lap
    op = reg.operator("lap")
    b = op.to_op_space(jnp.stack([jnp.asarray(_b(n, i)) for i in range(2)],
                                 axis=1))
    state = cg_init(op, b, tol=1e-6, maxiter=100)
    cg_step(op, state, 4)
    [fn] = stepper.chunk_programs(op).values()
    text = fn.lower(state).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("ghost.chunk/" in s and "/ghost.spmv/" in s for s in names)
    assert any("ghost.chunk/" in s and "/ghost.spmv/ghost.dots/" in s
               for s in names)             # the fused <p, Ap>
    assert any("ghost.chunk/" in s and "/ghost.dots/" in s
               and "ghost.spmv" not in s for s in names)   # ||r||^2
    # every SpMV of the chunk sits inside the chunk's scope
    assert all(s.split("/")[1] == "ghost.chunk"
               for s in names if "ghost.spmv" in s)


def _spans(logdir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("ghost.", "bench.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_profiler_trace_holds_the_service_spans(reg, lap, tmp_path):
    *_, n = lap
    svc = service.SolverService(reg, block_width=2, chunk_iters=8)
    tickets = [svc.submit("lap", _b(n, i), tol=1e-5, maxiter=500)
               for i in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        tickets.append(svc.submit("lap", _b(n, 2), tol=1e-5, maxiter=500))
        while svc.pending:
            with jax.profiler.TraceAnnotation("bench.step"):
                svc.step()
    finally:
        jax.profiler.stop_trace()
    assert all(t.status == "done" for t in tickets)
    spans = _spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert len(by["ghost.submit"]) == 1
    benches, steps = by["bench.step"], by["ghost.step"]
    assert len(steps) == len(benches) == svc.stats["chunks"]
    assert all(_inside(s, b) for s, b in zip(steps, benches))
    for name in ("ghost.chunk", "ghost.retire", "ghost.refill"):
        assert all(any(_inside(s, st) for st in steps) for s in by[name])
    assert all(st[3]["batches"] == 1 for st in steps)
    chunks = by["ghost.chunk"]
    assert len(chunks) == svc.stats["chunks"]
    for c in chunks:
        assert {"k", "advanced", "live", "width", "new_program"} \
            <= set(c[3])
        assert c[3]["k"] == 8 and c[3]["width"] == 2
    # the first chunk built its program, later ones reuse it
    assert [c[3]["new_program"] for c in chunks] \
        == [1] + [0] * (len(chunks) - 1)
    assert sum(c[3]["advanced"] for c in chunks) == svc.stats["iters"]
    assert sum(r[3]["retired"] for r in by["ghost.retire"]) == 3
    # opening the batch admits two requests; the third enters by refill
    admitted = [r[3]["admitted"] for r in by["ghost.refill"]]
    assert admitted[0] == 2 and sum(admitted) == 3


def _recording(monkeypatch):
    """Record (iterations advanced, batch width) of every chunk."""
    init, step, fin = service.SOLVERS["cg"]
    seen = []

    def recorded(op, state, k, M=None):
        new = step(op, state, k, M=M)
        seen.append((int(new.it) - int(state.it), int(state.x.shape[1])))
        return new

    monkeypatch.setitem(service.SOLVERS, "cg", (init, recorded, fin))
    return seen


SCENARIOS = {
    "fifo": dict(service=dict(block_width=4, chunk_iters=8),
                 submit=[dict(tol=10.0 ** -(3 + i % 4)) for i in range(7)]),
    "deadline": dict(
        service=dict(block_width=2, chunk_iters=16,
                     iter_time_hint=lambda key: 1.0),
        submit=[dict(tol=1e-30, maxiter=10 ** 6, deadline=6.0),
                dict(tol=1e-5), dict(tol=1e-30, maxiter=10 ** 6,
                                     deadline=21.0), dict(tol=1e-6)]),
    "bucketed": dict(service=dict(block_width=8, chunk_iters=8,
                                  admission="bucketed"),
                     submit=[dict(tol=10.0 ** -(2 + i % 5))
                             for i in range(5)]),
    "block": dict(service=dict(block_width=4, chunk_iters=4,
                               adaptive_width=True),
                  submit=[dict(tol=10.0 ** -(3 + i % 3), block=True)
                          for i in range(6)]),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_iteration_counters_add_up(reg, lap, monkeypatch, name):
    *_, n = lap
    sc = SCENARIOS[name]
    seen = _recording(monkeypatch)
    h = ServiceHarness(reg, **sc["service"])
    tickets = []
    for i, kw in enumerate(sc["submit"]):
        kw = dict(dict(maxiter=500), **kw)
        tickets.append(h.submit("lap", _b(n, i), **kw))
        if i % 2:
            h.step()                   # later requests enter by refill
    h.drain()
    assert_consistent(h.service, tickets)
    stats = h.service.stats
    assert stats["chunks"] == len(seen)
    assert stats["iters"] == sum(a for a, _ in seen)
    assert stats["column_iters"] == sum(a * w for a, w in seen)
    # a ticket holds its slot from admission to retirement, so the live
    # column-iterations are the iterations the tickets report
    assert stats["live_column_iters"] == sum(
        t.result.iters for t in tickets if t.result is not None)
    assert 0 < stats["live_column_iters"] <= stats["column_iters"]
    if name == "deadline":
        assert stats["deadline_chunks"] > 0
        assert stats["expired"] > 0
        assert any(a < sc["service"]["chunk_iters"] for a, _ in seen)
    if name == "block":
        assert len({w for _, w in seen}) > 1      # the width adapted
