"""The window's accounting and the arithmetic of ``rhs_per_s``,
``slot_fill_pct`` and ``iters_to_tol``, on a scripted service."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchtiny import harness


class Ticket:
    def __init__(self, iters):
        self.status = "queued"
        self.iters = iters
        self.result = None

    @property
    def resolved(self):
        return self.status == "done"


class ScriptedService:
    """Two slots, 16 iterations a chunk.  A request needs ``iters``
    iterations; it is admitted at the end of the step after its
    submission (a refill), or before the chunk when the step opens the
    batch."""

    chunk_iters = 16

    def __init__(self, iters):
        self.iters = list(iters)
        self.stats = {"batches_opened": 0}
        self.slots = []
        self.queue = []

    def submit(self, matrix, b, **kw):
        t = Ticket(self.iters.pop(0))
        self.queue.append(t)
        return t

    def _admit(self):
        while self.queue and len(self.slots) < 2:
            t = self.queue.pop(0)
            t.status, t.spent = "running", 0
            self.slots.append(t)

    def step(self):
        if not self.slots:
            self.stats["batches_opened"] += 1
            self._admit()
        for t in self.slots:
            t.spent += self.chunk_iters
        for t in [t for t in self.slots if t.spent >= t.iters]:
            t.status = "done"
            t.result = SimpleNamespace(iters=t.spent, converged=True)
            self.slots.remove(t)
        self._admit()
        return 1


def drive(iters, steps_before, steps_in):
    svc = ScriptedService(iters)
    traffic = {"solver": "cg", "tol": 1e-5, "maxiter": 100, "clients": 2}
    clients = harness.Clients(svc, "A", lambda i: np.full(4, i, np.float32),
                              traffic)
    for c in range(2):
        clients.submit(c)
    for _ in range(steps_before):
        harness.step(svc, clients)
    window = harness.Window(seconds=2.0)
    for _ in range(steps_in):
        harness.step(svc, clients, window)
    return clients, window


def test_a_refill_inside_the_window_leaves_one_chunk_empty():
    # client 0 needs 2 chunks a request, client 1 first needs 4; the
    # window holds steps 1 to 4 (0-based).  Each request that resolves
    # while the other slot is live leaves its slot empty for one chunk:
    # its successor is admitted by the refill at the end of that chunk
    clients, window = drive([32, 64, 32, 32, 32, 32], 1, 4)
    assert window.chunks == 4
    assert window.live_slot_chunks == 2 + 1 + 2 + 1
    # two in set-up, three submitted in the window
    assert len(clients.requests) == 5


def test_rhs_per_s_credits_a_request_that_spans_both_edges():
    # the first pair needs 3 chunks and straddles the window's start;
    # the second pair opens a new batch inside it and is still live at
    # its end
    clients, window = drive([48, 48, 64, 64, 64, 64], 1, 5)
    assert window.live_slot_chunks == 2 * 5
    run = SimpleNamespace(window=window, chunk_iters=16, width=2,
                          requests=clients.requests)
    run.converged_iters = lambda: harness.Run.converged_iters(run)
    assert run.converged_iters() == [48, 48]
    rhs = harness.load_module("metrics", "rhs_per_s").read(run)
    # 10 slot-chunks of 16 iterations in 2 s, 48 iterations a solve
    assert rhs == pytest.approx(10 * 16 / (2.0 * 48))
    fill = harness.load_module("metrics", "slot_fill_pct").read(run)
    assert fill == pytest.approx(100.0)
    mean = harness.load_module("metrics", "iters_to_tol").read(run)
    assert mean == 48


def test_iterations_are_the_harness_count_of_chunks():
    # the ticket says 299 iterations; the harness credits the 19 chunks
    # that advanced the request, as the window's slot count does
    clients, window = drive([299, 299, 10 ** 6, 10 ** 6], 0, 20)
    for r in clients.requests[:2]:
        r.ticket.result.iters = 299
    run = SimpleNamespace(chunk_iters=16, requests=clients.requests)
    assert harness.Run.converged_iters(run) == [304, 304]


def test_only_requests_due_by_the_close_may_be_missing():
    def request(due, result):
        return harness.Request(0, 0, SimpleNamespace(result=result),
                               np.ones(3, np.float32), due=due)

    coo = harness.Coo(np.arange(3), np.arange(3), np.ones(3), 3)
    x = SimpleNamespace(x=np.ones(3), converged=True)
    late = harness.check(coo, [request(True, x), request(False, None)], 1e-3)
    assert late.correct and late.attempted == 1
    lost = harness.check(coo, [request(True, x), request(True, None)], 1e-3)
    assert not lost.correct and lost.unresolved == 1


def test_a_batch_opened_in_the_step_counts_its_requests():
    clients, window = drive([16, 16, 16, 16], 0, 1)
    assert window.live_slot_chunks == 2


def test_requests_that_converge_in_the_drain_count():
    clients, window = drive([64, 64, 64, 64], 0, 2)
    run = SimpleNamespace(window=window, chunk_iters=16, width=2,
                          requests=clients.requests)
    assert harness.Run.converged_iters(run) == []
    clients.open = False
    while clients.live:
        harness.step(clients.svc, clients)
    assert harness.Run.converged_iters(run) == [64, 64]
