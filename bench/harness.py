"""One run of one benchmark cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
sits in a file of its own, found by name:

- ``configs/<name>.json``: the matrix (a generator and its sizes), its
  storage (C, sigma, w_align, dtype), the chips it needs and, for a
  matrix spread over several chips, the ``engine`` it is registered
  through;
- ``generators/<name>.py``: ``generate(**sizes) -> (rows, cols, vals, n)``
  and, where the generator draws its own right-hand sides,
  ``rhs(rng, **sizes) -> b`` (else they are standard normal);
- ``traffic/<name>.json``: the clients and what they ask (solver,
  preconditioner, tolerance, iteration cap, batch width), the service
  steps over which the clients join, the grace for answers due after the
  window and the limit on the true residual;
- ``metrics/<name>.py``: ``read(run) -> float | None`` for every metric,
  end-to-end and per layer.

A run has four phases.  Set-up generates the matrix from its definition,
registers it in a ``MatrixRegistry``, builds the preconditioner, loads or
compiles every program the traffic uses and lets the closed-loop clients
join over a few service steps (the ramp), so that the window sees them at
different stages of their solves.  The window steps ``SolverService`` for
the given seconds, under the profiler in a traced run; each client
submits its next right-hand side, drawn from the seed, as soon as its
request resolves.  Then the clients stop, and the service is stepped
only while a request submitted before the window is still open (each is
due by the window's close).  Last, every answer that came back is
checked against a float64 CSR reference built from the same COO.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import work  # noqa: E402

def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


# ------------------------------------------------------------- discovery
def _path(kind: str, name: str, suffix: str) -> Path:
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        have = sorted(p.name[:-len(suffix)]
                      for p in (BENCH / kind).glob(f"*{suffix}"))
        raise KeyError(f"no {kind} entry named {name!r} (have {have})")
    return path


def load_json(kind: str, name: str) -> dict:
    return json.loads(_path(kind, name, ".json").read_text())


def load_module(kind: str, name: str):
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str = ""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple = ()
    per_layer: tuple = ()


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration,
    traffic mix and the metrics it reports."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r} (have {sorted(cells)})")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(metrics):
        return tuple(Metric(m["name"], m["unit"], m.get("source", ""))
                     for m in metrics
                     if name in m.get("workloads", [name]))

    return Cell(name=name,
                config=json.loads((ROOT / cfg["file"]).read_text()),
                traffic=load_json("traffic", w["traffic"]),
                chips=int(w["chips"]),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


# ------------------------------------------------------------------ set-up
@dataclasses.dataclass
class Coo:
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    n: int


def generate(config: dict) -> Coo:
    """The configuration's matrix, from its generator and sizes."""
    spec = dict(config["matrix"])
    gen = load_module("generators", spec.pop("generator"))
    rows, cols, vals, n = gen.generate(**spec)
    for key, got in (("n", n), ("nnz", len(vals))):
        want = config.get(key)
        if want is not None and int(want) != int(got):
            raise ValueError(f"generated {key} = {got}, the configuration "
                             f"states {want}")
    return Coo(rows, cols, vals, int(n))


def register(config: dict, coo: Coo, devices, name: str = "A"):
    """A ``MatrixRegistry`` holding the matrix under ``name``: a SELL-C-sigma
    build, or a ``HeterogeneousEngine`` over ``devices`` where the
    configuration names one."""
    import jax.numpy as jnp
    from repro.runtime import MatrixRegistry

    st = config["storage"]
    layout = dict(C=int(st["C"]), sigma=int(st["sigma"]),
                  w_align=int(st["w_align"]), dtype=jnp.dtype(st["dtype"]))
    reg = MatrixRegistry()
    engine = config.get("engine")
    if engine:
        from jax.sharding import Mesh
        from repro.runtime import DevicePool, HeterogeneousEngine

        devs = list(devices)[:int(config["chips"])]
        eng = HeterogeneousEngine(coo.rows, coo.cols, coo.vals, coo.n,
                                  mesh=Mesh(np.array(devs), ("data",)),
                                  pool=DevicePool.detect(devs), **layout)
        reg.register(name, eng.operator(overlap=bool(engine["overlap"])))
    else:
        reg.register(name, rows=coo.rows, cols=coo.cols, vals=coo.vals,
                     shape=(coo.n, coo.n), **layout)
    return reg


def rhs(config: dict, seed: int, index: int, n: int) -> np.ndarray:
    """Right-hand side ``index`` of the pool of ``seed``: the
    configuration's generator draws it where it has an ``rhs``, else it
    is standard normal."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, index])
    spec = dict(config["matrix"])
    gen = load_module("generators", spec.pop("generator"))
    if hasattr(gen, "rhs"):
        b = gen.rhs(rng, **spec)
        if b.shape != (n,):
            raise ValueError(f"generator drew a right-hand side of shape "
                             f"{b.shape}, the matrix has {n} rows")
        return np.asarray(b, np.float32)
    return rng.standard_normal(n, dtype=np.float32)


class CompileCounter:
    """Counts the executables JAX builds, compiled or loaded from its
    persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Request:
    client: int
    rhs: int                           # index into the right-hand sides
    ticket: object
    b: np.ndarray
    due: bool = False                  # submitted before the window
    chunks: int = 0                    # service chunks that advanced it


class Clients:
    """Closed loop: each client keeps one request in flight and submits
    its next right-hand side as soon as that request resolves.

    The right-hand sides are a pool of one per client, ``draw(i)`` for
    ``i`` in ``range(clients)``.  At its ``k``-th request client ``c``
    sends pool entry ``(c + k) % clients``: every round of requests
    serves the whole pool."""

    def __init__(self, svc, matrix: str, draw: Callable[[int], np.ndarray],
                 traffic: dict):
        self.svc, self.matrix = svc, matrix
        self.kw = dict(solver=traffic["solver"], tol=float(traffic["tol"]),
                       maxiter=int(traffic["maxiter"]),
                       precond=traffic.get("precond"))
        self.count = int(traffic["clients"])
        self.pool = [draw(i) for i in range(self.count)]
        self.live: dict = {}
        self.requests: List[Request] = []
        self.sent = defaultdict(int)
        self.open = True
        self.due = True                # requests are due until the window

    def submit(self, c: int) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            k = self.sent[c]
            self.sent[c] += 1
            i = (c + k) % self.count
            t = self.svc.submit(self.matrix, self.pool[i], **self.kw)
            self.live[c] = Request(c, i, t, self.pool[i], due=self.due)
            self.requests.append(self.live[c])

    def poll(self) -> None:
        for c, req in list(self.live.items()):
            if req.ticket.resolved:
                del self.live[c]
                if self.open:
                    self.submit(c)


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    chunks: int = 0
    live_slot_chunks: int = 0          # sum over chunks of live requests
    #: (seconds, refills) of each service step
    steps: List[tuple] = dataclasses.field(default_factory=list)


def step(svc, clients: Clients, window: Optional[Window] = None) -> None:
    """One service step, counting for each request whether the chunk
    advanced it: it was running before the step, or queued and admitted
    by the batch that the step opened."""
    import jax
    before = {c: r.ticket.status for c, r in clients.live.items()}
    opened = svc.stats["batches_opened"]
    with jax.profiler.TraceAnnotation("bench.step"):
        chunks = svc.step()
    opened = svc.stats["batches_opened"] > opened
    advanced = [r for c, r in clients.live.items()
                if before.get(c) == "running"
                or (opened and before.get(c) == "queued"
                    and r.ticket.status != "queued")]
    if chunks:
        for r in advanced:
            r.chunks += 1
        if window is not None:
            window.chunks += chunks
            window.live_slot_chunks += len(advanced)
    clients.poll()


@dataclasses.dataclass
class Prepared:
    cell: Cell
    coo: Coo
    registry: object
    svc: object
    devices: list
    matrix: str = "A"

    @property
    def op(self):
        return self.registry.operator(self.matrix)

    @property
    def precond(self):
        spec = self.cell.traffic.get("precond")
        return self.registry.preconditioner(self.matrix, spec) if spec \
            else None


def prepare(c: Cell, devices, started: float) -> Prepared:
    """Generate and register the matrix, build the preconditioner and
    load every program that retiring a request runs."""
    import jax
    import jax.numpy as jnp
    from repro.runtime import SolverService

    t0 = time.perf_counter()
    coo = generate(c.config)
    t1 = time.perf_counter()
    reg = register(c.config, coo, devices)
    t2 = time.perf_counter()
    width = int(c.traffic["block_width"])
    svc = SolverService(reg, block_width=width)
    p = Prepared(c, coo, reg, svc, list(devices))
    p.precond                           # registry-cached from here on
    # retiring k requests at once gathers k columns of the solution and
    # maps them back to the original order: one program for each k
    op = p.op
    x = jnp.zeros((op.n, width), op.dtype)
    for k in range(1, width + 1):
        jax.block_until_ready(op.from_op_space(x[:, np.arange(k)]))
    log(phase="prepare", n=coo.n, nnz=int(len(coo.vals)),
        started_s=t0 - started, generate_s=t1 - t0, register_s=t2 - t1,
        precond_and_retire_s=time.perf_counter() - t2)
    return p


# ------------------------------------------------------------------ serving
@dataclasses.dataclass
class Served:
    clients: Clients
    window: Window
    trace: Optional[dict] = None
    compiles_in_window: int = 0

    @property
    def requests(self) -> List[Request]:
        return self.clients.requests


def ramp(p: Prepared, clients: Clients) -> List[float]:
    """Let the clients join evenly over the traffic's ``ramp_steps``
    service steps, the first group beside a request that is solved at
    submission (tolerance 1) and retires at the first step; the later
    groups enter the open batch by refill.  Returns the seconds of each
    step."""
    warm = p.svc.submit(p.matrix, clients.pool[0],
                        solver=clients.kw["solver"], tol=1.0,
                        maxiter=clients.kw["maxiter"],
                        precond=clients.kw["precond"])
    steps = int(p.cell.traffic["ramp_steps"])
    seconds = []
    for s in range(steps):
        t0 = time.perf_counter()
        for c in range(clients.count):
            if c * steps // clients.count == s:
                clients.submit(c)
        step(p.svc, clients)
        seconds.append(time.perf_counter() - t0)
    if warm.status != "done":
        raise RuntimeError(f"the warm-up request ended {warm.status}")
    return seconds


def serve(p: Prepared, seed: int, seconds: float, *, trace: bool = False,
          counter: Optional[CompileCounter] = None,
          on_window: Optional[Callable[[], None]] = None) -> Served:
    """Ramp the clients up and step through the window (under the
    profiler where asked), then stop the clients and step on only while
    a request submitted before the window is open, for at most the
    traffic's grace."""
    clients = Clients(p.svc, p.matrix,
                      lambda i: rhs(p.cell.config, seed, i, p.coo.n),
                      p.cell.traffic)
    before = counter.count if counter else 0
    steps = ramp(p, clients)
    log(phase="ramp", seconds=sum(steps), step_seconds=steps,
        compiles=(counter.count - before) if counter else 0)
    if on_window is not None:
        on_window()
    out = Served(clients, Window())
    clients.due = False

    def window():
        before = counter.count if counter else 0
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            refills = p.svc.stats["refills"]
            step(p.svc, clients, out.window)
            out.window.steps.append((time.perf_counter() - t,
                                     p.svc.stats["refills"] - refills))
            if time.perf_counter() - t0 >= seconds:
                break
        out.window.seconds = time.perf_counter() - t0
        out.compiles_in_window = (counter.count - before) if counter else 0

    if trace:
        cost = {}
        out.trace = devtrace.traced(window, cost)
        log(phase="traced_window", **cost)
    else:
        window()
    clients.open = False
    t0 = time.perf_counter()
    grace = float(p.cell.traffic["grace_s"])
    while (any(r.due for r in clients.live.values())
           and time.perf_counter() - t0 < grace):
        step(p.svc, clients)
    return out


def quiesce(svc, clients: Clients) -> None:
    """Cancel every open request and step until the service is idle, so
    that the next ``serve`` on the same service starts from nothing."""
    for r in clients.live.values():
        svc.cancel(r.ticket)
    while svc.pending:
        svc.step()


# ---------------------------------------------------------------- metrics
class Run:
    """What the metric readers read: the served requests and the window,
    the trace summary, the device memory peak, and device times of
    single kernels, traced on request after the window."""

    def __init__(self, p: Prepared, served: Served, setup_s: float,
                 memory_peak_bytes: Optional[int], peaks: Optional[dict]):
        self.prepared = p
        self.cell = p.cell
        self.window = served.window
        self.requests = served.requests
        self.trace = served.trace
        self.setup_s = setup_s
        self.memory_peak_bytes = memory_peak_bytes
        self.peaks = peaks
        self.width = int(p.cell.traffic["block_width"])
        self.chunk_iters = int(p.svc.chunk_iters)
        self._timed: dict = {}

    def converged_iters(self) -> List[int]:
        """Iterations that each converged request of the run took, as
        the harness counts them: the service chunks that advanced it,
        times the iterations of a chunk."""
        return [r.chunks * self.chunk_iters for r in self.requests
                if r.ticket.result is not None and r.ticket.result.converged]

    def nnz(self) -> int:
        return int(len(self.prepared.coo.vals))

    def _device_time(self, key: str, fn, x) -> Optional[float]:
        """Device seconds per call of ``fn(x)``: the busy time of the
        device over enough calls to span half a second, from the
        profiler's trace, compiled and warmed first.  None where the
        trace holds no device operation."""
        import jax
        if key not in self._timed:
            started = time.perf_counter()
            f = jax.jit(fn)
            jax.block_until_ready(f(x))
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            once = time.perf_counter() - t0
            reps = max(5, math.ceil(0.5 / max(once, 1e-6)))
            cost = {}
            t = devtrace.traced(
                lambda: jax.block_until_ready([f(x) for _ in range(reps)]),
                cost)
            self._timed[key] = None if t is None else t["busy_s"] / reps
            log(phase="kernel_traced", kernel=key, calls=reps,
                seconds=time.perf_counter() - started, **cost)
        return self._timed[key]

    def _vector(self):
        import jax
        op = self.prepared.op
        return jax.random.normal(jax.random.key(0), (op.n, self.width),
                                 op.dtype)

    def spmv_seconds(self) -> Optional[float]:
        return self._device_time("spmv", self.prepared.op.mv,
                                 self._vector())

    def precond_seconds(self) -> Optional[float]:
        M = self.prepared.precond
        if M is None:
            return None
        return self._device_time("precond", M.apply, self._vector())


def read_metrics(run: Run, metrics) -> dict:
    out = {}
    for m in metrics:
        value = load_module("metrics", m.name).read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


# ------------------------------------------------------------ correctness
@dataclasses.dataclass
class Checks:
    attempted: int
    unresolved: int
    unconverged: int
    over_limit: int
    worst_relres: float
    limit: float

    @property
    def failed(self) -> int:
        return self.unresolved + self.unconverged + self.over_limit

    @property
    def correct(self) -> bool:
        return self.attempted > 0 and self.failed == 0

    def numbers(self) -> dict:
        """Each number compared, beside its limit."""
        return {"worst_relres": {"value": self.worst_relres,
                                 "limit": self.limit},
                "unresolved": {"value": self.unresolved, "limit": 0},
                "unconverged": {"value": self.unconverged, "limit": 0}}


def check(coo: Coo, requests: List[Request], limit: float) -> Checks:
    """Every answer that came back, against the float64 CSR reference:
    the true relative residual ``||b - A x|| / ||b||`` must not pass
    ``limit``.  A request submitted before the window that never came
    back is unresolved; one submitted later and still open is not due,
    and not checked."""
    from scipy.sparse import csr_matrix

    A = csr_matrix((np.asarray(coo.vals, np.float64), (coo.rows, coo.cols)),
                   shape=(coo.n, coo.n))
    attempted = unresolved = unconverged = over = 0
    worst = 0.0
    for r in requests:
        res = r.ticket.result
        if res is None:
            if r.due:
                attempted += 1
                unresolved += 1
            continue
        attempted += 1
        if not res.converged:
            unconverged += 1
        b = np.asarray(r.b, np.float64)
        x = np.asarray(res.x, np.float64)
        rel = float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))
        if not np.isfinite(rel):
            rel = math.inf
        worst = max(worst, rel)
        over += rel > limit
    return Checks(attempted, unresolved, unconverged, int(over), worst,
                  float(limit))


# --------------------------------------------------------------- the run
def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(c: Cell, seed: int, seconds: float, trace: bool, *, devices,
             started: float, peaks: Optional[dict] = None) -> dict:
    """One run of cell ``c``: the result line, without the device names.
    ``started`` is the ``perf_counter`` reading at process start."""
    counter = CompileCounter()
    p = prepare(c, devices, started)
    marks = {}
    served = serve(p, seed, seconds, trace=trace, counter=counter,
                   on_window=lambda: marks.setdefault(
                       "setup_s", time.perf_counter() - started))
    steps = served.window.steps
    slowest = sorted(range(len(steps)), key=lambda i: -steps[i][0])[:3]
    log(phase="window", seconds=served.window.seconds,
        chunks=served.window.chunks,
        live_slot_chunks=served.window.live_slot_chunks,
        requests=len(served.requests),
        compiles_in_window=served.compiles_in_window,
        median_step_s=float(np.median([s for s, _ in steps])),
        slowest_steps=[[i, *steps[i]] for i in slowest])
    mem = memory_peak(p.devices[:c.chips])
    run = Run(p, served, marks["setup_s"], mem, peaks)
    metrics = read_metrics(run, c.per_layer if trace else c.end_to_end)
    result = {"metrics": metrics, "memory_peak_bytes": mem,
              "trace": served.trace}
    coo, requests = p.coo, served.requests
    del run, p, served
    gc.collect()
    checks = check(coo, requests, float(c.traffic["relres_limit"]))
    log(phase="checked", elapsed_s=time.perf_counter() - started)
    result.update(correct=checks.correct, attempted=checks.attempted,
                  failed=checks.failed, checks=checks.numbers())
    return result
