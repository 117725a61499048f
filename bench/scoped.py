"""Device time of the solve path by the program's own scopes, over one
chunk of the service's program traced after the window.

The solve path names its device work (``repro.core.trace``): jitted code
carries ``ghost.*`` named scopes in its HLO ``op_name`` metadata
(``ghost.chunk`` around a chunk's loop, ``ghost.spmv`` and ``ghost.dots``
inside it).  After the window, one chunk of the traffic's solver runs
under the profiler on a fresh state built from the run's right-hand
sides: the same jitted program the service runs (``stepper.run_chunk``
caches it per operator), with the service and the run's requests left as
the window left them.

The TPU's trace names each device operation by its HLO instruction and
its program (the ``XLA Modules`` line), but not by its ``op_name``:
the profiler runs without HLO protos, which would carry the matrix the
service's programs embed as constants.  So each operation of the chunk
program finds its scope through the ``op_name`` metadata of that
program's compiled HLO text, looked up once per distinct operation.  An
operation without metadata takes the scope of the operation that covers
it on the same line, and each operation counts its self time: its
duration less that of the operations it covers (a ``while`` covers its
body's operations).

Where the program does not name itself, ``measure`` returns None and the
metrics read nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional

import devtrace

PREFIX = "ghost."
MODULES_LINE = "XLA Modules"
CHUNK = "ghost.chunk"


class Op(NamedTuple):
    """One device operation: its HLO instruction, the program it ran in,
    and when."""
    name: str
    module: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ------------------------------------------------------------ HLO scopes
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')


def module_name(name: str) -> str:
    """A program's name without the id the trace appends:
    ``jit_chunk(12)`` becomes ``jit_chunk``."""
    return name.split("(", 1)[0]


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name to ``op_name`` metadata, for every instruction of
    a compiled HLO module's text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def ghost_path(op_name: str) -> str:
    """The ``ghost.*`` components of an ``op_name``, outermost first:
    ``jit(chunk)/ghost.chunk/while/body/ghost.spmv/gather`` becomes
    ``ghost.chunk/ghost.spmv``."""
    return "/".join(p for p in op_name.split("/") if p.startswith(PREFIX))


# ------------------------------------------------------------- reduction
def self_times(ops: List[Op], path_of) -> Dict[str, float]:
    """Self seconds by scope path, for the operations of one line.
    ``path_of(op)`` gives an operation's own path ("" where it has none);
    an operation without one takes the path of the operation that covers
    it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [op, path, self_ns]
    done = []
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        while stack and stack[-1][0].end_ns <= o.start_ns:
            done.append(stack.pop())
        path = path_of(o) or (stack[-1][1] if stack else "")
        if stack:                     # nested: not its parent's self time
            parent = stack[-1]
            parent[2] -= min(o.end_ns, parent[0].end_ns) - o.start_ns
        stack.append([o, path, o.dur_ns])
    for _, path, ns in done + stack:
        out[path] += ns / 1e9
    return dict(out)


def reduce(ops: List[Op], names: Dict[str, Dict[str, str]]
           ) -> Dict[str, float]:
    """Self seconds by scope path.  ``names`` maps a program's name to its
    instructions' ``op_name`` (``op_names``); each distinct operation is
    looked up once."""
    paths: Dict[tuple, str] = {}

    def path_of(o: Op) -> str:
        key = (o.module, o.name)
        if key not in paths:
            paths[key] = ghost_path(names.get(o.module, {}).get(o.name, ""))
        return paths[key]

    return self_times(ops, path_of)


def chunk_seconds(scopes: Dict[str, float], last: Optional[str] = None
                  ) -> float:
    """Self seconds under ``ghost.chunk``; with ``last``, only those whose
    innermost scope is ``last``."""
    return sum(s for p, s in scopes.items()
               if p.split("/")[0] == CHUNK
               and (last is None or p.split("/")[-1] == last))


# ---------------------------------------------------------------- reading
def read_xplane(path: str) -> List[Op]:
    """The first device plane's operations, each with the program it ran
    in, of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    ops: List[Op] = []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        if device is not None and plane.name > device:
            continue
        device, ops = plane.name, []
        modules = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = sorted((float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns),
                                  module_name(ev.name))
                                 for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if line.name != devtrace.OPS_LINE:
                continue
            for ev in line.events:
                t = float(ev.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                mod = modules[i][2] if i >= 0 and t < modules[i][1] else ""
                ops.append(Op(devtrace.short(ev.name), mod, t,
                              float(ev.duration_ns)))
    return ops


def capture(fn, logdir: str) -> List[Op]:
    """Run ``fn`` under the profiler (the harness's options) and read
    back its device operations."""
    import jax

    jax.profiler.start_trace(logdir, profiler_options=devtrace.options())
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {logdir}, "
                           f"found {paths}")
    return read_xplane(paths[0])


# ------------------------------------------------------------- the chunk
def chunk_hlo(programs, state) -> Dict[str, Dict[str, str]]:
    """``op_names`` of the chunk programs that take ``state``, by program
    name, from their compiled HLO text."""
    out = {}
    for fn in programs:
        try:
            text = fn.lower(state).compile().as_text()
        except (AttributeError, TypeError, ValueError):
            continue                  # a chunk of another kind of state
        first = text.split("\n", 1)[0].split()
        name = module_name(first[1].rstrip(",")) if len(first) > 1 else ""
        out.setdefault(name, {}).update(op_names(text))
    return out


def measure(run) -> Optional[dict]:
    """Scope self seconds over one chunk of the traffic's solver traced
    after the window (``scopes``), and the block iterations it ran
    (``iters``); computed once per run.  None where the program does not
    name itself or the run holds no right-hand side."""
    if "_scoped" in run.__dict__:
        return run.__dict__["_scoped"]
    run.__dict__["_scoped"] = None
    try:
        from repro.solvers.stepper import chunk_programs
    except ImportError:               # a program without its scopes
        return None
    if not run.requests:
        return None
    import harness
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime.service import SOLVERS

    started = time.perf_counter()
    traffic, p = run.cell.traffic, run.prepared
    op, M = p.op, p.precond
    init, step, _ = SOLVERS[traffic["solver"]]
    w, k = run.width, run.chunk_iters
    B = np.stack([run.requests[j % len(run.requests)].b for j in range(w)],
                 axis=1)
    tol = jnp.full((w,), float(traffic["tol"]),
                   jnp.finfo(jnp.dtype(op.dtype)).dtype)
    state = jax.block_until_ready(
        init(op, op.to_op_space(jnp.asarray(B)), tol=tol,
             maxiter=int(traffic["maxiter"]), M=M))
    t0 = time.perf_counter()
    names = chunk_hlo([fn for key, fn in chunk_programs(op).items()
                       if key[1] == k], state)
    hlo_s = time.perf_counter() - t0
    after = {}

    def chunk():
        after["state"] = jax.block_until_ready(step(op, state, k, M=M))

    logdir = tempfile.mkdtemp(prefix="bench-scoped-")
    try:
        t0 = time.perf_counter()
        ops = capture(chunk, logdir)
        trace_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    iters = int(after["state"].it) - int(state.it)
    scopes = reduce(ops, names)
    harness.log(phase="scoped_chunk", iters=iters, ops=len(ops),
                seconds=time.perf_counter() - started, hlo_s=hlo_s,
                trace_s=trace_s, programs=sorted(names),
                modules=sorted({o.module for o in ops}), scopes=scopes)
    run.__dict__["_scoped"] = {"scopes": scopes, "iters": iters}
    return run.__dict__["_scoped"]


def chunk_ms(run, last: Optional[str] = None) -> Optional[float]:
    """Device milliseconds an iteration under ``ghost.chunk`` (with
    ``last``, under that innermost scope) in the traced chunk."""
    m = measure(run)
    if m is None or not m["iters"]:
        return None
    seconds = chunk_seconds(m["scopes"], last)
    return 1e3 * seconds / m["iters"] if seconds else None
