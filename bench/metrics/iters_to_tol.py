"""Mean iterations that the run's converged requests took (solver and
preconditioner), counted by the harness: the service chunks that
advanced each request, times the iterations of a chunk."""


def read(run):
    iters = run.converged_iters()
    return sum(iters) / len(iters) if iters else None
