"""Device time (ms per round) of the operations whose JAX op_name is the
eigendecomposition of the server step (`proj_mu`'s eigh): their exclusive
time in each round's eigendecomposition that the trace holds whole, averaged
(the profiler keeps a bounded prefix of a chunk's device events)."""
from trace_reduce import op_clusters


def read(run):
    if run.trace is None or run.window is None:
        return None
    whole = [ns for ns, complete in op_clusters(
        run.trace, run.window, lambda label: label.split("/")[-1] == "eigh") if complete]
    if not whole:
        return None
    return sum(whole) / len(whole) / 1e6
