"""Host time (ms per chunk) the cohort serve loop spends in
`save_checkpoint`, whose payload is the fleet's host plane."""
from trace_reduce import mean_span_ms


def read(run):
    return mean_span_ms(run.trace, run.window, "save_checkpoint", "artifacts.py")
