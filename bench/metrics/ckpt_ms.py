"""Host time (ms per chunk) the serve loop spends in `save_checkpoint`."""
from trace_reduce import mean_span_ms


def read(run):
    return mean_span_ms(run.trace, run.window, "save_checkpoint", "artifacts.py")
