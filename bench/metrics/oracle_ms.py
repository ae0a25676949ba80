"""Device time (ms per round) of the client oracle: the exclusive time of
the operations whose innermost layer scope is ``oracle`` (per-client
gradients and Hessians), over the rounds the trace holds whole
(`program_spans.layer_rounds`)."""
from program_spans import layer_ms


def read(run):
    return layer_ms(run.trace, run.window, "oracle")
