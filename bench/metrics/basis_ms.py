"""Device time (ms per round) of the basis layer: the exclusive time of the
operations whose innermost layer scope is ``basis`` (`CoeffLayout`: the
data rotated into the basis, the coefficient target and the (n, r, d)
shift reconstruction), over the rounds the trace holds whole
(`program_spans.layer_rounds`)."""
from program_spans import layer_ms


def read(run):
    return layer_ms(run.trace, run.window, "basis")
