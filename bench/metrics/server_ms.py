"""Device time (ms per round) of the server step: the exclusive time of the
operations whose innermost layer scope is ``server`` (`Reducer.once`:
BL1's [H]_mu eigendecomposition and solve), over the rounds the trace holds
whole (`program_spans.layer_rounds` says how rounds are cut)."""
from program_spans import layer_ms


def read(run):
    return layer_ms(run.trace, run.window, "server")
