"""Device time (ms per round) of the compression layer: the exclusive time
of the operations whose innermost layer scope is ``compress`` (the Top-K
shift updates and the model downlink), over the rounds the trace holds
whole (`program_spans.layer_rounds`)."""
from program_spans import layer_ms


def read(run):
    return layer_ms(run.trace, run.window, "compress")
