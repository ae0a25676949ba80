"""Host time (ms per restart) a resumed serve call spends in
``serve.restore``: the newest checkpoint read, its digest checked and the
carry put back on the device, per ``serve.restore`` span in the traced
window."""
from program_spans import per_span_ms


def read(run):
    return per_span_ms(run.trace, run.window, "serve.restore")
