"""Host time (ms per restart) a resumed serve call spends in ``serve.warm``:
the carry template (the job's init program, run for its shapes) and the
serve programs loaded from the program cache, per ``serve.warm`` span in
the traced window."""
from program_spans import per_span_ms


def read(run):
    return per_span_ms(run.trace, run.window, "serve.warm")
