"""Share (%) of the traced window's device-idle time in which the serving
thread had no program span open: idle time that no named host phase
explains."""
from program_spans import idle_unspanned_pct


def read(run):
    return idle_unspanned_pct(run.trace, run.window)
