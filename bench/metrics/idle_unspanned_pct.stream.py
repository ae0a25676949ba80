"""Share (%) of the traced window's device-idle time in which the serving
thread had no program span open, in a cohort cell, where the host's
checkpoint and epoch swaps leave the device idle."""
from program_spans import idle_unspanned_pct


def read(run):
    return idle_unspanned_pct(run.trace, run.window)
