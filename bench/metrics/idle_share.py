"""Share of the traced window (%) in which no operation ran on the device."""
from trace_reduce import idle_share_pct


def read(run):
    return idle_share_pct(run.trace, run.window)
