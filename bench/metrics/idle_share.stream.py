"""Share of the traced window (%) in which no operation ran on the device,
in a cohort cell: the host's gather, scatter and checkpoint leave it idle."""
from trace_reduce import idle_share_pct


def read(run):
    return idle_share_pct(run.trace, run.window)
