"""Host time (ms per cohort epoch) of the cohort engine's epoch swap: the
scatter of the outgoing cohort (`cohort.unload`) and the gather, transfer
and prefetch wait of the incoming one (`cohort.load`), per epoch loaded in
the traced window."""
from program_spans import cohort_swap_ms


def read(run):
    return cohort_swap_ms(run.trace, run.window)
