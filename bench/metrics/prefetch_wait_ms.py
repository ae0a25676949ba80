"""Host time (ms per cohort epoch) the cohort engine waited for the next
epoch's gather and transfer: its own `prefetch_wait_us` counter over
`epochs_loaded`, from the serve record of the window."""


def read(run):
    wait = loaded = 0.0
    for rec in run.records:
        pf = rec.get("meta", {}).get("prefetch")
        if not pf:
            continue
        wait += pf.get("prefetch_wait_us", 0.0)
        loaded += pf.get("epochs_loaded", 0)
    if loaded <= 0:
        return None
    return wait / 1e3 / loaded
