"""Share (%) of the rounds served whose server step took the μ-projection's
eigendecomposition (`bl.proj_mu_route`): the serve record's own
`meta.server_eigh` count over the rounds its call served, set-up chunk
included.  A program that keeps no such count gives nothing."""


def read(run):
    took = served = 0
    for rec in run.records:
        count = rec.get("meta", {}).get("server_eigh")
        if count is None:
            continue
        took += count["rounds"]
        served += count["of"]
    if served <= 0:
        return None
    return 100.0 * took / served
