"""Share (%) of the rounds served whose server step took the μ-projection's
eigendecomposition (`bl.proj_mu_route`): the serve record's own
`meta.server_eigh` count over the rounds its call served, set-up chunk
included.  A program that keeps no such count gives nothing."""
from program_spans import server_eigh_share


def read(run):
    share = server_eigh_share(run.records)
    return None if share is None else 100.0 * share
