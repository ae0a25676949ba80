"""Device time (ms per round) of the Mamba-2 mixers inside the client
oracle, forward and backward: operations under the ``oracle`` scope whose
innermost model scope is ``mamba`` (`granite_scopes.oracle_scope_ms`)."""
from granite_scopes import oracle_scope_ms


def read(run):
    return oracle_scope_ms(run, "mamba")
