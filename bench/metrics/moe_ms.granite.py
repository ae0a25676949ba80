"""Device time (ms per round) of the dropless expert layers (held experts
and the shared expert) inside the client oracle, forward and backward:
operations under the ``oracle`` scope whose innermost model scope is
``moe`` (`granite_scopes.oracle_scope_ms`)."""
from granite_scopes import oracle_scope_ms


def read(run):
    return oracle_scope_ms(run, "moe")
