"""Device time of the Newton step's gradient phase over device busy time,
averaged over the chips used: the ops under ``gn.gradient`` (the state and
adjoint transports, the reduced gradient and its norm)."""
from bench import scopes

SCOPE = scopes.scope(r"gn\.gradient")


def read(run):
    return scopes.busy_pct(run, scopes.under(SCOPE))
