"""Device time of the Newton step's line search over device busy time,
averaged over the chips used: the ops under ``gn.line_search`` (the first
Armijo trial and the backtracking loop)."""
from bench import scopes

SCOPE = scopes.scope(r"gn\.line_search")


def read(run):
    return scopes.busy_pct(run, scopes.under(SCOPE))
