"""Device time of the Newton step's Krylov phase over device busy time,
averaged over the chips used: the ops under ``gn.pcg`` (the preconditioned
CG solve, its Hessian matvecs and preconditioner, and the descent
safeguard)."""
from bench import scopes

SCOPE = scopes.scope(r"gn\.pcg")


def read(run):
    return scopes.busy_pct(run, scopes.under(SCOPE))
