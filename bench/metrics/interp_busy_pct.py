"""Device time of the interpolation executor over device busy time,
averaged over the chips used: the ops under ``interp.plan``,
``interp.apply`` and ``interp.displace`` (``kernels/ops.Interp`` and the
mesh executor of ``dist/halo.py``), so the kernel, its pads and the plan's
weights, whatever implements them."""
from bench import scopes

SCOPE = scopes.scope(r"interp\.(plan|apply|displace)")


def read(run):
    return scopes.busy_pct(run, scopes.under(SCOPE))
