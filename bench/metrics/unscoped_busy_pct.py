"""Device time of the ops under none of the Newton step's four phases
(``gn.gradient``, ``gn.pcg``, ``gn.line_search``, ``gn.update``) over
device busy time, averaged over the chips used.  On the chip most of it is
the spectral transforms: XLA's TPU FFT expansion drops the scope names from
the DFT ops it makes (``bench/scopes.py``).  The rest is slot fills, input
generation and copies that carry no name."""
from bench import scopes
from bench import trace_reduce as tr


def outside(ops):
    """The ops under no phase; None where no op names one."""
    if not tr.matching(ops, scopes.ANY_PHASE):
        return None
    return [o for o in ops if not o.container and not scopes.ANY_PHASE.search(o.text())]


def read(run):
    return scopes.busy_pct(run, outside)
