"""Device ops found by the names the program gives its layers.

``repro.telemetry.annotate(name)`` writes the frontend attribute
``<family>="<name>"`` (``family`` is the part of ``name`` before its first
dot) into every op it traces, and XLA prints it in the op's HLO text, which
is the op's name in a TPU trace: ``frontend_attributes={gn="gn.pcg",
interp="interp.apply",...}``.  The readers key on those names alone, never
on the name of the kernel or fusion that implements a layer.  XLA's own
rewrites can drop the attribute: the TPU's FFT expansion does, so the
transforms' ops carry neither ``fft.*`` nor their ``gn.*`` phase.
"""
from __future__ import annotations

import re

from bench import trace_reduce as tr

# the four phases of a Newton step (core/gauss_newton.py), which split its
# device ops with no overlap
PHASES = ("gn.gradient", "gn.pcg", "gn.line_search", "gn.update")


def scope(names: str) -> re.Pattern:
    """Ops carrying one of ``names`` (a regex over scope names, dots literal
    as written: ``interp\\.(plan|apply|displace)``) as a frontend attribute."""
    return re.compile(rf'="(?:{names})"')


ANY_PHASE = scope("|".join(re.escape(p) for p in PHASES))


def under(pattern: re.Pattern):
    """The picker of the ops under a scope: None where no op carries it."""
    return lambda ops: tr.matching(ops, pattern) or None


def busy_pct(run, pick):
    """Busy time of the ops ``pick(ops)`` returns over device busy time,
    averaged over the chips where it returns a list; None where it returns
    None on every chip (a trace of a program that does not name the scope)."""
    trace = run["trace"]
    if trace is None:
        return None
    shares = [100.0 * tr.busy(found) / tr.busy(ops)
              for ops in trace["ops"].values() if (found := pick(ops)) is not None]
    return sum(shares) / len(shares) if shares else None
