"""Collective-permute time (halo exchange, packed FFT unpack) on the first
device during which no other op runs, over the traced window."""
from bench import trace_reduce as tr


def read(run):
    trace = run["trace"]
    if trace is None or not trace["ops"]:
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    if not tr.matching(ops, tr.PERMUTE):
        return None
    return 100.0 * tr.exposed(ops, tr.PERMUTE) / trace["window_s"]
