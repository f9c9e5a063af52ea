"""Device time of the tricubic interpolation kernel over device busy time,
averaged over the chips used.  The kernel is found by its Pallas kernel's
name in the op's name or HLO text."""
from bench import trace_reduce as tr

KERNEL = r"_kernel_planned|_kernel_displace|tricubic"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    shares = []
    for ops in trace["ops"].values():
        mine = tr.matching(ops, KERNEL)
        if mine:
            shares.append(100.0 * tr.busy(mine) / tr.busy(ops))
    return sum(shares) / len(shares) if shares else None
