"""The roofline's least time for the interpolation's own work over the
tricubic kernel's device time (``bench/work.py``: points, fields and dtypes
of each call; the published peaks of ``bench/peaks.json``)."""
from bench import trace_reduce as tr
from bench import work

KERNEL = r"_kernel_planned|_kernel_displace|tricubic"


def read(run):
    trace = run["trace"]
    if trace is None or trace["peaks"] is None:
        return None
    least = spent = 0.0
    for ops in trace["ops"].values():
        for op in tr.matching(ops, KERNEL):
            w = work.call_work(op.text())
            if w is None:
                return None
            least += work.least_time(*w, trace["peaks"])[0]
            spent += op.dur
    return 100.0 * least / spent if spent > 0 else None
