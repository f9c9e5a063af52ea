"""Reduce a profiler trace (``.xplane.pb``) to device busy time and op times.

A device op is an event on a device plane (``/device:TPU:<n>``) of its
``XLA Ops`` line, or, on a host-only trace, an event that names its HLO op
(``hlo_op`` stat).  Per device the reduction gives the busy time (the union
of the op intervals), the time of the ops whose name matches a pattern, and
the part of that time during which no other op runs (``exposed``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
A2A = re.compile(r"all-to-all", re.I)
PERMUTE = re.compile(r"collective-permute", re.I)
# control flow whose event spans the ops it runs (a TPU op's event name is
# its HLO text, "%while.3 = (...) while(...)"; the CPU's is "while.3")
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")
OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)")


@dataclass
class Op:
    name: str
    start: float  # seconds
    end: float
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """The HLO op's name without its number: ``tricubic_apply_pallas_padded``."""
        m = OP_NAME.match(self.name)
        return m.group(1) if m else self.name

    @property
    def container(self) -> bool:
        return bool(CONTAINER.match(self.name))

    def text(self) -> str:
        """Name and every string stat, for matching a kernel by name."""
        return " ".join([self.name] + [v for v in self.stats.values() if isinstance(v, str)])


def load(path_or_dir):
    """A ``ProfileData`` from an ``.xplane.pb`` file or the newest one under
    a trace directory."""
    from jax.profiler import ProfileData

    path = path_or_dir
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return ProfileData.from_file(path)


def device_ops(profile) -> dict[str, list[Op]]:
    """Device plane name -> its ops, sorted by start.  A trace with no device
    plane (the CPU backend) yields its host threads' HLO ops under
    ``/host:CPU``."""
    out: dict[str, list[Op]] = {}
    host: list[Op] = []
    for plane in profile.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if is_dev and line.name == OPS_LINE:
                    out.setdefault(plane.name, []).append(_op(ev, stats))
                elif plane.name == "/host:CPU" and "hlo_op" in stats:
                    host.append(_op(ev, stats))
    if not out and host:
        out["/host:CPU"] = host
    for ops in out.values():
        ops.sort(key=lambda o: o.start)
    return out


def _op(ev, stats) -> Op:
    return Op(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, stats)


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Intervals of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(ops) -> float:
    return length(union((o.start, o.end) for o in ops))


def matching(ops, pattern) -> list[Op]:
    """The ops (control flow left out) whose name or HLO text matches."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [o for o in ops if not o.container and rx.search(o.text())]


def exposed(ops, pattern) -> float:
    """Time of the ops matching ``pattern`` during which no other op runs
    (control-flow ops, which span the ops inside them, do not count)."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    leaves = [o for o in ops if not o.container]
    mine = union((o.start, o.end) for o in leaves if rx.search(o.short))
    others = union((o.start, o.end) for o in leaves if not rx.search(o.short))
    return length(subtract(mine, others))


def top_ops(ops, n=10) -> list[list]:
    """The ``n`` op names (control flow left out) that took the most device
    time, with seconds."""
    tot: dict[str, float] = {}
    for o in ops:
        if not o.container:
            tot[o.short] = tot.get(o.short, 0.0) + o.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_spans(profile) -> list[Op]:
    """Host events (Python calls, dispatches, compiles) on the host plane."""
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" not in stats and ev.duration_ns > 0:
                    out.append(_op(ev, stats))
    return out


def idle_gaps(ops, host=(), n=10) -> list[list]:
    """The ``n`` longest gaps between device ops, each named by the
    shortest host event that covers its middle (what the host was doing)."""
    spans = union((o.start, o.end) for o in ops)
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(spans, spans[1:])),
                  reverse=True)[:n]
    out = []
    for length_s, e0, s1 in gaps:
        mid = 0.5 * (e0 + s1)
        cover = [h for h in host if h.start <= mid <= h.end]
        out.append([min(cover, key=lambda h: h.dur).name if cover else "no host event",
                    length_s])
    return out
