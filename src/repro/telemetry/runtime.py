"""Telemetry runtime: sink registry, span/timer API, counters.

Design constraints (the ISSUE 7 tentpole):

* **near-zero overhead, zero trace-graph impact when disabled** — with no
  sink installed, a span opens a profiler annotation and makes one attribute
  check (``emit`` makes one); nothing here ever inserts an op into a traced
  program (spans live in the Python driver loops, ``annotate`` is pure
  HLO metadata), so enabling/disabling telemetry can
  not change compiled executables or counted collectives (pinned by
  ``tests/test_telemetry.py``);
* **honest wall-clock** — with a sink installed, a span calls
  ``jax.block_until_ready`` on whatever the caller registered via
  ``sp.sync(x)`` before reading the clock, so async dispatch does not
  attribute one phase's device time to the next;
* **one clock with the device** — every span always opens a
  ``jax.profiler.TraceAnnotation`` of its name, so a profiler trace shows
  the host phases beside the device ops (``annotate`` names the device
  side).  With no trace active the annotation costs about a microsecond
  and never syncs.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Any

from jax.profiler import TraceAnnotation

from repro.telemetry import events as ev
from repro.telemetry import sinks as _sinks

_SINKS: list[Any] = []
_COUNTERS: dict[str, float] = {}
_SPAN_STACK: list[str] = []

ENV_TRACE = "REPRO_TRACE"  # path of a JSONL trace to auto-install
ENV_VERBOSITY = "REPRO_TELEMETRY_VERBOSITY"  # >0: auto console sink


def add_sink(sink: Any) -> Any:
    """Register ``sink`` (any object with ``write(record: dict)``)."""
    _SINKS.append(sink)
    return sink


def remove_sink(sink: Any) -> None:
    if sink in _SINKS:
        _SINKS.remove(sink)


def sinks() -> tuple:
    return tuple(_SINKS)


def enabled() -> bool:
    """True when at least one sink is installed (spans measure, events land)."""
    return bool(_SINKS)


def configure_from_env() -> None:
    """Install sinks from the environment (CLI entry points call this):
    ``REPRO_TRACE=path.jsonl`` adds a JSONL sink, and
    ``REPRO_TELEMETRY_VERBOSITY=1|2`` adds a console sink."""
    path = os.environ.get(ENV_TRACE)
    if path:
        add_sink(_sinks.JsonlSink(path))
    verb = int(os.environ.get(ENV_VERBOSITY, "0") or 0)
    if verb > 0:
        add_sink(_sinks.ConsoleSink(verbosity=verb))


def emit(event: ev.Event, echo: bool = False) -> dict | None:
    """Send ``event`` to every sink; with ``echo=True`` also render its
    legacy console line (unless a ConsoleSink is installed — no doubles).

    Returns the emitted record, or None when telemetry was a no-op."""
    if not _SINKS and not echo:
        return None
    rec = event.to_record()
    for s in _SINKS:
        s.write(rec)
    if echo and not any(isinstance(s, _sinks.ConsoleSink) for s in _SINKS):
        line = _sinks.render(rec)
        if line is not None:
            print(line)
    return rec


def counter(name: str, value: float = 1.0, echo: bool = False, **attrs) -> float:
    """Accumulate a named counter and emit a CounterEvent when enabled.

    The process-local total survives with telemetry disabled, so hot paths
    (e.g. the halo-overflow poison branch via ``jax.debug.callback``) can
    always count and a later ``telemetry.counters()`` read still sees them.
    """
    total = _COUNTERS.get(name, 0.0) + float(value)
    _COUNTERS[name] = total
    emit(ev.CounterEvent(name=name, value=float(value), total=total, attrs=attrs),
         echo=echo)
    return total


def counters() -> dict[str, float]:
    return dict(_COUNTERS)


def reset_counters() -> None:
    _COUNTERS.clear()


class span:
    """Nestable wall-clock span: ``with telemetry.span("pcg") as sp: ...``.

    The region always appears in a profiler trace as a
    ``jax.profiler.TraceAnnotation`` of its name.  Disabled (no sinks):
    nothing more — no clock read, no block, no event.  Enabled: the exit
    path ``block_until_ready``s whatever was registered with ``sp.sync(x)``
    (pass the jit outputs of the timed region) before reading the clock and
    emits a SpanEvent carrying the slash-joined nesting path.
    """

    __slots__ = ("name", "attrs", "wall_s", "_t0", "_sync", "_ta")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.wall_s: float | None = None
        self._t0: float | None = None
        self._sync = None
        self._ta = None

    def sync(self, x):
        """Register ``x`` to be ``block_until_ready``'d at span exit."""
        self._sync = x
        return x

    def __enter__(self):
        self._ta = TraceAnnotation(self.name)
        self._ta.__enter__()
        if _SINKS:
            _SPAN_STACK.append(self.name)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._t0 is None:
            self._ta.__exit__(exc_type, exc, tb)
            return False
        try:
            if exc_type is None and self._sync is not None:
                import jax

                jax.block_until_ready(self._sync)
            self.wall_s = time.perf_counter() - self._t0
            path = "/".join(_SPAN_STACK)
            depth = len(_SPAN_STACK) - 1
        finally:
            if _SPAN_STACK and _SPAN_STACK[-1] == self.name:
                _SPAN_STACK.pop()
            self._ta.__exit__(exc_type, exc, tb)
        if exc_type is None:
            emit(ev.SpanEvent(name=self.name, wall_s=self.wall_s, path=path,
                              depth=depth, attrs=self.attrs))
        return False


@contextlib.contextmanager
def annotate(name: str):
    """Name a region INSIDE traced code: pure HLO metadata.

    Two carriers, both metadata only — never the graph structure,
    executables, or collectives:

    * ``jax.named_scope(name)``: the ``op_name`` stack of every op (HLO
      dumps, the profiler's op tables);
    * the frontend attribute ``<family>="<name>"`` (``family`` is the part
      of ``name`` before its first dot: ``gn``, ``interp``, ``fft``...),
      which XLA prints in the instruction's text.  A TPU device trace names
      each op by that text, so a trace reader finds the phase there.
    """
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata

    with jax.named_scope(name), set_xla_metadata(**{name.split(".", 1)[0]: name}):
        yield


def jsonl_sink(path) -> _sinks.JsonlSink:
    """A JSON-lines sink for ``path`` (context manager installs/removes it)."""
    return _sinks.JsonlSink(path)


def console_sink(verbosity: int = 1, stream=None) -> _sinks.ConsoleSink:
    return _sinks.ConsoleSink(verbosity=verbosity, stream=stream)
