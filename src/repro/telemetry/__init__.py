"""``repro.telemetry`` — the measurement substrate (ISSUE 7 tentpole).

Structured tracing, counters, and per-job metrics for every layer:

* ``span(name)`` — nestable host phase: always a
  ``jax.profiler.TraceAnnotation`` of its name (so a profiler trace shows
  it beside the device ops); with a sink installed also a wall-clock timer
  (``block_until_ready`` at exit via ``sp.sync(x)``) that emits a
  SpanEvent.  Zero trace-graph impact either way.
* ``annotate(name)`` — the device side: puts the phase's name in the HLO
  metadata of the ops it traces (the ``op_name`` stack, and a frontend
  attribute that a TPU trace prints in each op's name).
* typed events (``events.py``) with a versioned JSON-lines schema —
  Newton iterations, ladder levels, serve jobs, counters, collectives,
  bench rows — validated by ``validate_record`` (the CI contract).
* sinks: ``jsonl_sink(path)`` (the durable trace ``trace_report`` reads),
  ``console_sink(verbosity)`` (the single renderer behind every
  ``verbose=`` knob), ``ListSink`` (tests).
* ``count_collectives(lowered)`` — the HLO collective counting the tests
  and benchmark suites used to re-derive privately, as a reusable API.

Typical run capture::

    from repro import telemetry
    with telemetry.jsonl_sink("results/run.jsonl"):
        out = multilevel.solve(rho_R, rho_T, grid, cfg)
    # then: python -m repro.analysis.trace_report results/run.jsonl
"""
from repro.telemetry.collectives import count_collectives, emit_collectives, hlo_text
from repro.telemetry.events import (
    SCHEMA_VERSION,
    BenchEvent,
    CollectivesEvent,
    CounterEvent,
    Event,
    FaultEvent,
    JobEvent,
    LevelEvent,
    LevelStartEvent,
    NewtonIterEvent,
    RecoveryEvent,
    ServeStepEvent,
    SolveEvent,
    SpanEvent,
    validate_record,
)
from repro.telemetry.runtime import (
    add_sink,
    annotate,
    configure_from_env,
    console_sink,
    counter,
    counters,
    emit,
    enabled,
    jsonl_sink,
    remove_sink,
    reset_counters,
    sinks,
    span,
)
from repro.telemetry.sinks import ConsoleSink, JsonlSink, ListSink, render

__all__ = [
    "SCHEMA_VERSION",
    "Event",
    "SpanEvent",
    "NewtonIterEvent",
    "LevelEvent",
    "LevelStartEvent",
    "JobEvent",
    "ServeStepEvent",
    "CounterEvent",
    "CollectivesEvent",
    "BenchEvent",
    "SolveEvent",
    "FaultEvent",
    "RecoveryEvent",
    "validate_record",
    "span",
    "annotate",
    "emit",
    "counter",
    "counters",
    "reset_counters",
    "enabled",
    "sinks",
    "add_sink",
    "remove_sink",
    "configure_from_env",
    "jsonl_sink",
    "console_sink",
    "render",
    "JsonlSink",
    "ConsoleSink",
    "ListSink",
    "count_collectives",
    "emit_collectives",
    "hlo_text",
]
