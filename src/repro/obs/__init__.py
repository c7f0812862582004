"""Skytrace: the deterministic observability plane (ISSUE 9).

Two small, dependency-free primitives the rest of the repo instruments
itself with:

  * ``metrics`` — a process-local :class:`MetricsRegistry` of named
    counters / gauges / histograms. Ad-hoc module globals
    (``milp.N_STRUCT_BUILDS``) and report-only tallies
    (``GatewayReport.workers_leaked``) register here; reports expose a
    filtered snapshot through their ``to_dict()`` ``metrics`` section.
  * ``trace`` — a :class:`Tracer` recording spans, instant events and
    counter samples into a bounded ring buffer. Sim events carry
    sim-time; planner / gateway events carry ``perf_counter`` wall time
    re-based to the tracer's start. Disabled (the default) it is a
    shared no-op singleton and instrumented hot paths skip event
    construction entirely behind ``if tr.enabled:``. ``region`` is the
    host layers' span: a JAX profiler annotation of the same name always,
    and a tracer span while one records.

``export`` renders a tracer's buffer as Chrome-trace / Perfetto JSON or
a plain-text timeline; ``python -m repro.obs`` runs a seeded chaos
scenario and exports its (byte-deterministic) sim trace.
"""

from __future__ import annotations

from .export import text_timeline, to_chrome_trace, trace_json, write_trace
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    get_registry,
)
from .trace import Tracer, disable, enable, get_tracer, region

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "Tracer",
    "disable",
    "enable",
    "get_registry",
    "get_tracer",
    "region",
    "text_timeline",
    "to_chrome_trace",
    "trace_json",
    "write_trace",
]
