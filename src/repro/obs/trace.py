"""Bounded, deterministic event tracer.

A :class:`Tracer` records events into a ``deque(maxlen=capacity)`` ring
buffer — appends are GIL-atomic, so gateway worker threads emit without
a lock, and an unbounded run can never exhaust memory (old events fall
off the front).

Event timebases, by track:

  * ``sim`` — sim-time seconds from the simulators' own clocks. Two runs
    with the same seed produce byte-identical traces, and ``flowsim`` /
    ``flowsim_ref`` emit identical sim-event streams (pinned by
    tests/test_obs.py).
  * ``planner`` / ``gateway`` / ``service`` / ``sim-host`` wall spans —
    ``time.perf_counter()`` re-based to the tracer's start
    (``now_wall``); legal under SKY001, nondeterministic by nature.

The default tracer is a shared no-op singleton with ``enabled = False``.
Instrumented hot paths capture ``tr = get_tracer()`` once and guard
every emission with ``if tr.enabled:`` so disabled-mode overhead is one
attribute read (unmeasurable on ``flowsim_bench`` — gated by
``BENCH_obs.json``).

:class:`region` is the span primitive of the host layers: it always
enters a ``jax.profiler.TraceAnnotation`` of the same name, so the span
lands in any JAX profiler trace on the calling thread's line, nested in
whatever annotation encloses it, and it also records a wall span here
while a recording tracer is installed.
"""

from __future__ import annotations

import time
from collections import deque

DEFAULT_CAPACITY = 1 << 16

# Event tuples: (phase, name, ts_s, dur_s, track, args-or-None) with
# Chrome-trace phases — "X" complete span, "i" instant, "C" counter.


class Tracer:
    enabled = True

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        self._wall0 = time.perf_counter()

    def now_wall(self) -> float:
        """Wall seconds since this tracer was created (perf_counter)."""
        return time.perf_counter() - self._wall0

    def instant(self, name: str, ts_s: float, track: str = "sim", **args):
        self._buf.append(("i", name, float(ts_s), 0.0, track, args or None))

    def span(self, name: str, ts_s: float, dur_s: float,
             track: str = "sim", **args):
        self._buf.append(
            ("X", name, float(ts_s), float(dur_s), track, args or None)
        )

    def sample(self, name: str, ts_s: float, value, track: str = "sim"):
        self._buf.append(
            ("C", name, float(ts_s), 0.0, track, {"value": value})
        )

    def events(self) -> list:
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()

    def __len__(self) -> int:
        return len(self._buf)


class _NullTracer(Tracer):
    """The disabled tracer: every emission is a no-op."""

    enabled = False

    def __init__(self):
        super().__init__(capacity=0)

    def instant(self, name, ts_s, track="sim", **args):
        pass

    def span(self, name, ts_s, dur_s, track="sim", **args):
        pass

    def sample(self, name, ts_s, value, track="sim"):
        pass


_NULL = _NullTracer()
_CURRENT: list[Tracer] = [_NULL]  # one-slot box: swap, never rebind


def get_tracer() -> Tracer:
    """The process-current tracer (the no-op singleton when disabled)."""
    return _CURRENT[0]


def enable(capacity: int = DEFAULT_CAPACITY) -> Tracer:
    """Install (and return) a fresh recording tracer."""
    tr = Tracer(capacity=capacity)
    _CURRENT[0] = tr
    return tr


def disable() -> None:
    """Restore the shared no-op tracer."""
    _CURRENT[0] = _NULL


_ANNOTATION: list = []  # one-slot box: the profiler's annotation class


def _annotation():
    if not _ANNOTATION:  # imported on first use: repro.obs needs no jax
        from jax.profiler import TraceAnnotation

        _ANNOTATION.append(TraceAnnotation)
    return _ANNOTATION[0]


class region:
    """One host span on both clocks::

        with region("sim.build", track="sim-host", jobs=3) as args:
            ...

    It always enters ``jax.profiler.TraceAnnotation(name)``, with the name
    exactly and no metadata, since trace readers match names: a profiler
    trace then holds the span on the profiler's clock, on the calling
    thread's line, inside whatever annotation encloses the call. While a
    recording :class:`Tracer` is installed it also appends an ``"X"`` span
    on ``track`` carrying ``args``. The ``as`` target is ``args`` itself,
    so values known only at the end (a counter delta) can be added in the
    block. Parent and request come from nesting: one thread runs one
    request at a time."""

    __slots__ = ("name", "track", "args", "_ann", "_tr", "_t0")

    def __init__(self, name: str, *, track: str, **args):
        self.name = name
        self.track = track
        self.args = args

    def __enter__(self) -> dict:
        tr = _CURRENT[0]
        self._tr = tr if tr.enabled else None
        self._ann = _annotation()(self.name)
        self._ann.__enter__()
        if self._tr is not None:
            self._t0 = tr.now_wall()
        return self.args

    def __exit__(self, *exc):
        tr = self._tr
        if tr is not None:
            tr.span(self.name, self._t0, tr.now_wall() - self._t0,
                    track=self.track, **self.args)
        self._ann.__exit__(*exc)
        return False
