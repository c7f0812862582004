"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

From the device planes (``/device:TPU:<i>``): the union of the intervals in
which an operation ran (busy time), the summed device time and execution
count of each program (``XLA Modules`` line) and of each operation
(``XLA Ops`` line), and the spans whose events the profiler dropped.
From the host planes: the benchmark's own ``bench.*`` annotations and the
other events of the Python thread. The idle gaps of the device inside the
``bench.window`` annotation are labelled by the innermost benchmark
annotation around them and what the Python thread was doing there.
Dropped spans count neither as busy nor as idle. Times are in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:"
DROPPED = "Trace Buffers Dropped"  # a device event over what was lost
MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW = "bench.window"
PYTHON = "python"  # the host line of the Python thread's annotations
_ID = re.compile(r"\(\d+\)$")


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def covered(merged, a, b):
    """Length of [a, b) covered by merged intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


@dataclasses.dataclass
class Reduction:
    window: tuple  # (start, end) of the bench.window annotation
    busy: list  # per device: merged busy intervals inside the window
    programs: dict  # name -> [count, device seconds], summed over devices
    ops: dict  # name -> [count, device seconds], summed over devices
    annotations: list  # (name, start, end) of bench.* host annotations
    n_devices: int
    host: list = dataclasses.field(default_factory=list)  # (name, start,
    # end) of the other events on the Python thread: what the host did
    dropped: list = dataclasses.field(default_factory=list)  # merged spans
    # whose device events the profiler dropped (its buffers ran full)

    @property
    def window_s(self) -> float:
        """The window's length less what the profiler dropped."""
        return self.window[1] - self.window[0] - self.dropped_s

    @property
    def dropped_s(self) -> float:
        return covered(self.dropped, *self.window)

    @property
    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        return sum(covered(b, *self.window) for b in self.busy) / max(
            self.n_devices, 1)

    def busy_in(self, a: float, b: float) -> float:
        """Busy seconds inside [a, b), averaged over the devices."""
        return sum(covered(x, a, b) for x in self.busy) / max(
            self.n_devices, 1)

    def program(self, pattern: str):
        """[count, seconds] summed over programs whose name matches."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.programs.items() if rx.search(k)]
        return [sum(h[0] for h in hits), sum(h[1] for h in hits)]

    def op(self, pattern: str):
        """[count, seconds] summed over operations whose name matches."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.ops.items() if rx.search(k)]
        return [sum(h[0] for h in hits), sum(h[1] for h in hits)]

    def top_ops(self, n: int):
        return [[k, v[1]] for k, v in sorted(
            self.ops.items(), key=lambda kv: -kv[1][1])[:n]]

    def gaps(self):
        """Idle gaps of the first device inside the window, each as
        (label, seconds): the innermost bench annotation around its
        midpoint, and after a colon the innermost event of the Python
        thread there, if any."""
        lo, hi = self.window
        busy = union((self.busy[0] if self.busy else []) + self.dropped)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label = _innermost(self.annotations, mid) or WINDOW
            doing = _innermost(self.host, mid)
            out.append((f"{label}: {doing}" if doing else label, b - a))
        return out

    def top_gaps(self, n: int):
        return [[label, s] for label, s in sorted(
            self.gaps(), key=lambda g: -g[1])[:n]]


def _innermost(spans, t):
    around = [(e - s, name) for name, s, e in spans if s <= t < e]
    return min(around)[1] if around else None


def op_name(name: str) -> str:
    """An operation's instruction name: ``%fusion.3 = f32[8] fusion(...)``
    gives ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def reduce_file(path: str, n_devices: int) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    annotations, host, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({line.name: list(_events(line))
                            for line in plane.lines})
            continue
        for line in plane.lines:
            for ev in _events(line):
                if ev[0].startswith("bench."):
                    annotations.append(ev)
                elif line.name == PYTHON:
                    host.append(ev)
    devices = devices[:n_devices]
    wins = [(s, e) for name, s, e in annotations if name == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    window = wins[0]
    programs, ops, busy, dropped = {}, {}, [], []
    for dev in devices:
        dropped += [(s, e) for evs in dev.values() for name, s, e in evs
                    if name == DROPPED]
        for name, s, e in dev.get(MODULES, []):
            acc = programs.setdefault(_ID.sub("", name), [0, 0.0])
            acc[0] += 1
            acc[1] += e - s
        # busy: any operation or program running (a program's span
        # covers its operations where the profiler kept only the program)
        spans = [(s, e) for _, s, e in dev.get(MODULES, [])]
        for name, s, e in dev.get(OPS, []):
            acc = ops.setdefault(op_name(name), [0, 0.0])
            acc[0] += 1
            acc[1] += e - s
            spans.append((s, e))
        busy.append(union(spans))
    return Reduction(window, busy, programs, ops, annotations, len(devices),
                     host, union(dropped))


def reduce_dir(trace_dir: str, n_devices: int) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under a profiler output directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(files, key=os.path.getmtime), n_devices)
