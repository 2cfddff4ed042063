"""From a profiler trace to device busy time, program and kernel times.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
:func:`read_trace` reads it with ``jax.profiler.ProfileData`` into plain
tuples, and everything after that is arithmetic on those tuples, so the
reduction can be checked on a recorded trace without a chip.

Device events are those of the planes named ``/device:TPU:<n>``. Busy
time is the union of the intervals of the events on their ``XLA Ops``
line; a program's time is the sum of its events on the ``XLA Modules``
line (named ``jit_<function>(<fingerprint>)``), matched by name; a
kernel's time is the sum of its op events, matched by name. An op event
is named by the HLO text of its instruction (``%paged_attention.1 = f32[...]
custom-call(...)``); it is kept under the instruction's name without its
numeric suffix (``paged_attention``). Host spans are the events, on any
host plane, that bear one of the names the harness gives its spans.

On a v5e the device events lie a few milliseconds before the host spans
that started them (a recorded trace: 2.4-3.2 ms), so an idle gap is named
by the host span over its middle only to within that skew.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from bench.stats import clip_intervals, gaps, union_length

__all__ = ["Trace", "read_trace", "find_xplane", "op_name", "HOST_SPANS"]

HOST_SPANS = ("bench.client", "engine.sched", "engine.dispatch", "engine.commit")
SKEW_S = 0.01  # device events may lie this far before the host spans that start them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """Events as ``(line, name, start_s, end_s)`` on one clock."""

    device: list  # [(device, line, name, start, end)]
    host: list  # [(name, start, end)]
    window: tuple  # (start, end) of the traced interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def devices(self) -> list:
        return sorted({d for d, *_ in self.device})

    def op_intervals(self, device) -> list:
        return [(s, e) for d, line, _, s, e in self.device if d == device and line == OPS_LINE]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        lo, hi = self.window
        return sum(
            union_length(clip_intervals(self.op_intervals(d), lo, hi)) for d in devs
        ) / len(devs)

    def _matching(self, line: str, pattern: str) -> list:
        rx = re.compile(pattern)
        lo, hi = self.window
        return [
            (name, s, e) for _, ln, name, s, e in self.device
            if ln == line and rx.search(name) and s >= lo and e <= hi
        ]

    def module_events(self, pattern: str) -> list:
        return self._matching(MODULES_LINE, pattern)

    def op_events(self, pattern: str) -> list:
        return self._matching(OPS_LINE, pattern)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` op names that took most time, averaged over devices."""
        total: dict[str, float] = {}
        for _, line, name, s, e in self.device:
            if line == OPS_LINE:
                total[name] = total.get(name, 0.0) + (e - s)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(len(self.devices()), 1)] for k, v in ranked]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest stretches with no op on the first device,
        each named by the host span that covers its middle."""
        devs = self.devices()
        if not devs:
            return []
        lo, hi = self.window
        out = []
        for s, e in gaps(self.op_intervals(devs[0]), lo, hi):
            mid = (s + e) / 2
            label = "host: outside any span"
            for name, hs, he in self.host:
                if hs <= mid <= he:
                    label = f"host: {name}"
                    break
            out.append([label, e - s])
        return sorted(out, key=lambda x: -x[1])[:n]


def op_name(hlo_text: str) -> str:
    """``%paged_attention.1 = f32[...] custom-call(...)`` -> ``paged_attention``."""
    m = re.match(r"%?([^\s=]+)", hlo_text)
    name = m.group(1) if m else hlo_text
    return re.sub(r"(\.\d+)+$", "", name)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {paths}")
    return paths[0]


def read_trace(path: str, window_ns: tuple | None = None) -> Trace:
    """Read an ``.xplane.pb``. The traced window runs from ``SKEW_S``
    before the first of the harness's host spans to the end of the last
    span or device event (over the device events where there are no
    spans), unless ``window_ns`` names it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                ops = line.name == OPS_LINE
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = op_name(ev.name) if ops else ev.name
                    device.append((plane.name, line.name, name, s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    if window_ns is not None:
        window = (window_ns[0] * 1e-9, window_ns[1] * 1e-9)
    else:
        # The harness's spans tile the traced loop end to end. Before the
        # first one the host sits in the profiler's start, which is no
        # part of the system; SKEW_S leaves room for the device clock. The
        # window ends with the last span or the last device event, which
        # may finish work started inside the last span.
        if host:
            window = (min(s for _, s, _ in host) - SKEW_S,
                      max([e for *_, e in host] + [e for *_, e in device]))
        elif device:
            window = (min(s for *_, s, _ in device), max(e for *_, e in device))
        else:
            window = (0.0, 0.0)
    return Trace(device=device, host=host, window=window)
