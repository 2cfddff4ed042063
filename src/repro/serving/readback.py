"""The engine's one sanctioned device->host readback, its step hooks and
its spans.

Every device read the serving engine makes goes through
:func:`host_readback` (the batched argmax readbacks). The engine runs
each ``PipelineServer.step`` inside :class:`engine_step` and its
scheduling, dispatch and commit parts inside :class:`engine_phase`;
those report to an observer through :func:`mark_engine_step` and
:func:`mark_engine_phase`. Without an observer the readback is a plain
``np.asarray`` and the hooks are no-ops.

An observer is :class:`repro.analysis.sanitizer.TransferSanitizer`,
which registers itself with :func:`set_observer` while it is active and
counts each sanctioned read against the current step and phase. The
hooks live here, not in ``analysis``, so that the engine imports none
of the lint package.

Spans (:class:`span`) are host events on the profiler's clock: while a
profiler records (``jax.profiler.start_trace``), each one is written
into the same trace as the device's events, with its arguments; while
none records, a span builds nothing and times nothing. The engine's
spans are named ``serve.*`` and the scheduler's ``sched.*``::

    serve.step (step_num)                 PipelineServer.step
      serve.sched                         harvest, aborts, re-placement, admission
        sched.admit (rid)                 StepScheduler.try_admit
        sched.preempt (rid, g, r)         a victim evicted for memory or a slot
        sched.reroute (rid, g, src, dst)  a stage moved off a dead replica
      serve.dispatch
        serve.call (g, r, call, pm, kappa, chunk_lanes, decode_lanes,
                    chunk_tokens, rids)   one stage call, _start_call
          serve.inputs                    host arrays, block table, hand-offs in
          serve.launch                    the stage program, its argmax, hand-offs out
      serve.commit
        serve.readback (call)             the blocking read of a call's argmax
"""

from __future__ import annotations

import time

import jax
import numpy as np

__all__ = [
    "engine_phase",
    "engine_step",
    "host_readback",
    "in_readback",
    "mark_engine_phase",
    "mark_engine_step",
    "observer",
    "set_observer",
    "span",
    "tracing",
]

_OBSERVER = None
_IN_READBACK = False


def observer():
    """The active observer, or None."""
    return _OBSERVER


def set_observer(obs) -> None:
    """Install (or, with None, remove) the observer the hooks report to."""
    global _OBSERVER
    _OBSERVER = obs


def in_readback() -> bool:
    """True while :func:`host_readback` is materializing its array."""
    return _IN_READBACK


def host_readback(x) -> np.ndarray:
    """THE sanctioned device->host readback. Engine code must route
    every device read through here; anything else is a lint finding."""
    global _IN_READBACK
    obs = _OBSERVER
    if obs is None:
        return np.asarray(x)
    obs.note_sanctioned()
    _IN_READBACK = True
    try:
        with jax.transfer_guard_device_to_host("allow"):
            return np.asarray(x)
    finally:
        _IN_READBACK = False


def mark_engine_step() -> None:
    """Close the current replica-step's sync bucket."""
    if _OBSERVER is not None:
        _OBSERVER.mark_step()


def mark_engine_phase(phase: str) -> None:
    """Tag subsequent syncs with the engine step phase ("dispatch" /
    "commit" / "other")."""
    if _OBSERVER is not None:
        _OBSERVER.phase = phase


def tracing() -> bool:
    """True while a profiler records this process."""
    return jax.profiler.TraceAnnotation.is_enabled()


class span:
    """``with span(name, args) as s:`` a host span around the block.

    While a profiler records, the block becomes an event ``name`` in its
    trace, with the keyword arguments that ``args()`` returns, called as
    the block ends (so they may name what the block decided);
    ``s.recorded`` is then true and ``s.seconds`` the block's host time.
    While none records, ``args`` is never called, no argument is built,
    and ``s.seconds`` stays 0.
    """

    __slots__ = ("name", "args", "seconds", "_t0", "_event")
    _annotation = jax.profiler.TraceAnnotation

    def __init__(self, name: str, args=None):
        self.name = name
        self.args = args
        self.seconds = 0.0
        self._event = None

    @property
    def recorded(self) -> bool:
        return self._event is not None

    def __enter__(self) -> "span":
        if tracing():
            self._event = self._annotation(self.name)
            self._event.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._event is not None:
            self.seconds = time.perf_counter() - self._t0
            if self.args is not None:
                self._event.set_metadata(**self.args())
            self._event.__exit__(*exc)


class engine_step(span):
    """One ``PipelineServer.step``: a ``serve.step`` step event numbered
    ``step_num``; as it ends, the observer's step bucket closes
    (:func:`mark_engine_step`)."""

    __slots__ = ()
    _annotation = jax.profiler.StepTraceAnnotation

    def __init__(self, step_num: int):
        super().__init__("serve.step", lambda: {"step_num": step_num})

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        mark_engine_step()


# What the observer hears as each phase of a step begins and ends: the
# scheduling part is "other", and "other" follows the commit.
_OBSERVED = {"sched": (None, None), "dispatch": ("dispatch", None), "commit": ("commit", "other")}


class engine_phase(span):
    """One phase of a step, ``"sched"``, ``"dispatch"`` or ``"commit"``:
    the span ``serve.<phase>``, and the observer's phase set as the
    phase begins and ends (:func:`mark_engine_phase`)."""

    __slots__ = ("_marks",)

    def __init__(self, phase: str):
        super().__init__("serve." + phase)
        self._marks = _OBSERVED[phase]

    def __enter__(self) -> "engine_phase":
        if self._marks[0] is not None:
            mark_engine_phase(self._marks[0])
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        if self._marks[1] is not None:
            mark_engine_phase(self._marks[1])
