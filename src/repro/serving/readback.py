"""The engine's one sanctioned device->host readback, and its step hooks.

Every device read the serving engine makes goes through
:func:`host_readback` (the batched argmax readbacks). The engine calls
:func:`mark_engine_step` once per ``PipelineServer.step`` and
:func:`mark_engine_phase` around the dispatch and commit halves of the
step. Without an observer these are a plain ``np.asarray`` and no-ops.

An observer is :class:`repro.analysis.sanitizer.TransferSanitizer`,
which registers itself with :func:`set_observer` while it is active and
counts each sanctioned read against the current step and phase. The
hooks live here, not in ``analysis``, so that the engine imports none
of the lint package.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = [
    "host_readback",
    "in_readback",
    "mark_engine_phase",
    "mark_engine_step",
    "observer",
    "set_observer",
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
