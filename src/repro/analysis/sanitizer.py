"""Runtime device->host transfer sanitizer for the serving engine.

Each hidden per-step host sync serializes the host scheduler against
device compute — exactly what blocks the async-engine refactor
(ROADMAP). This module makes the syncs *visible and countable*:

* :func:`host_readback` (defined in :mod:`repro.serving.readback`, so
  the engine imports nothing from this package) is the engine's single
  sanctioned choke point for device->host reads (the batched argmax
  readbacks). Under an active :class:`TransferSanitizer` every call is
  counted against the current replica-step.
* :class:`TransferSanitizer` additionally installs
  ``jax.transfer_guard_device_to_host`` (inert on CPU where d2h is a
  zero-copy buffer view, but it turns unsanctioned transfers into hard
  errors on accelerator backends) and intercepts the common host
  materialization paths (``ArrayImpl._value`` — behind ``int()`` /
  ``float()`` / ``.tolist()`` — and ``ArrayImpl.__array__`` — behind
  ``jax.device_get``) to count *unsanctioned* syncs; ``strict=True``
  raises :class:`HostSyncError` on the spot.

The engine calls :func:`repro.serving.readback.mark_engine_step` once
per ``PipelineServer.step`` so counts bucket per replica-step and tests
can assert "<= K syncs per step" — the measurable precondition for
the async engine core. With the async engine it additionally calls
``mark_engine_phase`` around the producer ("dispatch") and consumer
("commit") halves of the step, so sanctioned syncs bucket by *where*
in the step they happened: the async contract is zero sanctioned syncs
inside the dispatch phase — readbacks drain only at the commit
boundary (``sanctioned_by_phase``).

Caveat: on the CPU backend a raw ``np.asarray(device_array)`` goes
through the C-level buffer protocol, which neither the transfer guard
nor the interception sees (it is also genuinely copy-free there). Run
the sanitizer on an accelerator backend for airtight enforcement; on
CPU the counted choke point plus the ``_value``/``__array__`` hooks
cover the engine's and the common injected sync paths. Enter the
sanitizer *after* warmup: tracing/compilation legitimately reads
constants through ``_value``.
"""

from __future__ import annotations

import contextlib

import jax

from ..serving import readback
from ..serving.readback import host_readback

__all__ = [
    "HostSyncError",
    "TransferSanitizer",
    "active_sanitizer",
    "host_readback",
]


class HostSyncError(RuntimeError):
    """An unsanctioned device->host sync under a strict sanitizer."""


def active_sanitizer() -> "TransferSanitizer | None":
    return readback.observer()


def _array_impl_type():
    import jax.numpy as jnp

    return type(jnp.zeros((), jnp.float32))


class _CountingValue:
    """Replacement ``ArrayImpl._value`` descriptor: counts (or rejects)
    host materializations that bypassed :func:`host_readback`."""

    def __init__(self, orig):
        self._orig = orig

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        _note_unsanctioned("ArrayImpl._value (int()/float()/.tolist() path)")
        return self._orig.__get__(obj, objtype)


def _note_unsanctioned(via: str) -> None:
    s = readback.observer()
    if s is None or readback.in_readback():
        return
    s._step_unsanctioned += 1
    if s.strict:
        raise HostSyncError(
            f"unsanctioned device->host sync via {via}; route engine "
            "readbacks through repro.serving.readback.host_readback"
        )


class TransferSanitizer:
    """Count device->host syncs per replica-step; optionally fail fast.

    ::

        with TransferSanitizer() as san:
            for _ in range(n):
                server.step()          # engine marks each step
        assert san.max_per_step <= K
        assert san.unsanctioned_total == 0
    """

    def __init__(self, strict: bool = False, guard: str = "disallow"):
        self.strict = strict
        self.guard = guard
        self.per_step: list[int] = []  # sanctioned + unsanctioned per step
        self.sanctioned_total = 0
        self.unsanctioned_total = 0
        # Engine step phase of each sanctioned sync ("dispatch" /
        # "commit"; "other" outside the engine's phase markers). The
        # async engine's contract: sanctioned_by_phase["dispatch"] == 0.
        self.phase = "other"
        self.sanctioned_by_phase: dict[str, int] = {
            "dispatch": 0, "commit": 0, "other": 0,
        }
        self._step_sanctioned = 0
        self._step_unsanctioned = 0
        self._stack: contextlib.ExitStack | None = None
        self._patched: list[tuple] = []

    # -- step accounting -------------------------------------------------
    def note_sanctioned(self) -> None:
        """Count one :func:`host_readback` against this step and phase."""
        self._step_sanctioned += 1
        by_phase = self.sanctioned_by_phase
        by_phase[self.phase] = by_phase.get(self.phase, 0) + 1

    def mark_step(self) -> None:
        self.per_step.append(self._step_sanctioned + self._step_unsanctioned)
        self.sanctioned_total += self._step_sanctioned
        self.unsanctioned_total += self._step_unsanctioned
        self._step_sanctioned = 0
        self._step_unsanctioned = 0

    @property
    def max_per_step(self) -> int:
        return max(self.per_step, default=0)

    @property
    def total(self) -> int:
        return self.sanctioned_total + self.unsanctioned_total

    # -- install / restore ----------------------------------------------
    def __enter__(self) -> "TransferSanitizer":
        if readback.observer() is not None:
            raise RuntimeError("TransferSanitizer does not nest")
        impl = _array_impl_type()
        orig_value = impl.__dict__["_value"]
        orig_array = impl.__dict__["__array__"]

        def counting_array(array_self, *args, **kwargs):
            _note_unsanctioned("ArrayImpl.__array__ (jax.device_get path)")
            return orig_array(array_self, *args, **kwargs)

        impl._value = _CountingValue(orig_value)
        impl.__array__ = counting_array
        self._patched = [(impl, "_value", orig_value), (impl, "__array__", orig_array)]
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(jax.transfer_guard_device_to_host(self.guard))
        readback.set_observer(self)
        return self

    def __exit__(self, *exc) -> None:
        readback.set_observer(None)
        for impl, name, orig in self._patched:
            setattr(impl, name, orig)
        self._patched = []
        if self._step_sanctioned or self._step_unsanctioned:
            self.mark_step()  # flush a trailing partial step
        if self._stack is not None:
            self._stack.close()
            self._stack = None
