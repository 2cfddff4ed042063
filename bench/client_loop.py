"""The open-loop client: requests fall due on a schedule, whatever the server does.

Each turn submits every request whose due time has passed, calls
``PipelineServer.step`` once, then stamps the host time of every token
that has newly appeared in a request's ``generated`` list: the moment the
client can see it. Latency runs from a request's due time, so a stalled
server also delays every request that falls due during the stall, and the
generator's own lateness is kept beside it.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax

__all__ = ["Client", "PhaseClock", "run_window"]


@dataclasses.dataclass
class Client:
    prompt_len: int
    n_out: int
    due: float  # host clock
    req: object = None
    refused: bool = False
    lag_s: float = 0.0  # how late the generator submitted it
    token_times: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.done and not self.req.dropped

    @property
    def failed(self) -> bool:
        return not self.done


class PhaseClock:
    """Observer of the engine's step hooks (``repro.serving.readback``).

    The engine sets ``phase`` to ``"dispatch"``, ``"commit"`` and
    ``"other"`` inside each ``step``; with the step's entry, stamped by
    :meth:`begin_step`, that splits a step into its scheduling, dispatch
    and commit parts. With ``annotate`` the parts are also written into
    the profiler's trace as ``engine.sched``, ``engine.dispatch`` and
    ``engine.commit``.
    """

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.steps: list[list[float]] = []  # [entry, dispatch, commit, other]
        self._span = None
        self._phase = "other"

    def note_sanctioned(self) -> None:
        pass

    def mark_step(self) -> None:
        pass

    def _open(self, name):
        self._close()
        if self.annotate:
            self._span = jax.profiler.TraceAnnotation(name)
            self._span.__enter__()

    def _close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def begin_step(self) -> None:
        self.steps.append([time.perf_counter()])
        self._open("engine.sched")

    @property
    def phase(self) -> str:
        return self._phase

    @phase.setter
    def phase(self, value: str) -> None:
        self._phase = value
        if self.steps and len(self.steps[-1]) < 4:
            self.steps[-1].append(time.perf_counter())
        if value == "dispatch":
            self._open("engine.dispatch")
        elif value == "commit":
            self._open("engine.commit")
        else:
            self._close()


def run_window(server, arrivals, *, seconds: float, drain_s: float, events=(),
               clock: PhaseClock | None = None, on_tick=None):
    """Offer ``arrivals`` open-loop over a window of ``seconds`` from now,
    then step on with no new arrivals until every request has ended or
    ``drain_s`` has passed. Returns ``(clients, t0, t_end, steps)``, with
    the number of engine steps begun inside the window.

    ``events``: ``[{"at": fraction of the window, "do": "fail"|"recover",
    "stage": g, "replica": r}]``. ``on_tick(now)`` runs once per turn.
    """
    t0 = time.perf_counter()
    end = t0 + seconds
    clients = [Client(len(a.prompt), a.n_out, t0 + a.due_s) for a in arrivals]
    due = collections.deque(zip(clients, arrivals))
    todo = sorted(events, key=lambda e: e["at"])
    live: list[Client] = []
    steps = 0
    annotate = clock is not None and clock.annotate
    while True:
        now = time.perf_counter()
        if on_tick is not None:
            on_tick(now, t0)
        span = jax.profiler.TraceAnnotation("bench.client") if annotate else None
        if span is not None:
            span.__enter__()
        while todo and now >= t0 + todo[0]["at"] * seconds:
            ev = todo.pop(0)
            act = server.fail_replica if ev["do"] == "fail" else server.recover_replica
            act(ev["stage"], ev["replica"])
        while due and due[0][0].due <= now:
            c, a = due.popleft()
            c.lag_s = now - c.due
            c.req = server.submit(a.prompt, n_tokens=a.n_out)
            if c.req is None:
                c.refused = True
            else:
                live.append(c)
        if span is not None:
            span.__exit__(None, None, None)
        if not live:
            if not due:
                break
            time.sleep(max(0.0, min(due[0][0].due, end) - time.perf_counter()))
            continue
        if now >= end + drain_s:
            break
        if clock is not None:
            clock.begin_step()
        steps += now < end
        server.step()
        t = time.perf_counter()
        still = []
        for c in live:
            n = len(c.req.generated)
            while len(c.token_times) < n:
                c.token_times.append(t)
            if not (c.req.done or c.req.dropped):
                still.append(c)
        live = still
    return clients, t0, end, steps
