"""The serving program's own spans in a profiler trace.

``PipelineServer`` writes host spans named ``serve.*`` and ``sched.*``
(``src/repro/serving/readback.py``) into the same ``.xplane.pb`` as the
device's events. :func:`read_program` reads them as ``(name, start_s,
end_s, args)``; :func:`label_gaps` names each idle gap of the device by
the harness's span over its middle, as ``Trace.idle_gaps`` does, and
then by the innermost program span there (``host: engine.commit >
serve.readback``); :func:`span_table` and :func:`launch_to_module`
reduce them to per-step and per-call times.

As a script it runs one cell as ``bench/run.py --trace 1`` does and also
prints, as its last line, what the program's spans show of the traced
window and the end-to-end metrics of that traced run::

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parent.parent)  # the package ``bench``

from bench.stats import gaps, percentile  # noqa: E402

__all__ = ["PREFIXES", "read_program", "innermost", "label_gaps", "span_table",
           "launch_to_module"]

PREFIXES = ("serve.", "sched.")
STAGE_PROGRAMS = r"^jit_(decode_fn|chunk_pages)\b"
# How far before the launch span a device event may start and still be
# the launch's own (the device clock runs a few ms ahead of the host's).
LAUNCH_SKEW_S = 0.01


def read_program(path: str) -> list:
    """The program's spans in an ``.xplane.pb``, by start time."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda x: x[1])


def innermost(program: list, t: float) -> str | None:
    """The innermost program span covering ``t``: the latest to start of
    those that do (the spans nest on the engine's one thread)."""
    name = None
    for n, s, e, _ in program:
        if s > t:
            break
        if t <= e:
            name = n
    return name


def label_gaps(trace, program: list, n: int = 10) -> list:
    """``Trace.idle_gaps`` with the innermost program span over each
    gap's middle appended to its label."""
    devs = trace.devices()
    if not devs:
        return []
    lo, hi = trace.window
    out = []
    for s, e in gaps(trace.op_intervals(devs[0]), lo, hi):
        mid = (s + e) / 2
        label = next((f"host: {name}" for name, hs, he in trace.host if hs <= mid <= he),
                     "host: outside any span")
        inner = innermost(program, mid)
        if inner is not None:
            label += f" > {inner}"
        out.append([label, e - s])
    return sorted(out, key=lambda x: -x[1])[:n]


def span_table(program: list, window: tuple) -> dict:
    """Per span name, over the spans that start in ``window``: how many,
    their total ms, and ms per ``serve.step`` and per ``serve.call``."""
    lo, hi = window
    inside = [(n, s, e) for n, s, e, _ in program if lo <= s < hi]
    steps = sum(n == "serve.step" for n, _, _ in inside)
    calls = sum(n == "serve.call" for n, _, _ in inside)
    table = {}
    for n, s, e in inside:
        row = table.setdefault(n, {"count": 0, "total_ms": 0.0})
        row["count"] += 1
        row["total_ms"] += 1e3 * (e - s)
    for row in table.values():
        row["per_step_ms"] = row["total_ms"] / steps if steps else None
        row["per_call_ms"] = row["total_ms"] / calls if calls else None
    return {"steps": steps, "calls": calls, "spans": table}


def launch_to_module(trace, program: list) -> list:
    """Seconds from each ``serve.launch`` span's start to the start of the
    stage program it issued, matched in order: each launch takes the
    first unmatched stage-program event that starts no more than
    ``LAUNCH_SKEW_S`` before it. A launch queued behind running work
    reads long; the least readings bound the dispatch latency less the
    clocks' skew."""
    modules = sorted(s for _, s, _ in trace.module_events(STAGE_PROGRAMS))
    out, k = [], 0
    for n, s, _, _ in program:
        if n != "serve.launch":
            continue
        while k < len(modules) and modules[k] < s - LAUNCH_SKEW_S:
            k += 1
        if k == len(modules):
            break
        out.append(modules[k] - s)
        k += 1
    return out


def main(argv=None) -> int:
    import argparse
    import json

    from bench import run, trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    # ``run_cell`` deletes its trace and keeps its clients to itself: wrap
    # the two functions it calls to see them.
    seen = {}
    read_trace, run_window = trace_reduce.read_trace, run.run_window

    def reading(path, *a, **k):
        seen["trace"] = read_trace(path, *a, **k)
        seen["program"] = read_program(path)
        return seen["trace"]

    def windowed(*a, **k):
        seen["window"] = run_window(*a, **k)
        return seen["window"]

    trace_reduce.read_trace, run.run_window = reading, windowed
    cell = run.load_cell(args.workload)
    run.log(f"compile cache: {run.use_compile_cache()}")
    try:
        result = run.run_cell(cell, args.seed, args.seconds, True)
    except run.NoChip as e:
        run.log(f"no result: {e}")
        return 3
    finally:
        trace_reduce.read_trace, run.run_window = read_trace, run_window
    clients, t0, end, steps = seen["window"]
    tr, program = seen["trace"], seen["program"]
    skew = launch_to_module(tr, program)
    out = {
        "correct": result["correct"],
        "metrics": result["metrics"],
        "end_to_end_traced": run.end_to_end(clients, t0, end, args.seconds, t0 - run.T_START),
        "steps_in_window": steps,
        "breakdown": result.get("breakdown"),
        "program": span_table(program, tr.window),
        "idle_gaps": label_gaps(tr, program, 10),
        "launch_to_module_ms": {
            "n": len(skew),
            **({"min": 1e3 * min(skew)} if skew else {}),
            **{f"p{q}": 1e3 * percentile(skew, q) for q in (5, 50, 95) if skew},
        },
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
