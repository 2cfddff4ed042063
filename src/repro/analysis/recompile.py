"""Recompile-hazard gate + engine runtime smoke gates.

:func:`repro.serving.trace_counts` counts actual jit cache misses per
``(kind, stage, *shape)``. This module turns those observations into
*enforced budgets*: per ``kind`` a maximum number of distinct compiled
shapes per stage (``trace_budgets`` in ``budgets.json``). A code
change that reintroduces shape-dependent re-jitting — e.g. keying the
chunked-prefill dispatch on prompt length again — multiplies the
shapes per stage and fails the gate with a named rule and entry point.

Two engine smoke gates (both run by ``cli --check``; the compile gate
is also wired into the main-lane smoke benchmarks):

* :func:`run_recompile_gate` — drains a mixed-prompt-length workload
  through a chunked dense and a chunked paged server and applies the
  trace budgets; chunked runs must additionally contain *zero*
  whole-prompt prefill traces (their shape count scales with the
  workload's prompt lengths).
* :func:`run_host_sync_gate` — repeats the drain under a
  :class:`~.sanitizer.TransferSanitizer` and enforces the per-step
  device->host sync budget (``host_sync.per_step_budget``).

Serving imports stay function-local so ``repro.analysis`` never drags
the engine in at import time.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

from .budgets import resolve_budget
from .rules import Finding

__all__ = [
    "check_trace_budgets",
    "run_recompile_gate",
    "run_host_sync_gate",
]

# Trace kinds whose dispatch shape must not depend on the workload.
_CHUNKED_FORBIDDEN = ("prefill", "prefill_pages")


def shapes_per_stage(counts: dict) -> dict:
    """{(kind, stage): set of traced shapes} from a trace_counts dict."""
    out: dict = defaultdict(set)
    for key in counts:
        kind, stage, *shape = key
        out[(kind, stage)].add(tuple(shape))
    return dict(out)


def check_trace_budgets(
    counts: dict, budgets: dict, context: str = "engine"
) -> list[Finding]:
    """Apply ``trace_budgets`` to a ``trace_counts()`` snapshot."""
    section = budgets.get("trace_budgets", {})
    findings = []
    for (kind, stage), shapes in sorted(shapes_per_stage(counts).items()):
        limits = resolve_budget(section, kind)
        max_shapes = limits.get("max_shapes_per_stage")
        if max_shapes is not None and len(shapes) > max_shapes:
            sample = ", ".join(str(s) for s in sorted(shapes)[:4])
            findings.append(
                Finding(
                    "recompile-budget",
                    f"{context}:{kind}:stage{stage}",
                    f"{len(shapes)} distinct compiled shapes for one stage "
                    f"(shapes: {sample}) — shape-dependent re-jitting",
                    measured=len(shapes),
                    budget=max_shapes,
                )
            )
    return findings


# ---------------------------------------------------------------------------
# Engine smoke harness
# ---------------------------------------------------------------------------

# Mixed prompt lengths: enough distinct values that any length-keyed
# dispatch shows up as multiple compiled shapes immediately.
_PROMPT_LENS = (4, 6, 10, 14)


def _smoke_server(paged: bool, prefill_chunk: int | None = 4):
    from ..configs import get_smoke_config
    from ..models import build_model
    from ..models.common import init_from_template
    from ..serving import PipelineServer

    import jax

    cfg = dataclasses.replace(
        get_smoke_config("stablelm-1.6b"), dtype="float32", param_dtype="float32"
    )
    model = build_model(cfg)
    params = init_from_template(model.template, jax.random.PRNGKey(0), "float32")
    server = PipelineServer(
        model, params,
        n_groups=2, n_replicas=1, policy="uniform",
        harvest_bounds=(60.0, 80.0),  # energy-unconstrained smoke
        max_len=64, max_batch=4,
        paged=paged, page_size=8,
        prefill_chunk=prefill_chunk, seed=0,
    )
    return cfg, server


def _drain(server, cfg, n_requests: int = 6, n_tokens: int = 3) -> None:
    import numpy as np

    reqs = [
        server.submit(
            (np.arange(_PROMPT_LENS[i % len(_PROMPT_LENS)]) + i) % cfg.vocab_size,
            n_tokens=n_tokens,
        )
        for i in range(n_requests)
    ]
    steps = 0
    while not all(r.done for r in reqs):
        server.step()
        steps += 1
        if steps > 10_000:  # pragma: no cover
            raise RuntimeError("smoke drain did not converge")


def run_recompile_gate(budgets: dict) -> list[Finding]:
    """Chunked dense + paged smoke drains under the trace budgets."""
    from ..serving import reset_trace_counts, trace_counts

    findings: list[Finding] = []
    for paged in (False, True):
        context = "paged" if paged else "dense"
        reset_trace_counts()
        cfg, server = _smoke_server(paged)
        _drain(server, cfg)
        counts = trace_counts()
        findings.extend(check_trace_budgets(counts, budgets, context=context))
        for kind in _CHUNKED_FORBIDDEN:
            hits = {k: v for k, v in counts.items() if k[0] == kind}
            if hits:
                findings.append(
                    Finding(
                        "recompile-budget",
                        f"{context}:{kind}",
                        "whole-prompt prefill traced in a chunked run — "
                        "compile count scales with workload prompt lengths "
                        f"(traces: {sorted(hits)})",
                        measured=len(hits),
                        budget=0,
                    )
                )
    return findings


def run_host_sync_gate(budgets: dict) -> list[Finding]:
    """Warmed engine steps under the transfer sanitizer: per-step
    device->host syncs must stay within ``host_sync.per_step_budget``
    and every one must flow through the sanctioned choke point."""
    from .sanitizer import TransferSanitizer

    section = budgets.get("host_sync", {})
    per_step = section.get("per_step_budget", {})
    findings: list[Finding] = []
    for paged in (False, True):
        context = "paged" if paged else "dense"
        budget = int(per_step.get(context, 3))
        cfg, server = _smoke_server(paged)
        _drain(server, cfg)  # warmup: compile every dispatch shape first
        with TransferSanitizer() as san:
            _drain(server, cfg)
        if san.max_per_step > budget:
            findings.append(
                Finding(
                    "host-sync",
                    f"{context}:replica-step",
                    "device->host syncs per replica-step over budget",
                    measured=san.max_per_step,
                    budget=budget,
                )
            )
        if san.unsanctioned_total > 0:
            findings.append(
                Finding(
                    "host-sync",
                    f"{context}:replica-step",
                    f"{san.unsanctioned_total} device->host sync(s) bypassed "
                    "the sanctioned host_readback choke point",
                    measured=san.unsanctioned_total,
                    budget=0,
                )
            )
    return findings
