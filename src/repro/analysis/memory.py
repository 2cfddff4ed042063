"""Static memory-flow pass: byte costs, liveness peaks, donation lint.

The paper's binding constraint is memory on energy-starved edge
devices, and the roofline's "fast as the hardware allows" claim needs
*bytes moved per step* as a first-class, statically-enforced quantity.
This module adds that third axis to the PR-6 analysis subsystem (which
counts primitives and host syncs but is blind to memory):

* :func:`transfer_bytes` — a per-equation byte-cost model over jaxprs.
  Every equation charges operand-read + result-write bytes from its
  avals; ``scan``/``while`` bodies are weighted by their trip counts
  (``while`` trips recovered from the loop condition's literal bound,
  the jaxpr-level analog of the roofline's HLO
  :func:`~repro.roofline.analysis.call_multipliers` machinery);
  ``cond`` charges its widest branch; ``pjit``/custom-vjp descend x1;
  ``pallas_call`` kernels are accounted at their *block-spec DMA
  granularity* — ``prod(grid) * block_bytes`` per operand/output, which
  is exactly what the TPU memory system moves (an int8 page pool
  therefore shows ~1/4 the fp32 DMA bytes with no further modeling).
  Index-driven ops (``gather``/``scatter``/``dynamic_update_slice``)
  charge the rows actually touched, not the whole buffer — the XLA
  in-place/gather semantics the roofline HLO walker also assumes.

* :func:`peak_live_bytes` — a liveness-based peak-residency estimate:
  a backward last-use sweep over the equations, then a forward walk of
  the live set (inputs live from entry, values die at last use,
  jaxpr outputs live to the end). Call-like equations add their
  sub-jaxpr's *internal* peak (boundary values are the caller's
  operands/results and counted once, at the call site). Donated input
  indices are excluded from the peak — their buffers alias outputs.

* :func:`entry_memory` — both of the above for one lint
  :class:`~.entry_points.EntryPoint`, normalized to ``bytes_per_token``
  via the entry's ``tokens`` metadata, plus the static roofline term
  (:func:`repro.roofline.analysis.static_memory_seconds`).

* :func:`analyze_dispatch` / :func:`run_donation_gate` — the
  donation/aliasing lint over the engine's *real* jitted dispatch
  signatures: any large (>= ``donation.min_bytes``) input that is
  consumed-and-rebuilt (an output with the identical aval exists) must
  be donated. Donation intent is read from the lowered MLIR
  (``tf.aliasing_output`` arg attributes) and cross-checked against
  ``compiled.memory_analysis()`` aliased bytes and the compiled HLO's
  ``input_output_alias`` table — the same artifacts
  :mod:`repro.launch.dryrun` records one-off, now shared via
  :func:`memory_report`.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Iterable

from jax.extend.core import Literal

from .rules import Finding
from .walker import subjaxprs

__all__ = [
    "MemoryStats",
    "DispatchReport",
    "aval_bytes",
    "eqn_bytes",
    "pallas_dma_bytes",
    "while_trip_count",
    "transfer_bytes",
    "io_bytes",
    "peak_live_bytes",
    "entry_memory",
    "memory_report",
    "analyze_dispatch",
    "engine_dispatches",
    "run_donation_gate",
    "memory_section",
    "update_memory_budgets",
]


# ---------------------------------------------------------------------------
# Per-equation byte cost model
# ---------------------------------------------------------------------------

def aval_bytes(aval) -> int:
    """Bytes of one abstract value (0 for tokens/opaque avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * dtype.itemsize if shape else dtype.itemsize


def _invar_bytes(eqn) -> int:
    return sum(
        aval_bytes(v.aval) for v in eqn.invars if not isinstance(v, Literal)
    )


def _outvar_bytes(eqn) -> int:
    return sum(aval_bytes(v.aval) for v in eqn.outvars)


# Ops whose big operand is addressed by index: traffic is the rows
# actually touched (the result / the updates), never the whole buffer.
_GATHER_LIKE = ("gather", "take", "dynamic_slice")
_SCATTER_LIKE = (
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max", "dynamic_update_slice",
)


def eqn_bytes(eqn) -> int:
    """Memory traffic one equation moves, from its operand/result avals.

    * gather/dynamic_slice: read the gathered rows + indices, write the
      result — ``2 * result + indices`` (the source buffer is only
      touched at row granularity);
    * scatter/dynamic_update_slice: read-modify-write at update
      granularity — ``2 * (updates + indices)``; the big operand is
      updated in place (XLA aliases it), so the result is free;
    * everything else: operand reads + result writes.
    """
    name = eqn.primitive.name
    if name in _GATHER_LIKE:
        idx = sum(
            aval_bytes(v.aval)
            for v in eqn.invars[1:]
            if not isinstance(v, Literal)
        )
        return 2 * _outvar_bytes(eqn) + idx
    if name in _SCATTER_LIKE:
        small = sum(
            aval_bytes(v.aval)
            for v in eqn.invars[1:]
            if not isinstance(v, Literal)
        )
        return 2 * small
    return _invar_bytes(eqn) + _outvar_bytes(eqn)


def pallas_dma_bytes(eqn) -> int:
    """DMA traffic of one ``pallas_call``: block-spec granularity.

    Every grid cell DMAs one block per (non-scalar-prefetch) operand and
    per output — ``prod(grid) * prod(block_shape) * itemsize`` each.
    Scalar-prefetch operands (block tables, lengths) are read once, in
    full. The kernel body's VMEM arithmetic moves no HBM bytes, so this
    is the whole memory cost of the kernel — and it is exactly where an
    int8 page pool shows its ~4x byte reduction over fp32 pages.
    """
    gm = eqn.params["grid_mapping"]
    grid = 1
    for d in gm.grid:
        grid *= int(d)
    per_cell = 0
    for bm in gm.block_mappings:
        block = 1
        for d in bm.block_shape:
            if isinstance(d, int):
                block *= d
        per_cell += block * bm.array_aval.dtype.itemsize
    n_prefetch = gm.num_index_operands
    prefetch = sum(
        aval_bytes(v.aval)
        for v in eqn.invars[:n_prefetch]
        if not isinstance(v, Literal)
    )
    return grid * per_cell + prefetch


def while_trip_count(eqn) -> int:
    """Trip count of a ``while`` equation, recovered from the literal
    bound in its condition jaxpr (the jaxpr-level analog of the roofline
    HLO walker's :func:`~repro.roofline.analysis.trip_count`). Falls
    back to 1 when the condition carries no literal comparison."""
    cond = eqn.params["cond_jaxpr"].jaxpr
    bounds = []
    for ceqn in cond.eqns:
        if ceqn.primitive.name in ("lt", "le", "gt", "ge"):
            for v in ceqn.invars:
                if isinstance(v, Literal) and isinstance(v.val, (int,)):
                    bounds.append(int(v.val))
    return max(bounds) if bounds else 1


def _as_jaxpr(obj):
    inner = getattr(obj, "jaxpr", None)
    return _as_jaxpr(inner) if inner is not None else obj


def transfer_bytes(jaxpr) -> int:
    """Trip-weighted bytes the jaxpr tree moves per invocation."""
    jaxpr = _as_jaxpr(jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            trip = int(eqn.params.get("length", 1))
            total += trip * transfer_bytes(eqn.params["jaxpr"])
        elif name == "while":
            trip = while_trip_count(eqn)
            total += trip * transfer_bytes(eqn.params["body_jaxpr"])
            total += (trip + 1) * transfer_bytes(eqn.params["cond_jaxpr"])
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            total += max(
                (transfer_bytes(b) for b in branches), default=0
            ) + _invar_bytes(eqn) - sum(
                aval_bytes(v.aval)
                for v in eqn.invars[1:]
                if not isinstance(v, Literal)
            )
        elif name == "pallas_call":
            total += pallas_dma_bytes(eqn)
        else:
            subs = list(subjaxprs(eqn))
            if subs:
                # pjit / custom-vjp / remat: descend x1, no call-site cost
                # (the sub-jaxpr's own equations charge the traffic).
                total += sum(transfer_bytes(s) for s in subs)
            else:
                total += eqn_bytes(eqn)
    return total


def io_bytes(jaxpr) -> tuple[int, int]:
    """(input_bytes, output_bytes) of a (closed) jaxpr's boundary."""
    jaxpr = _as_jaxpr(jaxpr)
    ins = sum(aval_bytes(v.aval) for v in jaxpr.invars)
    ins += sum(aval_bytes(v.aval) for v in jaxpr.constvars)
    outs = sum(aval_bytes(v.aval) for v in jaxpr.outvars)
    return ins, outs


# ---------------------------------------------------------------------------
# Liveness: peak resident bytes
# ---------------------------------------------------------------------------

def peak_live_bytes(jaxpr, donated: Iterable[int] = ()) -> int:
    """Liveness-based peak-resident-bytes estimate.

    Backward sweep records each variable's last use; the forward walk
    then grows the live set at every definition and shrinks it at last
    use. Inputs are live from entry; jaxpr outputs stay live to the
    end; ``donated`` input *indices* contribute nothing (their buffers
    alias outputs). Call-like equations (scan/while/cond/pjit) add
    their sub-jaxpr's internal peak on top of the caller's live set —
    boundary values are the caller's operands/results, counted once.
    """
    return _sweep(_as_jaxpr(jaxpr), boundary=True, donated=frozenset(donated))


def _sweep(jaxpr, *, boundary: bool, donated: frozenset[int]) -> int:
    jaxpr = _as_jaxpr(jaxpr)
    n = len(jaxpr.eqns)
    out_set = {id(v) for v in jaxpr.outvars if not isinstance(v, Literal)}

    # Backward: last equation index using each var (outputs live to end).
    last_use: dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[id(v)] = i
    for vid in out_set:
        last_use[vid] = n

    live: dict[int, int] = {}

    def _add(v, nbytes):
        live[id(v)] = nbytes

    for i, v in enumerate(jaxpr.constvars):
        _add(v, aval_bytes(v.aval) if boundary else 0)
    for i, v in enumerate(jaxpr.invars):
        keep = boundary and i not in donated
        _add(v, aval_bytes(v.aval) if keep else 0)
    # Inputs never read still occupy memory until the call returns; give
    # them last_use = n so they are not dropped mid-walk.
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        last_use.setdefault(id(v), n)

    peak = sum(live.values())
    for i, eqn in enumerate(jaxpr.eqns):
        internal = 0
        if eqn.primitive.name != "pallas_call":
            for sub in subjaxprs(eqn):
                internal = max(
                    internal, _sweep(sub, boundary=False, donated=frozenset())
                )
        for v in eqn.outvars:
            nb = aval_bytes(v.aval)
            if not boundary and id(v) in out_set:
                nb = 0  # caller accounts for the call's results
            _add(v, nb)
            last_use.setdefault(id(v), i)
        peak = max(peak, sum(live.values()) + internal)
        for v in list(eqn.invars) + list(eqn.outvars):
            if not isinstance(v, Literal) and last_use.get(id(v), n) == i:
                live.pop(id(v), None)
    return peak


# ---------------------------------------------------------------------------
# Per-entry-point stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MemoryStats:
    """Static memory profile of one lint entry point."""

    entry_point: str
    tokens_per_call: int
    input_bytes: int
    output_bytes: int
    transfer_bytes: int
    bytes_per_token: int
    peak_live_bytes: int
    kv_pool_bytes: int | None
    roofline_memory_s: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def entry_memory(entry) -> MemoryStats:
    """Compute (and cache on the entry) one entry point's MemoryStats."""
    cached = getattr(entry, "_memory", None)
    if cached is not None:
        return cached
    from ..roofline import hw
    from ..roofline.analysis import static_memory_seconds

    jaxpr = entry.jaxpr
    ins, outs = io_bytes(jaxpr)
    moved = transfer_bytes(jaxpr)
    tokens = max(int(getattr(entry, "tokens", 1)), 1)
    stats = MemoryStats(
        entry_point=entry.name,
        tokens_per_call=tokens,
        input_bytes=ins,
        output_bytes=outs,
        transfer_bytes=moved,
        bytes_per_token=-(-moved // tokens),
        peak_live_bytes=peak_live_bytes(jaxpr),
        kv_pool_bytes=getattr(entry, "kv_pool_bytes", None),
        # The static pass runs before any device exists; its floor is the
        # serving chip's, one v5e.
        roofline_memory_s=static_memory_seconds(float(moved), 1, hw.V5E),
    )
    entry._memory = stats
    return stats


# ---------------------------------------------------------------------------
# Shared compiled-artifact byte accounting (used by launch/dryrun too)
# ---------------------------------------------------------------------------

def memory_report(compiled) -> dict:
    """``compiled.memory_analysis()`` as a plain dict — the one byte
    accounting shared by the donation gate, the CLI report, and
    ``repro.launch.dryrun``'s per-cell artifacts."""
    mem = compiled.memory_analysis()
    return {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }


# ---------------------------------------------------------------------------
# Donation / aliasing lint over the engine's jitted dispatches
# ---------------------------------------------------------------------------

_ALIAS_RE = re.compile(r"tf\.aliasing_output")
_MAIN_RE = re.compile(
    r"func\.func public @main\((?P<args>.*?)\)\s*->", re.S
)


def _donated_arg_indices(mlir_text: str) -> set[int]:
    """Flat input indices carrying ``tf.aliasing_output`` in the lowered
    MLIR main signature (jit flattens arguments in pytree order, so MLIR
    arg N is flat input N)."""
    m = _MAIN_RE.search(mlir_text)
    if not m:
        return set()
    donated: set[int] = set()
    # Split on "%argN:" boundaries; attributes for argN trail its type.
    parts = re.split(r"%arg(\d+):", m.group("args"))
    # parts = ["", "0", "<type+attrs>", "1", ...]
    for idx_str, body in zip(parts[1::2], parts[2::2]):
        if _ALIAS_RE.search(body):
            donated.add(int(idx_str))
    return donated


@dataclasses.dataclass(frozen=True)
class DispatchReport:
    """Donation/aliasing verdict for one real engine dispatch."""

    name: str
    inputs: int
    large_rebuilt: int  # inputs >= min_bytes with an identically-shaped output
    donated: int  # of those, how many are donated (tf.aliasing_output)
    aliased_bytes: int | None  # compiled.memory_analysis() cross-check
    memory: dict

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def analyze_dispatch(
    name: str,
    fn,
    args: tuple,
    *,
    min_bytes: int,
    compile_check: bool = True,
) -> tuple[DispatchReport, list[Finding]]:
    """Lint one jitted dispatch: every large consumed-and-rebuilt input
    must be donated. ``fn`` is the engine's real jitted callable; args
    may mix concrete arrays and ShapeDtypeStructs."""
    import jax

    lowered = fn.lower(*args)
    donated = _donated_arg_indices(lowered.as_text())
    flat_in = jax.tree_util.tree_leaves(args)
    out = jax.eval_shape(fn, *args)
    out_avals = [
        (tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(out)
    ]

    findings: list[Finding] = []
    large_rebuilt: list[int] = []
    out_pool = list(out_avals)
    for i, leaf in enumerate(flat_in):
        nbytes = math.prod(tuple(leaf.shape)) * leaf.dtype.itemsize
        key = (tuple(leaf.shape), str(leaf.dtype))
        if nbytes < min_bytes or key not in out_pool:
            continue
        out_pool.remove(key)  # each output absorbs at most one input
        large_rebuilt.append(i)
        if i not in donated:
            findings.append(
                Finding(
                    "donation",
                    name,
                    f"input #{i} {key[1]}{list(key[0])} ({nbytes} bytes) is "
                    "consumed-and-rebuilt without donate_argnums — every "
                    "dispatch pays a full copy of a cache-sized buffer",
                    measured=nbytes,
                    budget=min_bytes,
                )
            )

    aliased = None
    mem: dict = {}
    if compile_check:
        compiled = lowered.compile()
        mem = memory_report(compiled)
        aliased = mem.get("alias_bytes")
        donated_bytes = sum(
            math.prod(tuple(flat_in[i].shape)) * flat_in[i].dtype.itemsize
            for i in large_rebuilt
            if i in donated
        )
        if donated_bytes and aliased is not None and aliased < donated_bytes:
            findings.append(
                Finding(
                    "donation",
                    name,
                    "donation declared but not honored by the compiler "
                    "(aliased bytes below the donated input bytes)",
                    measured=int(aliased),
                    budget=donated_bytes,
                )
            )
    report = DispatchReport(
        name=name,
        inputs=len(flat_in),
        large_rebuilt=len(large_rebuilt),
        donated=sum(1 for i in large_rebuilt if i in donated),
        aliased_bytes=aliased,
        memory=mem,
    )
    return report, findings


def engine_dispatches(paged: bool):
    """The engine's real jitted stage dispatches with faithful abstract
    argument signatures, from a smoke server (stage 0; the cache/pool
    signature — what donation is about — is identical across stages)."""
    import jax
    import jax.numpy as jnp

    from .recompile import _smoke_server

    cfg, server = _smoke_server(paged)
    g = 0
    ex = server._exec[g]
    _, params_g = server.stages[g]
    cache = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), server._caches[(g, 0)]
    )
    W, C = server.max_batch, server.prefill_chunk
    kind = "paged" if paged else "dense"
    out = []
    if paged:
        nb = -(-server.max_len // server.page_size)
        tok = jax.ShapeDtypeStruct((W, 1), jnp.int32)
        lens = jax.ShapeDtypeStruct((W,), jnp.int32)
        bt = jax.ShapeDtypeStruct((W, nb), jnp.int32)
        chunk_tok = jax.ShapeDtypeStruct((W, C), jnp.int32)
        offs = jax.ShapeDtypeStruct((W,), jnp.int32)
        valids = jax.ShapeDtypeStruct((W,), jnp.int32)
        out.append(
            (f"engine:{kind}:decode", ex.decode_fn,
             (params_g, tok, cache, lens, bt))
        )
        out.append(
            (f"engine:{kind}:chunk", ex.chunk_pages,
             (params_g, chunk_tok, cache, offs, valids, bt))
        )
        page_ids = jax.ShapeDtypeStruct((2, 2), jnp.int32)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 16), jnp.int32)}
        out.append(
            (f"engine:{kind}:prefill", ex.prefill_pages,
             (params_g, batch, cache, page_ids))
        )
    else:
        tok = jax.ShapeDtypeStruct((W, 1, 1), jnp.int32)
        mask = jax.ShapeDtypeStruct((W,), jnp.bool_)
        chunk_tok = {"tokens": jax.ShapeDtypeStruct((W, 1, C), jnp.int32)}
        offs = jax.ShapeDtypeStruct((W,), jnp.int32)
        valids = jax.ShapeDtypeStruct((W,), jnp.int32)
        out.append(
            (f"engine:{kind}:decode", ex.decode_masked,
             (params_g, tok, cache, mask))
        )
        out.append(
            (f"engine:{kind}:chunk", ex.chunk_masked,
             (params_g, chunk_tok, cache, offs, valids, mask))
        )
        batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 16), jnp.int32)}
        slots = jax.ShapeDtypeStruct((2,), jnp.int32)
        out.append(
            (f"engine:{kind}:prefill", ex.prefill_into,
             (params_g, batch, cache, slots))
        )
    return out


def run_donation_gate(budgets: dict) -> tuple[list[dict], list[Finding]]:
    """Donation lint over every engine dispatch (dense + paged)."""
    section = budgets.get("donation", {})
    min_bytes = int(section.get("min_bytes", 16384))
    reports: list[dict] = []
    findings: list[Finding] = []
    for paged in (False, True):
        for name, fn, args in engine_dispatches(paged):
            report, found = analyze_dispatch(
                name, fn, args, min_bytes=min_bytes
            )
            reports.append(report.as_dict())
            findings.extend(found)
    return reports, findings


# ---------------------------------------------------------------------------
# CLI report section + budget regeneration
# ---------------------------------------------------------------------------

def memory_section(entries) -> dict:
    """The ``memory`` block of the CLI JSON report."""
    return {e.name: entry_memory(e).as_dict() for e in entries}


def update_memory_budgets(budgets: dict, entries) -> dict:
    """Regenerate the measured-exact ``memory_budgets`` section in place
    (``cli --update-budgets``; the budgets-drift test asserts the
    committed file matches this)."""
    section = {}
    for e in entries:
        stats = entry_memory(e)
        section[e.name] = {
            "bytes_per_token": stats.bytes_per_token,
            "peak_live_bytes": stats.peak_live_bytes,
        }
    budgets["memory_budgets"] = section
    return budgets
