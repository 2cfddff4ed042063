"""Roofline terms from a compiled dry-run artifact.

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically on XLA:CPU), which silently drops ~n_layers× of the work for
scan-over-layers models. We therefore walk the post-optimization HLO text
ourselves:

* computations are parsed into blocks; call multiplicity is propagated
  from ENTRY through ``while`` bodies (trip count recovered from the loop
  condition's comparison constant), ``fusion``/``call``/``to_apply``
  edges;
* FLOPs: ``dot`` = 2 * prod(out) * prod(contracting dims) (batch dims
  included in out), ``convolution`` ~ 2 * prod(out) * prod(kernel
  spatial), plus 1 FLOP/element for top-level elementwise ops;
* HBM bytes: per *top-level* op (fusion internals excluded — a fusion is
  XLA's unit of HBM materialization): output bytes + shaped operand
  bytes;
* collective bytes: output bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute ops, trip-scaled.
"""

from __future__ import annotations

import dataclasses
import re

from . import hw

__all__ = [
    "Computation",
    "HloCost",
    "Op",
    "RooflineTerms",
    "analyze_hlo",
    "call_multipliers",
    "callees",
    "parse_computations",
    "roofline_terms",
    "static_memory_seconds",
    "top_contributors",
    "trip_count",
]

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "power", "compare",
    "select", "and", "or", "xor", "negate", "abs", "floor", "sign",
}
_BYTE_FREE = {
    "bitcast", "tuple", "get-tuple-element", "parameter", "constant",
    "after-all", "partition-id", "replica-id",
}


def _shape_dims(s: str) -> tuple[str, list[int]] | None:
    m = _SHAPE_RE.match(s)
    if not m:
        return None
    dtype, dims = m.group(1), m.group(2)
    sizes = [int(d) for d in dims.split(",") if d] if dims else []
    return dtype, sizes


def _all_shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Op:
    """One HLO instruction: ``%name = <result_type> kind(operands), ...``."""

    name: str
    kind: str
    line: str
    result_type: str  # text before the op kind
    operands: tuple[str, ...] = ()  # referenced value names


@dataclasses.dataclass
class Computation:
    """One parsed HLO computation block (ENTRY is also under ``__entry__``)."""

    name: str
    ops: list
    types: dict = dataclasses.field(default_factory=dict)  # value -> type str


def parse_computations(hlo: str) -> dict[str, Computation]:
    """Parse post-optimization HLO text into named computation blocks.

    The ENTRY computation is additionally keyed ``"__entry__"``.
    """
    comps: dict[str, Computation] = {}
    current: Computation | None = None
    for raw in hlo.splitlines():
        line = raw.strip()
        if not line:
            continue
        header = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{$", line)
        if header and ("->" in line or line.startswith("ENTRY")):
            current = Computation(header.group(1), [])
            comps[current.name] = current
            if line.startswith("ENTRY"):
                comps["__entry__"] = current
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        m = re.match(r"(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(2)
        # Result type: scalar/array "bf16[...]{layout}" or a tuple type
        # "(s32[], f32[...], /*index=5*/ ...)" (comments may contain '=').
        km = re.match(
            r"((?:\((?:[^()]|\([^()]*\))*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+)?([\w\-]+)\(",
            rhs,
        )
        if not km:
            continue
        result_type = (km.group(1) or "").strip()
        kind = km.group(2)
        # Operand names: %refs inside the first (...) argument list.
        args = rhs.split(kind + "(", 1)[1]
        depth, end = 1, 0
        for j, ch in enumerate(args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = j
                    break
        operands = tuple(re.findall(r"%([\w.\-]+)", args[:end]))
        op = Op(m.group(1), kind, line, result_type, operands)
        current.ops.append(op)
        current.types[op.name] = result_type
    return comps


def callees(op: Op) -> dict[str, str]:
    """callee name -> edge kind ('fusion'|'control'|'call')."""
    out = {}
    for key, val in re.findall(r"(calls|to_apply|body|condition)=%?([\w.\-]+)", op.line):
        if key == "calls" and op.kind == "fusion":
            out[val] = "fusion"
        elif key in ("body", "condition"):
            out[val] = key
        else:
            out[val] = "call"
    return out


def trip_count(comps: dict, while_op: Op, cond_name: str | None) -> int:
    """Loop trip count: backend_config known_trip_count when present,
    else the loop bound from the condition's compare constant(s)."""
    tm = re.search(r'known_trip_count[^0-9]*(\d+)', while_op.line)
    if tm:
        return int(tm.group(1))
    if cond_name is None:
        return 1
    seen, stack, consts = set(), [cond_name], []
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for op in comps[name].ops:
            cm = re.search(r"[su]32\[\]\s+constant\((\d+)\)", op.line)
            if cm:
                consts.append(int(cm.group(1)))
            for callee in callees(op):
                stack.append(callee)
    return max(consts) if consts else 1


def _operand_dims(comp: Computation, op: Op, idx: int) -> list[int] | None:
    if idx >= len(op.operands):
        return None
    t = comp.types.get(op.operands[idx])
    if t is None:
        return None
    sh = _shape_dims(t)
    return sh[1] if sh else None


def _dot_flops(comp: Computation, op: Op) -> float:
    out = _shape_dims(op.result_type)
    if out is None:
        return 0.0
    _, out_dims = out
    lhs_dims = _operand_dims(comp, op, 0)
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    if lhs_dims is None or cm is None:
        return 0.0
    contract = 1
    for idx in cm.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            contract *= lhs_dims[int(idx)]
    n_out = 1
    for d in out_dims:
        n_out *= d
    return 2.0 * n_out * contract


def _conv_flops(comp: Computation, op: Op) -> float:
    out = _shape_dims(op.result_type)
    if out is None:
        return 0.0
    _, out_dims = out
    kernel_dims = _operand_dims(comp, op, 1)
    if kernel_dims is None:
        return 0.0
    n_out = 1
    for d in out_dims:
        n_out *= d
    kernel = 1
    for d in kernel_dims:
        kernel *= d
    # kernel dims include in/groups and out channels; flops per output
    # element ~ 2 * prod(kernel)/out_channels.
    out_ch = kernel_dims[-1] if kernel_dims else 1
    return 2.0 * n_out * kernel / max(out_ch, 1)


def _op_bytes(comp: Computation, op: Op) -> float:
    """HBM traffic of a top-level op: output write + operand reads.

    Special cases:
    * dynamic-update-slice (op or fusion root): the big buffer is aliased
      in place — traffic is the updated slice (2x: read-modify-write at
      slice granularity), not the whole tensor;
    * ``convert``-rooted fusions: XLA:CPU materializes bf16->f32 weight
      conversions because the CPU backend lacks native bf16 matmul — on
      the TPU target the MXU consumes bf16 directly, so these are
      excluded from the (TPU) roofline.
    """
    root = op.name
    if op.kind in ("while", "conditional"):
        return 0.0  # carried buffers alias; bodies account for the work
    if op.kind == "convert" or (
        op.kind == "fusion" and re.match(r"(wrapped_)?convert", root)
    ):
        return 0.0
    operand_bytes = []
    for name in op.operands:
        t = comp.types.get(name)
        if t:
            operand_bytes.append(float(_all_shape_bytes(t)))
    out_bytes = float(_all_shape_bytes(op.result_type))
    if op.kind == "dynamic-update-slice" or (
        op.kind == "fusion" and "dynamic-update-slice" in root
    ):
        # In-place slice update: traffic = the small operands (the slice
        # + indices), read-modify-write. Aliased full buffers (possibly
        # several) don't move.
        big = max(operand_bytes, default=0.0)
        small = sum(b for b in operand_bytes if b < 0.5 * big)
        return 2.0 * small
    return out_bytes + sum(operand_bytes)


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)


def call_multipliers(
    comps: dict[str, Computation],
) -> tuple[dict[str, float], dict[str, bool]]:
    """Call multiplicity and fusion-internality per computation.

    Returns ``(mult, fused)``: ``mult[name]`` is the number of times the
    computation executes per ENTRY invocation (trip-scaled across
    ``while`` bodies); ``fused[name]`` is True when *every* call site is
    fusion-internal (the computation never materializes HBM traffic of
    its own). Propagated in topological order (Kahn) — a BFS can visit
    a computation before all of its callers' multipliers have
    accumulated. Shared by :func:`analyze_hlo` and
    :func:`top_contributors` (and ``scripts/hlo_top.py``).
    """
    from collections import deque

    if "__entry__" not in comps:
        return {}, {}
    entry = comps["__entry__"].name
    names = [n for n in comps if n != "__entry__"]

    # (callee, factor, fusion_edge) per caller computation.
    comp_edges: dict[str, list[tuple[str, float, bool]]] = {n: [] for n in names}
    in_deg: dict[str, int] = {n: 0 for n in names}
    for name in names:
        for op in comps[name].ops:
            edges = callees(op)
            trip = None
            if op.kind == "while":
                cond = next((c for c, k in edges.items() if k == "condition"), None)
                trip = trip_count(comps, op, cond)
            for callee, kind in edges.items():
                if callee not in in_deg:
                    continue
                if kind == "condition":
                    factor, fus = float((trip or 1) + 1), True
                elif kind == "body":
                    factor, fus = float(trip or 1), False
                elif kind == "fusion":
                    factor, fus = 1.0, True
                else:
                    factor, fus = 1.0, False
                comp_edges[name].append((callee, factor, fus))
                in_deg[callee] += 1

    mult: dict[str, float] = {n: 0.0 for n in names}
    fused: dict[str, bool | None] = {n: None for n in names}
    mult[entry] = 1.0
    fused[entry] = False
    q = deque([n for n in names if in_deg[n] == 0])
    while q:
        name = q.popleft()
        in_fusion = bool(fused[name])
        for callee, factor, fus_edge in comp_edges[name]:
            mult[callee] += mult[name] * factor
            child_fused = in_fusion or fus_edge
            # bytes-free only if EVERY call site is fusion-internal
            fused[callee] = (
                child_fused if fused[callee] is None else (fused[callee] and child_fused)
            )
            in_deg[callee] -= 1
            if in_deg[callee] == 0:
                q.append(callee)
    return mult, {n: bool(v) for n, v in fused.items()}


def analyze_hlo(hlo: str) -> HloCost:
    comps = parse_computations(hlo)
    if "__entry__" not in comps:
        return HloCost()
    mult, fused = call_multipliers(comps)

    cost = HloCost(collectives={k: {"count": 0, "bytes": 0.0} for k in _COLLECTIVES})
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        for op in comp.ops:
            if op.kind == "dot":
                cost.flops += m * _dot_flops(comp, op)
            elif op.kind == "convolution":
                cost.flops += m * _conv_flops(comp, op)
            elif op.kind in _ELEMENTWISE:
                sh = _shape_dims(op.result_type)
                if sh:
                    n = 1
                    for d in sh[1]:
                        n *= d
                    cost.flops += m * n
            # HBM bytes: top-level ops only (fusions are the HBM unit).
            if not fused.get(name, False) and op.kind not in _BYTE_FREE:
                cost.bytes += m * _op_bytes(comp, op)
            # Collectives
            base = op.kind
            if base.endswith("-start"):
                base = base[: -len("-start")]
            if base in _COLLECTIVES and not op.kind.endswith("-done"):
                b = _all_shape_bytes(op.result_type)
                cost.collective_bytes += m * b
                cost.collectives[base]["count"] += m
                cost.collectives[base]["bytes"] += m * b
    return cost


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    flops: float  # total FLOPs (all devices)
    hbm_bytes: float  # total bytes accessed
    collective_bytes: float  # total collective payload bytes
    chips: int
    device_kind: str  # peaks key (``jax.Device.device_kind``)

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * hw.peaks(self.device_kind).flops_bf16)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * hw.peaks(self.device_kind).hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * hw.peaks(self.device_kind).ici_link_bw)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-optimistic step time (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_time_s": self.step_time_s,
        }


def roofline_terms(
    hlo_text: str, chips: int, device_kind: str
) -> tuple[RooflineTerms, HloCost]:
    """Trip-scaled terms from the post-SPMD HLO (per-device program);
    totals scale by ``chips``, the per-chip time terms divide them out
    against the peaks of ``device_kind``."""
    cost = analyze_hlo(hlo_text)
    terms = RooflineTerms(
        flops=cost.flops * chips,
        hbm_bytes=cost.bytes * chips,
        collective_bytes=cost.collective_bytes * chips,
        chips=chips,
        device_kind=device_kind,
    )
    return terms, cost


def static_memory_seconds(
    required_bytes: float, chips: int, device_kind: str
) -> float:
    """Attainable-bandwidth floor on step time from *statically* required
    bytes — the jaxpr-level memory pass (``repro.analysis.memory``) feeds
    its per-entry transfer bytes through here, so the roofline's memory
    term is available before anything compiles, not just from
    post-optimization HLO."""
    return required_bytes / (chips * hw.peaks(device_kind).hbm_bw)


def top_contributors(
    hlo: str, mode: str = "bytes", limit: int | None = None
) -> list[tuple[float, str, str]]:
    """Trip-scaled per-op contributors, largest first.

    ``mode``: ``"bytes"`` (HBM traffic of top-level ops), ``"flops"``
    (dot/convolution FLOPs), or ``"coll"`` (collective payload bytes).
    Returns ``(value, op_kind, hlo_line)`` tuples — the drill-down view
    behind ``scripts/hlo_top.py``, sharing :func:`call_multipliers` with
    :func:`analyze_hlo` so both always agree on loop trip scaling.
    """
    if mode not in ("bytes", "flops", "coll"):
        raise ValueError(f"unknown mode {mode!r} (expected bytes|flops|coll)")
    comps = parse_computations(hlo)
    if "__entry__" not in comps:
        return []
    mult, fused = call_multipliers(comps)
    contrib: list[tuple[float, str, str]] = []
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        for op in comp.ops:
            if mode == "flops":
                if op.kind == "dot":
                    v = m * _dot_flops(comp, op)
                elif op.kind == "convolution":
                    v = m * _conv_flops(comp, op)
                else:
                    continue
            elif mode == "coll":
                base = op.kind[:-6] if op.kind.endswith("-start") else op.kind
                if base not in _COLLECTIVES or op.kind.endswith("-done"):
                    continue
                v = m * _all_shape_bytes(op.result_type)
            else:
                if fused.get(name, False) or op.kind in _BYTE_FREE:
                    continue
                v = m * _op_bytes(comp, op)
            if v > 0:
                contrib.append((v, op.kind, op.line))
    contrib.sort(key=lambda t: -t[0])
    return contrib[:limit] if limit is not None else contrib


# Back-compat aliases for the pre-public-API names.
_parse_computations = parse_computations
_callees = callees
_trip_count = trip_count
