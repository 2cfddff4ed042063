"""Decentralized serving engine: the paper's system with real compute.

``PipelineServer`` hosts G pipeline groups × R replicas of a partitioned
model (:mod:`.partition`). Time advances in slots (the paper's delta);
per slot every replica harvests budget, resident requests execute real
JAX decode compute on their designated replicas, and the control plane
decides everything else. The engine is the *execution* third of a
three-way split:

* :mod:`.cache` — ``KVCacheManager``: slot + memory accounting, one
  abstraction over the dense slot-stacked layout (``DenseSlotCache``)
  and the paged pool (``PagedKVCache``). The engine and scheduler never
  branch on cache layout.
* :mod:`.scheduler` — ``StepScheduler``: admission (Alg. 1 routing),
  FIFO backpressure queueing, failover re-placement, youngest-resident
  preemption, and energy gating — one implementation for both layouts.
* this module — building the jitted stage entry points, assembling
  batched inputs, issuing the calls, and committing their results.

Continuous batching
-------------------
Each (group, replica) owns one static-shaped batched KV cache with
``max_batch`` per-request slots. Per simulation slot a replica issues
**one** batched stage call covering every resident request at that stage
— a masked decode over the full slot width plus the prefill work of any
joining requests — and charges ``CE(PM)/kappa`` per slot per call (the
paper's device-level job cost amortized over the batch). Call results
are committed when the call completes, so an aborted call (replica
death mid-call) never corrupts request state.

Chunked prefill (``prefill_chunk=N``)
-------------------------------------
Whole-prompt prefill issues one vmapped dispatch *per distinct prompt
length*, so realistic mixed traffic re-jits continuously and long
prompts head-of-line block resident decodes. With ``prefill_chunk``
set, each joining prompt is split into fixed ``N``-token chunks that
ride one static call shape — prefill chunks and decode tokens are
co-scheduled in the same per-step call, per-slot offsets advancing
through the chunk — so the number of compiled prefill computations is
independent of the workload's prompt lengths (observable via
:func:`trace_counts`) and per-step prefill work is bounded by ``N``.
Uniform full-attention architectures only (the ``supports_paged``
coverage); paged mode writes each chunk's K/V into the request's
reserved pages incrementally.

Paged KV cache (``paged=True``)
-------------------------------
The dense layout reserves ``max_batch x max_len`` KV entries per
replica — worst-case memory for every slot. In paged mode each replica
instead owns a shared pool of fixed-size pages: a request holds
``ceil(context/page_size)`` pages per group named by its block table,
``decode_paged`` reads the scattered cache directly, the router weighs
replicas by free pages, and page exhaustion preempts the youngest
resident back to the queue (loss-free: prompt + generated re-prefill).

Quantized KV pages (``kv_dtype="int8"``)
----------------------------------------
Pages default to the model's compute dtype; ``kv_dtype="int8"`` stores
int8 entries with one fp32 amax scale per page row, quantized at
scatter time (decode, chunked and whole prefill write bit-identical
pages) and dequantized inside the page gather — Pallas kernels and the
XLA fallback alike. KV bytes per token drop 4x (fp32 compute) / 2x
(bf16), so the same pool admits proportionally more residents
(``benchmarks/quant_kv_bench.py``; accuracy swept in
``tests/test_quant_kv.py``).

Mesh-sharded execution (``mesh=...``)
-------------------------------------
With a ``(data, model)`` serving mesh (``launch.mesh.make_serving_mesh``)
each replica owns a tensor-parallel **submesh**: the mesh's data axis is
carved into per-replica device slices
(:func:`repro.distributed.sharding.replica_submeshes`, round-robin when
replicas outnumber slices) and every stage's params are placed once per
slice under ``SERVE_RULES`` NamedShardings — TP over ``model``,
replicated over ``data`` — so one jitted dispatch per replica step
lowers to collectives over the slice's devices, with no per-device
Python loop. KV caches and paged pools are committed to the owning
replica's submesh (sharded only on ``cache_batch``, which is the data
axis — i.e. fully replicated *within* a tensor-parallel slice), so a
replica's cache never straddles replica boundaries and the Router
routes over real disjoint device sets. Stage handoffs between replicas
on different slices are placed onto the consuming replica's submesh at
assembly time — a device-to-device transfer, dispatched inside the
async ring's dispatch phase (no host sync: d2h stays commit-only under
the sanitizer contract). Token streams are bit-for-bit identical to the
single-device engine (``tests/test_mesh_serving.py``,
``benchmarks/mesh_bench.py``).

Async engine core (``async_depth=K``)
-------------------------------------
The step loop is split into a **producer** (scheduler decisions + call
assembly + jitted dispatch) and a **consumer** (the committer: batched
argmax readback through ``host_readback``, slot/page release, failover
re-queue). A dispatch no longer blocks on its own results: each
``_StageCall`` carries *deferred readbacks* — the device argmax arrays
plus finalizer closures — and the host sync happens only when the call
is committed from the per-replica completion queue, never at dispatch.
Each (group, replica) owns an in-flight ring of up to ``async_depth``
calls, so a replica dispatches its next call (over members not already
in flight) while previous ones are still executing; JAX async dispatch
overlaps the device work with all host-side scheduling in between.

* ``async_depth=0`` — legacy synchronous engine: ring depth 1 and the
  readback happens eagerly at dispatch (the pre-async behavior, kept as
  the differential baseline).
* ``async_depth=1`` — ring depth 1, commit-time readback. Scheduling,
  token streams and ``ServerStats`` are *identical* to depth 0; only
  the host no longer stalls inside the dispatch phase.
* ``async_depth>=2`` — true in-flight pipelining: queued calls charge
  energy and advance every slot and commit in dispatch order.

Abort-safety contract: a replica death mid-flight discards every ring
entry *without finalizing its readbacks* — deferred results are
dropped on the floor, members are re-queued by the scheduler, and no
request state is ever mutated from a call that did not commit. Token
streams are therefore bit-for-bit identical across every depth
(``tests/test_async_engine.py`` proves this differentially under
admission, chunked prefill, preemption and double failover).

Speculative draft-verify decoding (``spec_draft=(model, params)``)
------------------------------------------------------------------
Plain decode pays one full pipeline dispatch per token. With a draft
model attached, each decode round instead (1) runs the draft
autoregressively for ``spec_k`` greedy tokens in ONE scanned dispatch
on the stage-0 replica (argmax chained on device — no host sync), then
(2) verifies all ``spec_k + 1`` positions in ONE
``verify_step_paged`` chunk call per stage — the existing paged
chunk-prefill computation, no new kernel. The accept rule is greedy
prefix match on the verify argmaxes, so committed streams are
**bit-for-bit identical** to plain paged decode (the paged chunk and
decode paths share one attention reduction order — proven in
``tests/test_spec_decode.py``); a round commits between 1 (all drafts
rejected: the verify's own argmax) and ``spec_k + 1`` tokens per
pipeline pass. Rejected rows are rewound through
``KVCacheManager.rollback`` (pure host accounting: stale rows past the
length mirror are never attended and are re-written before any later
read). The commit finalizer is deferred-readback compatible with the
async ring at any depth; a round broken by replica death or preemption
is rewound by ``StepScheduler.rewind_spec`` to exactly the state plain
decode would have left. Energy is charged per *call*; throughput is
reported per *accepted token* (``ServerStats.accepted_tokens``,
``acceptance_rate``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import Counter, deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec

from .readback import engine_phase, engine_step, host_readback, span
from ..core.power import PowerModePolicy, dynamic_policy
from ..distributed.sharding import (
    SERVE_RULES,
    param_shardings,
    replica_submeshes,
    serve_cache_spec,
)
from ..models.registry import Model
from .budget import ReplicaBudget
from .cache import DenseSlotCache, KVCacheManager, PagedKVCache
from .partition import partition_model
from .router import Router
from .scheduler import Request, StepScheduler

__all__ = [
    "Request",
    "PipelineServer",
    "ServerStats",
    "trace_counts",
    "reset_trace_counts",
]


# --- compile accounting ---------------------------------------------------
# Incremented inside the traced stage entry points, so it counts actual jit
# cache misses (= XLA compiles) per (kind, stage, shape). Used by the
# chunked-prefill compile-count regression test and benchmarks/chunked_bench.
_TRACE_COUNTS: Counter = Counter()


def trace_counts() -> dict[tuple, int]:
    """jit trace (cache-miss) count per ``(kind, stage, *shape)``."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


def _count_trace(kind: str, g: int, *shape: int) -> None:
    _TRACE_COUNTS[(kind, g) + tuple(shape)] += 1


@dataclasses.dataclass
class _StageCall:
    """One in-flight batched stage execution on a (group, replica).

    ``outputs[i]`` is a ``(kind, value, advance)`` tuple per member:
    ``("token", t, 0)`` — final-stage token; ``("hidden", h, 0)`` —
    handoff to the next stage; ``("chunk_part", h|None, n)`` — ``n``
    more prompt tokens consumed, prefill continues next step;
    ``("chunk_done", t|h, n)`` — the chunk that completed the stage's
    prefill.

    Token-valued entries are *deferred*: at dispatch they hold ``None``
    and ``readbacks`` carries ``(device_array, finalize)`` pairs — the
    batched argmax outputs still in flight plus the closures that patch
    the host integers into ``outputs``. The committer drains them
    through :func:`host_readback` when the call completes; an aborted
    call (replica death mid-flight) is discarded with its readbacks
    unfinalized, so a dead dispatch can never mutate request state.
    """

    members: list[Request]
    outputs: list[tuple]
    readbacks: list[tuple]
    pm: int
    slots_left: int
    cid: int = 0  # call id, unique within the server (span arguments)
    t_dispatch: float = 0.0
    # Stamped the slot the call's device slots complete (dispatch-
    # observable) — NOT when the completion queue finally drains it;
    # ``Request.ttft_slots`` reads it, so a deep ring cannot inflate it.
    ready_slot: int | None = None


@dataclasses.dataclass
class ServerStats:
    submitted: int = 0
    completed_jobs: int = 0
    dropped_jobs: int = 0
    queued_jobs: int = 0  # submissions that waited in the pending queue
    tokens_generated: int = 0
    accepted_tokens: int = 0  # committed tokens, dispatch-observable —
    # identical to tokens_generated for the plain engine; the shared
    # metric spec and plain engines are compared on (a speculative round
    # commits a variable number of accepted tokens per verify call)
    stage_executions: int = 0  # per-request stage work units
    prefill_calls: int = 0  # batched JAX dispatches (whole-prompt prefill)
    chunk_prefill_calls: int = 0  # batched JAX dispatches (chunked prefill)
    decode_calls: int = 0  # batched JAX dispatches (decode)
    draft_calls: int = 0  # speculative: draft-model scan dispatches
    verify_calls: int = 0  # speculative: target verify chunk dispatches
    spec_rounds: int = 0  # speculative rounds committed
    spec_proposed: int = 0  # draft tokens proposed to verification
    spec_accepted: int = 0  # draft tokens accepted (excl. bonus tokens)
    energy_charged: float = 0.0  # total CE(PM)/kappa charged across calls
    rerouted_stages: int = 0
    preempted_jobs: int = 0  # paged: evicted on page exhaustion, requeued
    aged_placements: int = 0  # parked > max_park_steps: force-placed
    peak_active: int = 0  # max concurrently resident requests
    inflight_peak: int = 0  # max calls in one replica's in-flight ring
    slots: int = 0
    downtime_replica_slots: int = 0  # whole (replica, slot) pairs down
    stage_calls: int = 0  # stage calls opened; the next call's id
    # What a profiler recorded of the engine's spans while one ran (all 0
    # without one, so ServerStats stays deterministic): steps and stage
    # calls begun, and host seconds blocked in ``serve.readback`` and
    # issuing stage programs in ``serve.launch``.
    traced_steps: int = 0
    traced_calls: int = 0
    traced_readback_s: float = 0.0
    traced_launch_s: float = 0.0
    # Steps (slots) a request took: from submit to the step its first
    # token landed in ``generated``, and between consecutive tokens.
    first_token_steps: int = 0
    first_tokens: int = 0
    token_gap_steps: int = 0
    token_gaps: int = 0
    n_groups: int = 1
    n_replicas: int = 1

    @property
    def downtime_fraction(self) -> float:
        denom = self.slots * self.n_groups * self.n_replicas
        return self.downtime_replica_slots / max(denom, 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.spec_accepted / max(self.spec_proposed, 1)


def _pad_tail(x, C: int):
    """Pad a [1, c, ...] chunk slice to width ``C`` along axis 1."""
    c = x.shape[1]
    if c == C:
        return x
    pad = [(0, 0), (0, C - c)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)


def _seq_len(seq) -> int:
    """Length of a stage input: [S] token ids or [1, S, D] hidden."""
    return seq.shape[1] if seq.ndim >= 2 else len(seq)


def _group_by_len(jobs) -> dict[int, list]:
    """Whole-prompt prefill pays one dispatch per distinct input length."""
    by_len: dict[int, list] = {}
    for i, m, inp in jobs:
        by_len.setdefault(int(inp.shape[1]), []).append((i, m, inp))
    return by_len


def _emit_whole_outputs(server, g, grp, out, outputs, mgr, length, readbacks):
    """Shared whole-prefill tail for both backends: record the host
    length mirror and emit one deferred token readback (batched argmax,
    one host sync at commit) or hidden handoff per member of a
    same-length dispatch group."""
    for _, m, _ in grp:
        mgr.lengths[m.slot_ids[g]] = length
    if g == server.G - 1:
        idxs = [i for i, _, _ in grp]

        def fin(toks, idxs=idxs):
            for j, i in enumerate(idxs):
                outputs[i] = ("token", int(toks[j]), 0)

        readbacks.append((jnp.argmax(out[:, 0, -1], axis=-1), fin))
    else:
        for j, (i, _, _) in enumerate(grp):
            outputs[i] = ("hidden", out[j], 0)


def _emit_chunk_outputs(server, g, jobs, outputs, mgr, argmax, hidden_at, readbacks):
    """Shared chunk-job tail for both backends: advance the host length
    mirror, decide per-lane completion, and emit ``chunk_part`` /
    ``chunk_done`` results. ``argmax`` is the batched [W, C] argmax
    device array (last stage only — its readback is deferred to
    commit); ``hidden_at(slot, valid)`` slices a lane's [1, valid, D]
    hidden from the dispatch output (mid stages only)."""
    last = g == server.G - 1
    finals: list[tuple[int, int, int]] = []
    for i, m, seq, pos, valid in jobs:
        slot = m.slot_ids[g]
        mgr.lengths[slot] = pos + valid
        done = pos + valid == _seq_len(seq)
        if last:
            if done:
                finals.append((i, slot, valid))
            outputs[i] = ("chunk_done" if done else "chunk_part", None, valid)
        else:
            value = hidden_at(slot, valid)
            outputs[i] = ("chunk_done" if done else "chunk_part", value, valid)
    if last:
        # One deferred readback per chunk dispatch (sync-count parity
        # with the pre-async engine even when no lane completed).
        def fin(toks, finals=finals):
            for i, slot, valid in finals:
                outputs[i] = ("chunk_done", int(toks[slot, valid - 1]), valid)

        readbacks.append((argmax, fin))


class _SpecState:
    """Speculative-decoding state: the draft model, its per-stage-0-replica
    slot-stacked dense caches, the host lockstep mirrors, and the two
    jitted draft entry points.

    The draft runs *unpartitioned* on each stage-0 replica: one dense
    cache of ``max_batch`` lanes keyed by the replica's stage-0 slot ids.
    ``rid``/``lens`` are host mirrors of which request owns each draft
    lane and how many rows of its true stream (prompt + committed
    tokens) are valid — a mismatched rid (lane reuse, failover) rebuilds
    the lane from position 0 via fixed-width catch-up ingests, so draft
    state needs no abort protocol of its own: it is *advisory* and every
    committed token comes from the target's verify.
    """

    def __init__(self, server: "PipelineServer", draft: Model, draft_params, k: int):
        self.model = draft
        self.params = draft_params
        self.k = k
        W = server.max_batch
        # Draft rows past the target's max_len are never *read* (requests
        # complete within max_len) but the fixed-width ingest and the
        # k-step scan may *write* up to k positions past the committed
        # context; the headroom keeps every dynamic-slice write in bounds
        # (a clamped start would silently overwrite live rows).
        shapes = draft.cache_shapes(1, server.max_len + k + 1)
        self.caches = {
            r: server._place(
                r,
                jax.tree_util.tree_map(
                    lambda sh: jnp.zeros((W,) + tuple(sh.shape), sh.dtype), shapes
                ),
            )
            for r in range(server.R)
        }
        # The draft runs unpartitioned, so under a mesh its params are
        # simply replicated onto each stage-0 replica's submesh (one
        # copy per distinct data slice).
        self._placed_params = None
        self._slice_of = server._slice_of
        if server._repl_shardings is not None:
            self._placed_params = {}
            for r in range(server.R):
                d = self._slice_of[r]
                if d not in self._placed_params:
                    self._placed_params[d] = jax.device_put(
                        draft_params, server._repl_shardings[r]
                    )
        self.rid = {r: np.full((W,), -1, np.int64) for r in range(server.R)}
        self.lens = {r: np.zeros((W,), np.int64) for r in range(server.R)}

        model = draft

        def merge(mask, new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(
                    mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1)), n, o
                ),
                new,
                old,
            )

        @partial(jax.jit, donate_argnums=(2,))
        def draft_ingest(params, buf, cache, offs, valids, mask):
            # buf: [W, 1, C] catch-up token chunks (lane rebuilds after
            # failover / reuse); masked-out lanes keep their cache.
            _count_trace("draft_ingest", 0, buf.shape[0], buf.shape[2])
            _, new = model.prefill_chunk_batch(
                params, {"tokens": buf}, cache, offs, valids
            )
            return merge(mask, new, cache)

        @partial(jax.jit, donate_argnums=(2,))
        def draft_round(params, buf, cache, offs, valids, tok0, mask):
            # ONE dispatch per round: ingest the <= C tokens the draft has
            # not seen yet (usually the previous round's accepted tail),
            # then scan k greedy steps chaining the argmax on device —
            # the k draft tokens never touch the host inside the round.
            _count_trace("draft_round", 0, buf.shape[0], buf.shape[2])
            _, c = model.prefill_chunk_batch(
                params, {"tokens": buf}, cache, offs, valids
            )

            def step(carry, _):
                tok, c = carry
                logits, c = model.decode_batch(params, tok[:, None, None], c)
                nxt = jnp.argmax(logits[:, 0, -1], axis=-1).astype(jnp.int32)
                return (nxt, c), nxt

            (_, c), drafts = jax.lax.scan(step, (tok0, c), None, length=k)
            return drafts.T, merge(mask, c, cache)  # [W, k], merged cache

        self.draft_ingest = draft_ingest
        self.draft_round = draft_round

    def params_for(self, r: int):
        if self._placed_params is None:
            return self.params
        return self._placed_params[self._slice_of[r]]


class _DenseExec:
    """Dense execution backend for one stage: slot-stacked cache, vmapped
    entry points, masked full-width decode/chunk dispatches."""

    def __init__(self, server: "PipelineServer", g: int):
        self.server = server
        self.g = g
        model_g, _ = server.stages[g]
        self.model_g = model_g
        max_len = server.max_len

        @partial(jax.jit, donate_argnums=(2,))
        def prefill_into(params, batch, cache, slot_idx):
            # batch leaves: [N, 1, S(, D)] — N joining requests, same S.
            leaf = jax.tree_util.tree_leaves(batch)[0]
            _count_trace("prefill", g, leaf.shape[0], leaf.shape[2])
            out, new = model_g.prefill_batch(params, batch, max_len)
            cache = jax.tree_util.tree_map(
                lambda big, small: big.at[slot_idx].set(small), cache, new
            )
            return out, cache

        @partial(jax.jit, donate_argnums=(2,))
        def decode_masked(params, inp, cache, mask):
            # inp: [W, 1, 1(, D)] over the full slot width W = max_batch;
            # mask selects participating slots — the others' caches are
            # preserved by the select (their computed garbage is dropped).
            _count_trace("decode", g, mask.shape[0])
            out, new = model_g.decode_batch(params, inp, cache)
            merged = jax.tree_util.tree_map(
                lambda n, o: jnp.where(
                    mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1)), n, o
                ),
                new,
                cache,
            )
            return out, merged

        self.prefill_into = prefill_into
        self.decode_masked = decode_masked
        self.chunk_masked = None
        if server.prefill_chunk is not None:

            @partial(jax.jit, donate_argnums=(2,))
            def chunk_masked(params, inp, cache, offs, valids, mask):
                # inp leaves: [W, 1, C(, D)] — one fixed chunk width for
                # every prompt length in the workload.
                leaf = jax.tree_util.tree_leaves(inp)[0]
                _count_trace("chunk", g, leaf.shape[0], leaf.shape[2])
                out, new = model_g.prefill_chunk_batch(
                    params, inp, cache, offs, valids
                )
                merged = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1)), n, o
                    ),
                    new,
                    cache,
                )
                return out, merged

            self.chunk_masked = chunk_masked

    def init_cache(self, r):
        """Zeroed slot-stacked cache: [max_batch, <B=1 cache>],
        committed to replica ``r``'s submesh under a serving mesh
        (sharded only on the leading slot axis = ``cache_batch``)."""
        s = self.server
        shapes = self.model_g.cache_shapes(1, s.max_len)
        cache = jax.tree_util.tree_map(
            lambda sh: jnp.zeros((s.max_batch,) + tuple(sh.shape), sh.dtype), shapes
        )
        return s._place_cache(self.g, r, cache)

    # -- dispatches ------------------------------------------------------
    def run_prefill_whole(self, r, jobs, outputs, mgr: KVCacheManager, readbacks):
        """jobs: [(out_idx, member, inp [1,S(,D)])], grouped by length."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        cache = s._caches[(g, r)]
        key = "tokens" if g == 0 else "hidden"
        for length, grp in sorted(_group_by_len(jobs).items()):
            stacked = jnp.stack([s._place(r, inp) for _, _, inp in grp])
            slots = jnp.asarray([m.slot_ids[g] for _, m, _ in grp], jnp.int32)
            out, cache = self.prefill_into(params_g, {key: stacked}, cache, slots)
            s.stats.prefill_calls += 1
            _emit_whole_outputs(s, g, grp, out, outputs, mgr, length, readbacks)
        s._caches[(g, r)] = cache

    def run_chunks(self, r, jobs, outputs, mgr: KVCacheManager, readbacks):
        """jobs: [(out_idx, member, seq, pos, valid)] — one fixed-shape
        masked dispatch advances every joining prompt by <= C tokens."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        C = s.prefill_chunk
        W = s.max_batch
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        with span("serve.inputs"):
            mask = np.zeros((W,), bool)
            offs = np.zeros((W,), np.int32)
            valids = np.zeros((W,), np.int32)
            for _, m, _, pos, valid in jobs:
                slot = m.slot_ids[g]
                mask[slot] = True
                offs[slot] = pos
                valids[slot] = valid
            if g == 0:
                buf = np.zeros((W, 1, C), np.int32)
                for _, m, seq, pos, valid in jobs:
                    buf[m.slot_ids[g], 0, :valid] = seq[pos : pos + valid]
                inp = {"tokens": jnp.asarray(buf)}
            else:
                slots = np.asarray([m.slot_ids[g] for _, m, _, _, _ in jobs], np.int32)
                hs = jnp.stack(
                    [
                        s._place(r, _pad_tail(seq[:, pos : pos + valid], C))
                        for _, _, seq, pos, valid in jobs
                    ]
                )  # [N, 1, C, D]
                inp = {
                    "hidden": jnp.zeros((W, 1, C, s.cfg.d_model), hs.dtype)
                    .at[jnp.asarray(slots)]
                    .set(hs)
                }
            args = (jnp.asarray(offs), jnp.asarray(valids), jnp.asarray(mask))
        with span("serve.launch") as launch:
            out, cache = self.chunk_masked(params_g, inp, cache, *args)
            argmax = jnp.argmax(out[:, 0], axis=-1) if last else None
            _emit_chunk_outputs(
                s, g, jobs, outputs, mgr, argmax,
                lambda slot, valid: out[slot, :, :valid],  # [1, valid, D]
                readbacks,
            )
        s.stats.traced_launch_s += launch.seconds
        s._caches[(g, r)] = cache
        s.stats.chunk_prefill_calls += 1

    def run_decode(self, r, jobs, outputs, mgr: KVCacheManager, readbacks):
        """jobs: [(out_idx, member)] — one masked dispatch over the full
        static slot width."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        W = s.max_batch
        with span("serve.inputs"):
            mask = np.zeros((W,), bool)
            slots = np.asarray([m.slot_ids[g] for _, m in jobs], np.int32)
            mask[slots] = True
            if g == 0:
                buf = np.zeros((W, 1, 1), np.int32)
                for _, m in jobs:
                    buf[m.slot_ids[g], 0, 0] = m.generated[-1]
                inp = jnp.asarray(buf)
            else:
                # Assemble on device: the handoffs are device arrays and a
                # host round-trip per member would not amortize. After an
                # upstream re-prefill the handoff carries the whole
                # prefix; a caching stage only consumes the newest position.
                hs = jnp.stack(
                    [
                        s._place(r, m.hidden if m.hidden.shape[1] == 1 else m.hidden[:, -1:])
                        for _, m in jobs
                    ]
                )
                inp = (
                    jnp.zeros((W, 1, 1, s.cfg.d_model), hs.dtype)
                    .at[jnp.asarray(slots)]
                    .set(hs)
                )
            mask = jnp.asarray(mask)
        with span("serve.launch") as launch:
            out, cache = self.decode_masked(params_g, inp, cache, mask)
            if last:
                # Capture concrete slot ints now: by commit time a member's
                # slot_ids could be rewritten by a later placement.
                pairs = [(i, m.slot_ids[g]) for i, m in jobs]

                def fin(toks, pairs=pairs):
                    for i, slot in pairs:
                        outputs[i] = ("token", int(toks[slot]), 0)

                readbacks.append((jnp.argmax(out[:, 0, -1], axis=-1), fin))
            else:
                for i, m in jobs:
                    outputs[i] = ("hidden", out[m.slot_ids[g]], 0)
        s.stats.traced_launch_s += launch.seconds
        s._caches[(g, r)] = cache
        s.stats.decode_calls += 1
        for _, m in jobs:
            mgr.lengths[m.slot_ids[g]] += 1


class _PagedExec:
    """Paged execution backend for one stage: shared page pool, block
    tables from the manager, natively batched decode/chunk dispatches."""

    def __init__(self, server: "PipelineServer", g: int):
        self.server = server
        self.g = g
        model_g, _ = server.stages[g]
        self.model_g = model_g
        ps = server.page_size

        @partial(jax.jit, donate_argnums=(2,))
        def prefill_pages(params, batch, pools, page_ids):
            # batch leaves: [N, 1, S(, D)]; page_ids: [N, NBs] with
            # NBs * ps >= S. The transient dense cache is per-call only.
            # Compute-dtype pools only — int8 whole prefill goes through
            # prefill_whole_quant instead (see _run_prefill_whole_quant).
            leaf = jax.tree_util.tree_leaves(batch)[0]
            _count_trace("prefill_pages", g, leaf.shape[0], leaf.shape[2])
            N, NBs = page_ids.shape
            out, cache = model_g.prefill_batch(params, batch, NBs * ps)
            flat = page_ids.reshape(-1)

            def rows(leaf):
                # leaf: [N, n_layers, 1, NBs*ps, KV, Dh] -> page rows
                # [n_layers, N*NBs, ps, KV * Dh]
                n = leaf.shape[1]
                x = leaf[:, :, 0].reshape(N, n, NBs, ps, *leaf.shape[4:])
                return x.transpose(1, 0, 2, 3, 4, 5).reshape(n, N * NBs, ps, -1)

            new = dict(pools)
            new["k"] = pools["k"].at[:, flat].set(
                rows(cache["c0"]["k"]).astype(pools["k"].dtype)
            )
            new["v"] = pools["v"].at[:, flat].set(
                rows(cache["c0"]["v"]).astype(pools["v"].dtype)
            )
            return out, new

        @partial(jax.jit, donate_argnums=(2,))
        def decode_fn(params, inp, pools, lens, bt):
            _count_trace("decode_paged", g, lens.shape[0])
            return model_g.decode_paged(params, inp, pools, lens, bt)

        @partial(jax.jit, donate_argnums=(2,))
        def prefill_whole_quant(params, inp, pools, offs, valids, bt):
            # int8 pools only: whole-prompt prefill runs as ONE
            # whole-length chunk, so its logits come from the same
            # quantized pages every later read sees — chunked and
            # whole-prompt prefill stay token-exact at int8 (the
            # fp-exact prefill_pages path would emit its first token
            # from pre-quantization K/V the pool no longer holds).
            _count_trace("prefill_pages", g, inp.shape[0], inp.shape[1])
            return model_g.prefill_chunk_paged(
                params, inp, pools, offs, valids, bt
            )

        self.prefill_pages = prefill_pages
        self.prefill_whole_quant = prefill_whole_quant
        self.decode_fn = decode_fn
        self.chunk_pages = None
        if server.prefill_chunk is not None:

            @partial(jax.jit, donate_argnums=(2,))
            def chunk_pages(params, inp, pools, offs, valids, bt):
                # inp: [W, C(, D)] — one fixed chunk width; each lane's
                # K/V scatter into its reserved pages incrementally.
                _count_trace("chunk_paged", g, inp.shape[0], inp.shape[1])
                return model_g.prefill_chunk_paged(
                    params, inp, pools, offs, valids, bt
                )

            self.chunk_pages = chunk_pages
        self.verify_fn = None
        if server._spec is not None:

            @partial(jax.jit, donate_argnums=(2,))
            def verify_fn(params, inp, pools, offs, valids, bt):
                # inp: [W, k+1] — lane w holds [gen[-1], d_1..d_k] (stage
                # 0) or the upstream verify hidden (mid stages); one
                # chunk-shaped call verifies all k+1 positions, bit-exact
                # against sequential paged decode (no new kernel).
                _count_trace("verify_paged", g, inp.shape[0], inp.shape[1])
                return model_g.verify_step_paged(
                    params, inp, pools, offs, valids, bt
                )

            self.verify_fn = verify_fn

    def init_cache(self, r):
        """Shared page pool: [n_layers, P+1, page, KV * Dh] (page index P
        is the scratch page for masked lanes; a page row holds its KV
        heads side by side, the layout the TPU tiles without padding and
        the paged kernel reads in place). ``kv_dtype="int8"`` pools
        store int8 entries plus one fp32 scale per page row (init 1.0 so
        untouched rows dequantize to 0)."""
        s = self.server
        c = self.model_g.cfg
        shape = (
            c.n_layers, s.max_pages + 1, s.page_size, c.n_kv_heads * c.head_dim,
        )
        pools = {
            "k": jnp.zeros(shape, s.kv_dtype),
            "v": jnp.zeros(shape, s.kv_dtype),
        }
        if s.kv_dtype == jnp.int8:
            # Distinct buffers: the dispatches donate the pool tree, and
            # XLA rejects donating one buffer at two argument positions.
            pools["k_scale"] = jnp.ones(shape[:3], jnp.float32)
            pools["v_scale"] = jnp.ones(shape[:3], jnp.float32)
        # The shared pool is addressed by page id, not by slot: no
        # ``cache_batch`` dim exists, so ``serve_cache_spec`` degenerates
        # to replication within the slice — which is exactly ``_place``.
        return s._place(r, pools)

    # -- dispatches ------------------------------------------------------
    def run_prefill_whole(self, r, jobs, outputs, mgr: PagedKVCache, readbacks):
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        cache = s._caches[(g, r)]
        if "k_scale" in cache:
            return self._run_prefill_whole_quant(r, jobs, outputs, mgr, readbacks)
        key = "tokens" if g == 0 else "hidden"
        for length, grp in sorted(_group_by_len(jobs).items()):
            stacked = jnp.stack([s._place(r, inp) for _, _, inp in grp])
            nbs = mgr.pool.blocks_for(length)
            page_ids = np.asarray(
                [mgr.pages[m.rid][:nbs] for _, m, _ in grp], np.int32
            )
            out, cache = self.prefill_pages(
                params_g, {key: stacked}, cache, jnp.asarray(page_ids)
            )
            s.stats.prefill_calls += 1
            _emit_whole_outputs(s, g, grp, out, outputs, mgr, length, readbacks)
        s._caches[(g, r)] = cache

    def _run_prefill_whole_quant(self, r, jobs, outputs, mgr: PagedKVCache, readbacks):
        """int8 pools: one whole-length chunk dispatch per distinct
        prompt length, over ONLY the joining lanes with a compact
        [N, nbs] block table (same work profile as the fp32
        prefill_pages path — dispatching the full slot width against
        the full-width table measured as a whole-percent tokens/s
        hit). The extra masked positions a full-width table would
        gather contribute exp(-inf) = 0, so the compact call is
        bit-identical to what the chunked path later reads."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        for length, grp in sorted(_group_by_len(jobs).items()):
            N = len(grp)
            nbs = mgr.pool.blocks_for(length)
            page_ids = np.asarray(
                [mgr.pages[m.rid][:nbs] for _, m, _ in grp], np.int32
            )
            offs = jnp.zeros((N,), jnp.int32)
            valids = jnp.full((N,), length, jnp.int32)
            if g == 0:
                inp_w = jnp.stack([jnp.asarray(inp[0]) for _, _, inp in grp])
            else:
                inp_w = jnp.stack([s._place(r, inp[0]) for _, _, inp in grp])  # [N, S, D]
            out, cache = self.prefill_whole_quant(
                params_g, inp_w, cache, offs, valids, jnp.asarray(page_ids)
            )
            s.stats.prefill_calls += 1
            for _, m, _ in grp:
                mgr.lengths[m.slot_ids[g]] = length
            if last:
                idxs = [i for i, _, _ in grp]

                def fin(toks, idxs=idxs):
                    for j, i in enumerate(idxs):
                        outputs[i] = ("token", int(toks[j]), 0)

                readbacks.append((jnp.argmax(out[:, length - 1], axis=-1), fin))
            else:
                for j, (i, _, _) in enumerate(grp):
                    outputs[i] = ("hidden", out[j, :length][None], 0)
        s._caches[(g, r)] = cache

    def run_chunks(self, r, jobs, outputs, mgr: PagedKVCache, readbacks):
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        C = s.prefill_chunk
        W = s.max_batch
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        with span("serve.inputs"):
            offs = np.full((W,), -1, np.int32)  # -1 = masked lane
            valids = np.zeros((W,), np.int32)
            for _, m, _, pos, valid in jobs:
                slot = m.slot_ids[g]
                offs[slot] = pos
                valids[slot] = valid
            if g == 0:
                buf = np.zeros((W, C), np.int32)
                for _, m, seq, pos, valid in jobs:
                    buf[m.slot_ids[g], :valid] = seq[pos : pos + valid]
                inp = jnp.asarray(buf)
            else:
                slots = np.asarray([m.slot_ids[g] for _, m, _, _, _ in jobs], np.int32)
                hs = jnp.stack(
                    [
                        s._place(r, _pad_tail(seq[:, pos : pos + valid], C)[0])
                        for _, _, seq, pos, valid in jobs
                    ]
                )  # [N, C, D]
                inp = (
                    jnp.zeros((W, C, s.cfg.d_model), hs.dtype)
                    .at[jnp.asarray(slots)]
                    .set(hs)
                )
            args = (jnp.asarray(offs), jnp.asarray(valids), mgr.device_block_table())
        with span("serve.launch") as launch:
            out, cache = self.chunk_pages(params_g, inp, cache, *args)
            argmax = jnp.argmax(out, axis=-1) if last else None
            _emit_chunk_outputs(
                s, g, jobs, outputs, mgr, argmax,
                lambda slot, valid: out[slot, :valid][None],  # [1, valid, D]
                readbacks,
            )
        s.stats.traced_launch_s += launch.seconds
        s._caches[(g, r)] = cache
        s.stats.chunk_prefill_calls += 1

    def run_decode(self, r, jobs, outputs, mgr: PagedKVCache, readbacks):
        """One natively-batched paged dispatch over the slot width.
        Lanes marked -1 write to the scratch page and attend one masked
        position; their outputs are never read. The device block table
        is cached by the manager and refreshed only on page alloc/free."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        W = s.max_batch
        with span("serve.inputs"):
            lens_arr = np.full((W,), -1, np.int32)
            for _, m in jobs:
                slot = m.slot_ids[g]
                lens_arr[slot] = mgr.lengths[slot]
            if g == 0:
                buf = np.zeros((W, 1), np.int32)
                for _, m in jobs:
                    buf[m.slot_ids[g], 0] = m.generated[-1]
                inp = jnp.asarray(buf)
            else:
                slots = np.asarray([m.slot_ids[g] for _, m in jobs], np.int32)
                # Hand-offs: [1, D] from an upstream decode, [1, S, D]
                # after an upstream re-prefill (consume the last position).
                hs = jnp.stack(
                    [
                        s._place(r, m.hidden if m.hidden.ndim == 2 else m.hidden[:, -1])
                        for _, m in jobs
                    ]
                )  # [N, 1, D]
                inp = (
                    jnp.zeros((W, 1, s.cfg.d_model), hs.dtype)
                    .at[jnp.asarray(slots)]
                    .set(hs)
                )
            args = (jnp.asarray(lens_arr), mgr.device_block_table())
        with span("serve.launch") as launch:
            out, cache = self.decode_fn(params_g, inp, cache, *args)
            if last:
                pairs = [(i, m.slot_ids[g]) for i, m in jobs]

                def fin(toks, pairs=pairs):
                    for i, slot in pairs:
                        outputs[i] = ("token", int(toks[slot]), 0)

                readbacks.append((jnp.argmax(out[:, 0], axis=-1), fin))
            else:
                # Hand-offs stay [1, D] (not dense's [1, 1, D]): the
                # per-member [None] here costs one eagerly-dispatched
                # expand_dims per request per stage round, which measured
                # as a whole-percent tokens/s hit; both consumers branch
                # on ndim instead.
                for i, m in jobs:
                    outputs[i] = ("hidden", out[m.slot_ids[g]], 0)
        s.stats.traced_launch_s += launch.seconds
        s._caches[(g, r)] = cache
        s.stats.decode_calls += 1
        for _, m in jobs:
            mgr.lengths[m.slot_ids[g]] += 1

    def run_verify(self, r, jobs, outputs, mgr: PagedKVCache, readbacks, tok_dev):
        """jobs: [(out_idx, member, seq, pos, valid)] — ONE fixed-shape
        verify chunk covers every speculating lane's ``valid`` = k+1 (or
        fewer, near completion) positions. Stage 0 consumes the on-device
        token assembly built by the engine's draft runner; mid stages
        consume the upstream verify hidden. The host length mirror
        advances optimistically by ``valid`` — the accept finalizer (or
        an abort's ``rewind_spec``) rolls the rejected tail back."""
        s, g = self.server, self.g
        params_g = s._params_for(g, r)
        C = s._spec.k + 1
        W = s.max_batch
        cache = s._caches[(g, r)]
        last = g == s.G - 1
        offs = np.full((W,), -1, np.int32)  # -1 = masked lane
        valids = np.zeros((W,), np.int32)
        for _, m, _, pos, valid in jobs:
            slot = m.slot_ids[g]
            offs[slot] = pos
            valids[slot] = valid
        if g == 0:
            inp = tok_dev  # [W, C], assembled on device from the drafts
        else:
            slots = np.asarray([m.slot_ids[g] for _, m, _, _, _ in jobs], np.int32)
            hs = jnp.stack(
                [s._place(r, _pad_tail(seq, C)[0]) for _, _, seq, _, _ in jobs]
            )  # [N, C, D]
            inp = (
                jnp.zeros((W, C, s.cfg.d_model), hs.dtype)
                .at[jnp.asarray(slots)]
                .set(hs)
            )
        out, cache = self.verify_fn(
            params_g, inp, cache,
            jnp.asarray(offs), jnp.asarray(valids), mgr.device_block_table(),
        )
        s._caches[(g, r)] = cache
        s.stats.verify_calls += 1
        for _, m, _, pos, valid in jobs:
            mgr.lengths[m.slot_ids[g]] = pos + valid
            if m.spec_adv is None:
                m.spec_adv = [0] * s.G
            m.spec_adv[g] = valid
        if last:
            entries = [(i, m, m.slot_ids[g], valid) for i, m, _, _, valid in jobs]

            def fin(toks, entries=entries):
                for i, m, slot, v in entries:
                    # Greedy accept: row j predicts the token after input
                    # j, so drafts[a] is accepted while it matches row
                    # a's argmax; row a then donates the bonus token.
                    tgt = [int(toks[slot, j]) for j in range(v)]
                    drafts = m.spec_drafts or []
                    a = 0
                    while a < v - 1 and drafts[a] == tgt[a]:
                        a += 1
                    outputs[i] = ("spec_done", tgt[: a + 1], v)

            readbacks.append((jnp.argmax(out, axis=-1), fin))
        else:
            for i, m, _, _, valid in jobs:
                outputs[i] = ("spec_hidden", out[m.slot_ids[g], :valid][None], valid)


class PipelineServer:
    def __init__(
        self,
        model: Model,
        params,
        *,
        n_groups: int = 3,
        n_replicas: int = 3,
        policy: str = "adaptive",
        pm_policy: PowerModePolicy | None = None,
        harvest_bounds: tuple[float, float] = (6.0, 10.0),
        long_term_rates: np.ndarray | None = None,
        max_len: int = 256,
        max_batch: int = 4,
        max_queue: int | None = None,
        paged: bool = False,
        page_size: int = 16,
        max_pages: int | None = None,
        kv_dtype: str | None = None,
        prefill_chunk: int | None = None,
        max_park_steps: int | None = 32,
        async_depth: int = 2,
        spec_draft: tuple[Model, Any] | None = None,
        spec_k: int = 4,
        mesh=None,
        elastic=None,
        seed: int = 0,
    ):
        self.cfg = model.cfg
        self.stages = partition_model(model.cfg, params, n_groups)
        self.G, self.R = n_groups, n_replicas
        # Mesh-sharded execution: params TP over the model axis per
        # replica slice, caches committed to the owning slice. All state
        # is None without a mesh — every placement helper degrades to
        # identity and the engine is byte-for-byte the single-device one.
        self.mesh = mesh
        self.elastic = elastic
        self._slice_of: list[int] | None = None
        self._replica_meshes = None
        self._repl_shardings: list[NamedSharding] | None = None
        self._placed_params: dict[tuple[int, int], Any] | None = None
        if mesh is not None:
            slices, self._slice_of = replica_submeshes(mesh, n_replicas)
            self._replica_meshes = [slices[d] for d in self._slice_of]
            self._repl_shardings = [
                NamedSharding(m, PartitionSpec()) for m in self._replica_meshes
            ]
            self._placed_params = {}
            for g, (model_g, params_g) in enumerate(self.stages):
                for d, sub in enumerate(slices):
                    self._placed_params[(g, d)] = jax.device_put(
                        params_g,
                        param_shardings(model_g.template, sub, SERVE_RULES),
                    )
        self.max_len = max_len
        self.max_batch = max_batch
        self.paged = paged
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        # KV page dtype: None keeps pages at the model's compute dtype;
        # "int8" quantizes at scatter (per-row fp32 scales ride along),
        # so the same pool bytes hold ~4x (fp32) / ~2x (bf16) the pages.
        if kv_dtype is not None and not paged:
            raise ValueError("kv_dtype applies to the paged KV cache only")
        self.kv_dtype = (
            jnp.dtype(model.cfg.compute_dtype)
            if kv_dtype is None
            else jnp.dtype(kv_dtype)
        )
        if self.kv_dtype not in (jnp.dtype(model.cfg.compute_dtype), jnp.int8):
            raise ValueError(
                f"kv_dtype must be the compute dtype or int8, got {kv_dtype}"
            )
        # Default pool = dense capacity (max_batch full-length contexts);
        # the paged win comes from setting max_pages *below* this while
        # raising max_batch — short requests then pack the same memory.
        nb_max = -(-max_len // page_size)
        self.max_pages = max_pages if max_pages is not None else max_batch * nb_max
        if paged and any(m.decode_paged is None for m, _ in self.stages):
            raise ValueError(
                f"{model.cfg.name}: paged serving needs uniform full "
                "attention (see repro.models.transformer.supports_paged)"
            )
        if prefill_chunk is not None:
            if prefill_chunk <= 0:
                raise ValueError("prefill_chunk must be a positive token count")
            if any(m.prefill_chunk is None for m, _ in self.stages):
                raise ValueError(
                    f"{model.cfg.name}: chunked prefill needs uniform full "
                    "attention (see repro.models.transformer.supports_paged)"
                )
        # Speculative draft-verify decoding: a (draft Model, draft params)
        # pair turns every decode round into k draft steps (one scanned
        # dispatch on the stage-0 replica) plus ONE k+1-wide verify chunk
        # on the target. Paged substrate only: the paged chunk and decode
        # paths share one attention reduction order, so greedy accept is
        # bit-for-bit against plain decode — the dense chunk path is not.
        self._spec = None
        if spec_draft is not None:
            if not paged:
                raise ValueError(
                    "speculative decoding runs on the paged substrate only "
                    "(the dense chunk path is not bit-exact vs decode)"
                )
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            draft_model, draft_params = spec_draft
            if any(m.verify_step_paged is None for m, _ in self.stages):
                raise ValueError(
                    f"{model.cfg.name}: speculative verify needs uniform full "
                    "attention (see repro.models.transformer.supports_paged)"
                )
            if (
                draft_model.prefill_chunk_batch is None
                or draft_model.decode_batch is None
            ):
                raise ValueError(
                    f"{draft_model.cfg.name}: a draft model needs chunked "
                    "prefill + batched decode (uniform full attention)"
                )
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    "draft and target must share a vocabulary: "
                    f"{draft_model.cfg.vocab_size} vs {model.cfg.vocab_size}"
                )
        if async_depth < 0:
            raise ValueError("async_depth must be >= 0 (0 = legacy sync)")
        self.async_depth = async_depth
        # Ring capacity: depth 0 (legacy sync) still needs one open call.
        self._depth = max(1, async_depth)
        self.pm_policy = pm_policy or dynamic_policy(100)
        # Independent RNG streams: harvest/arrival draws and routing draws
        # must not be correlated (same-integer seeding would lockstep them).
        engine_seq, router_seq = np.random.SeedSequence(seed).spawn(2)
        self._rng = np.random.default_rng(engine_seq)
        # Replicas share stage weights (replication within a group) but
        # have independent budgets/harvests (heterogeneous nodes).
        lo, hi = harvest_bounds
        centers = self._rng.uniform(lo, hi, size=(self.G, self.R))
        self.harvest = np.stack([centers - 2.0, centers + 2.0], axis=-1).clip(0.0)
        self.budgets = [
            [ReplicaBudget(policy=self.pm_policy) for _ in range(n_replicas)]
            for _ in range(n_groups)
        ]
        self.router = Router(
            policy=policy, long_term_rates=long_term_rates, seed=router_seq
        )
        self.stats = ServerStats(n_groups=n_groups, n_replicas=n_replicas)
        self._next_rid = 0
        # One cache manager per (group, replica): the scheduler and the
        # single _start_call below talk only to this interface.
        if paged:
            self.managers: dict[tuple[int, int], KVCacheManager] = {
                (g, r): PagedKVCache(
                    max_batch, max_len, page_size, self.max_pages,
                    kv_dtype=str(self.kv_dtype),
                    # One snapshot buffer per possible in-flight call plus
                    # the one being built: a block-table refresh never
                    # touches a buffer a pending dispatch may still read.
                    table_buffers=self._depth + 1,
                )
                for g in range(n_groups)
                for r in range(n_replicas)
            }
        else:
            self.managers = {
                (g, r): DenseSlotCache(max_batch, max_len)
                for g in range(n_groups)
                for r in range(n_replicas)
            }
        self.scheduler = StepScheduler(
            budgets=self.budgets,
            managers=self.managers,
            router=self.router,
            stats=self.stats,
            max_queue=max_queue,
            max_park_steps=max_park_steps,
        )
        if self._repl_shardings is not None and paged:
            # Block-table snapshots must live where the pool lives, or
            # every paged dispatch re-transfers the table to the slice.
            for (g, r), mgr in self.managers.items():
                mgr.sharding = self._repl_shardings[r]
        if spec_draft is not None:
            # Built before _exec: the paged backend compiles its verify
            # entry point only when speculation is on.
            self._spec = _SpecState(self, spec_draft[0], spec_draft[1], spec_k)
        self._exec = self._build_exec()
        self._caches = {
            (g, r): self._exec[g].init_cache(r)
            for g in range(n_groups)
            for r in range(n_replicas)
        }
        # Per-replica in-flight rings (completion queues): producer
        # appends at dispatch, consumer drains committed heads in order.
        self._calls: dict[tuple[int, int], deque[_StageCall]] = {
            (g, r): deque() for g in range(n_groups) for r in range(n_replicas)
        }
        # (group, replica, perf_counter) per dispatch — async_bench reads
        # inter-dispatch gaps from this.
        self.dispatch_log: list[tuple[int, int, float]] = []
        self.scheduler.inflight = lambda: [
            [len(self._calls[(g, r)]) for r in range(self.R)]
            for g in range(self.G)
        ]

    # ------------------------------------------------------------------
    # Execution substrate (overridable: mpserve proxies these to worker
    # processes)
    # ------------------------------------------------------------------
    def _build_exec(self):
        return [
            (_PagedExec if self.paged else _DenseExec)(self, g)
            for g in range(self.G)
        ]

    def _params_for(self, g: int, r: int):
        """Stage ``g``'s params as replica ``r``'s dispatch should see
        them: the raw tree without a mesh, the slice-placed TP copy with
        one."""
        if self._placed_params is None:
            return self.stages[g][1]
        return self._placed_params[(g, self._slice_of[r])]

    def _trace_mesh(self, r: int):
        """Replica ``r``'s abstract mesh, in scope while its dispatches
        trace: the Pallas attention kernels read it to ``shard_map``
        themselves over heads (XLA cannot partition a Mosaic kernel).
        A no-op without a mesh."""
        if self._replica_meshes is None:
            return contextlib.nullcontext()
        return jax.sharding.use_abstract_mesh(
            self._replica_meshes[r].abstract_mesh
        )

    def _place(self, r: int, x):
        """Commit an array (or tree) to replica ``r``'s submesh, replicated.

        Identity without a mesh. A handoff produced on another replica's
        slice becomes a real device-to-device transfer here — issued in
        the dispatch phase with no host sync; placing an array already
        on the slice is a no-op.
        """
        if self._repl_shardings is None:
            return x
        return jax.device_put(x, self._repl_shardings[r])

    def _place_cache(self, g: int, r: int, cache):
        """Commit stage ``g``'s slot-stacked cache to replica ``r``'s
        submesh under :func:`serve_cache_spec`: each leaf shards only on
        its ``cache_batch`` (slot) dim — the data axis, size 1 inside a
        tensor-parallel slice — and replicates everywhere else, so a
        replica's cache never straddles a slice boundary. Identity
        without a mesh; models that declare no cache axes fall back to
        plain replication."""
        if self._repl_shardings is None:
            return cache
        model_g = self.stages[g][0]
        if model_g.cache_axes is None:
            return jax.device_put(cache, self._repl_shardings[r])
        mesh = self._replica_meshes[r]
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        names = treedef.flatten_up_to(model_g.cache_axes())
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                jax.device_put(
                    leaf,
                    NamedSharding(mesh, serve_cache_spec(leaf.shape, n, mesh)),
                )
                for leaf, n in zip(leaves, names)
            ],
        )

    def _on_ring_abort(self, g: int, r: int) -> None:
        """Hook: a dead replica's in-flight ring was just discarded.
        The multi-process engine drains the worker's now-orphaned RPC
        responses here; in-process execution has nothing to clean up."""

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def submit(self, tokens: np.ndarray, n_tokens: int = 8) -> Request | None:
        """Admit a new request (one replica + batch slot per group, Alg. 1)
        or hold it in the pending queue when the fleet is full."""
        self.stats.submitted += 1
        req = Request(
            rid=self._next_rid,
            prompt=np.asarray(tokens),
            n_tokens=n_tokens,
            t_submit=time.perf_counter(),
            submit_slot=self.stats.slots,
        )
        self._next_rid += 1
        return self.scheduler.submit(req)

    # ------------------------------------------------------------------
    # Batched stage execution (single path over KVCacheManager)
    # ------------------------------------------------------------------
    def _stage_input(self, req: Request, g: int):
        """The sequence this request still has to prefill at stage g."""
        if g == 0:
            ids = np.asarray(req.prompt, np.int32)
            if req.generated:
                # Failover/preemption re-prefill: rebuild the full prefix
                # — prompt plus every generated token, the current round's
                # input included — from the immutable prompt. The last
                # position's output then replaces the decode step the dead
                # replica lost, so decoding stays token-exact across any
                # number of failovers.
                ids = np.concatenate([ids, np.asarray(req.generated, np.int32)])
            return ids
        # Upstream handoff: [1, S, D] after a prefill/chunk assembly,
        # [1, D] ([1, 1, D] dense) after an upstream decode.
        h = req.hidden
        return h[:, None] if h.ndim == 2 else h

    def _run_draft(self, r: int, jobs, readbacks):
        """Draft work for a stage-0 verify call: catch each lane's draft
        cache up to the committed stream (usually just the previous
        round's accepted tail), then scan ``k`` greedy draft steps in ONE
        dispatch, chaining the argmax on device. Returns the [W, k+1]
        on-device verify input — lane w = [gen[-1], d_1..d_k] — with no
        host sync in the dispatch phase; the drafts' host copies ride the
        call's deferred readbacks (needed only by the accept finalizer).
        """
        spec = self._spec
        k = spec.k
        C = k + 1
        W = self.max_batch
        cache = spec.caches[r]
        tok0 = np.zeros((W,), np.int32)
        entries = []  # [member, slot, ctx, draft_len, L] — lanes that draft
        dr_entries = []
        for _, m, _, pos, valid in jobs:
            slot = m.slot_ids[0]
            ctx = np.concatenate(
                [np.asarray(m.prompt, np.int64), np.asarray(m.generated, np.int64)]
            )
            L = len(ctx) - 1  # committed rows; ctx[L] = the round's true input
            tok0[slot] = ctx[L]
            if valid < 2:
                continue  # request's last token: nothing to draft
            if spec.rid[r][slot] != m.rid:
                # First round on this lane (or the lane was reused): the
                # draft knows nothing of the stream — rebuild from 0.
                spec.rid[r][slot] = m.rid
                spec.lens[r][slot] = 0
            entries.append([m, slot, ctx, int(spec.lens[r][slot]), L])
            dr_entries.append((m, slot, valid - 1))
        if not entries:
            drafts = jnp.zeros((W, k), jnp.int32)
        else:
            # Catch-up: a rebuilt lane may be arbitrarily far behind; feed
            # fixed C-wide chunks until one round's ingest suffices.
            while any(e[4] - e[3] > C for e in entries):
                offs = np.zeros((W,), np.int32)
                valids = np.zeros((W,), np.int32)
                mask = np.zeros((W,), bool)
                buf = np.zeros((W, 1, C), np.int32)
                for e in entries:
                    _, slot, ctx, dl, L = e
                    if L - dl > C:
                        mask[slot] = True
                        offs[slot] = dl
                        valids[slot] = C
                        buf[slot, 0, :] = ctx[dl : dl + C]
                        e[3] = dl + C
                cache = spec.draft_ingest(
                    spec.params_for(r), jnp.asarray(buf), cache,
                    jnp.asarray(offs), jnp.asarray(valids), jnp.asarray(mask),
                )
                self.stats.draft_calls += 1
            offs = np.zeros((W,), np.int32)
            valids = np.zeros((W,), np.int32)
            mask = np.zeros((W,), bool)
            buf = np.zeros((W, 1, C), np.int32)
            for _, slot, ctx, dl, L in entries:
                mask[slot] = True
                gap = L - dl
                if gap > 0:
                    offs[slot] = dl
                    valids[slot] = gap
                    buf[slot, 0, :gap] = ctx[dl:L]
                else:
                    # Caught up (an abandoned round can even leave the
                    # draft one speculative row ahead): ingest nothing,
                    # just pin the draft context length back to L.
                    offs[slot] = L
                    valids[slot] = 0
                spec.lens[r][slot] = L + 1  # the scan writes ctx[L]'s row
            drafts, cache = spec.draft_round(
                spec.params_for(r), jnp.asarray(buf), cache,
                jnp.asarray(offs), jnp.asarray(valids),
                jnp.asarray(tok0), jnp.asarray(mask),
            )
            self.stats.draft_calls += 1

            def fin(d, dr=dr_entries):
                for m, slot, ke in dr:
                    m.spec_drafts = [int(x) for x in d[slot, :ke]]

            readbacks.append((drafts, fin))
        spec.caches[r] = cache
        return jnp.concatenate([jnp.asarray(tok0)[:, None], drafts], axis=1)

    def _start_call(self, g: int, r: int, members: list[Request]) -> _StageCall | None:
        """Issue the batched JAX work for every member and open the call.

        One implementation for both cache layouts: members secure memory
        through the manager oldest-first (the scheduler preempts the
        youngest resident on paged exhaustion — members that cannot get
        memory this slot are deferred), then at most three fixed-shape
        dispatches run — whole-prompt prefills (per distinct length,
        legacy path), ONE chunked-prefill call, and ONE masked decode —
        so prefill chunks and decode tokens are co-scheduled per step.
        """
        mgr = self.managers[(g, r)]
        sched = self.scheduler
        chunk = self.prefill_chunk
        t_dispatch = time.perf_counter()

        # Build each member's work item first (prefill length drives page
        # demand), then secure memory oldest-first; _ensure may preempt
        # younger members — skip those when reached (queued/dropped flips).
        plan: dict[int, tuple] = {}
        need: dict[int, int] = {}
        spec = self._spec
        for m in members:
            if m.cache_ready[g]:
                # Speculative rounds start at stage 0; a mid stage joins
                # one only while the round is live (spec_adv[0] set by the
                # stage-0 verify dispatch) — after a mid-round failover
                # re-prefill the handoff is a plain prefix and downstream
                # stages fall back to plain decode for the pass.
                if spec is not None and (
                    g == 0 or (m.spec_adv is not None and m.spec_adv[0] > 0)
                ):
                    if g == 0:
                        v = min(spec.k + 1, m.n_tokens - len(m.generated))
                    else:
                        v = m.spec_adv[0]
                    plan[m.rid] = ("spec", v)
                    need[m.rid] = int(mgr.lengths[m.slot_ids[g]]) + v
                else:
                    plan[m.rid] = ("decode",)
                    need[m.rid] = int(mgr.lengths[m.slot_ids[g]]) + 1
            else:
                if chunk is not None:
                    # Cache the assembled stage input across chunk steps
                    # (stage 0 re-prefill would otherwise re-concatenate
                    # prompt + generated once per chunk — O(S^2/C) host
                    # copying). Reset on failover/preemption via chunk_seq.
                    if m.chunk_seq is None:
                        m.chunk_seq = self._stage_input(m, g)
                    seq = m.chunk_seq
                    pos = m.chunk_pos
                    valid = min(chunk, _seq_len(seq) - pos)
                    plan[m.rid] = ("chunk", seq, pos, valid)
                    need[m.rid] = pos + valid
                else:
                    seq = self._stage_input(m, g)
                    # Host-side [1, S] here; the exec backend's jnp.stack
                    # moves it to the device (or the remote backend ships
                    # it as-is — no device array ever enters MP dispatch).
                    inp = np.asarray(seq)[None, :] if g == 0 else seq
                    plan[m.rid] = ("whole", inp)
                    need[m.rid] = _seq_len(seq)
        served: list[Request] = []
        protected: set[int] = set()
        for m in sorted(members, key=lambda q: q.rid):
            if m.queued or m.dropped:
                continue  # preempted/dropped by an earlier member's ensure
            if sched.ensure_capacity(g, r, m, need[m.rid], protected | {m.rid}):
                served.append(m)
                protected.add(m.rid)
        if not served:
            return None

        outputs: list[tuple] = [None] * len(served)
        whole_jobs, chunk_jobs, decode_jobs, spec_jobs = [], [], [], []
        pm = self.budgets[g][r].pm
        kappa = self.pm_policy.mode(pm).kappa
        cid = self.stats.stage_calls
        self.stats.stage_calls += 1
        for i, m in enumerate(served):
            item = plan[m.rid]
            if item[0] == "decode":
                decode_jobs.append((i, m))
            elif item[0] == "spec":
                seq = None if g == 0 else m.hidden
                spec_jobs.append(
                    (i, m, seq, int(mgr.lengths[m.slot_ids[g]]), item[1])
                )
            elif item[0] == "chunk":
                chunk_jobs.append((i, m, item[1], item[2], item[3]))
            else:
                whole_jobs.append((i, m, item[1]))

        def call_args():
            return {
                "g": g, "r": r, "call": cid, "pm": pm, "kappa": kappa,
                "chunk_lanes": len(chunk_jobs), "decode_lanes": len(decode_jobs),
                "chunk_tokens": sum(job[4] for job in chunk_jobs),
                "rids": " ".join(str(m.rid) for m in served),
            }

        readbacks: list[tuple] = []
        ex = self._exec[g]
        with span("serve.call", call_args) as call_span, self._trace_mesh(r):
            if whole_jobs:
                ex.run_prefill_whole(r, whole_jobs, outputs, mgr, readbacks)
            if chunk_jobs:
                ex.run_chunks(r, chunk_jobs, outputs, mgr, readbacks)
            if spec_jobs:
                # Stage 0 drafts first (its readback precedes the verify's
                # in the call's drain order — the accept finalizer needs
                # the round's drafts already patched in).
                tok_dev = self._run_draft(r, spec_jobs, readbacks) if g == 0 else None
                ex.run_verify(r, spec_jobs, outputs, mgr, readbacks, tok_dev)
            if decode_jobs:
                ex.run_decode(r, decode_jobs, outputs, mgr, readbacks)

        self.stats.traced_calls += call_span.recorded
        self.stats.stage_executions += len(served)
        for m in served:
            m.in_call = True
        self.dispatch_log.append((g, r, t_dispatch))
        call = _StageCall(
            members=served,
            outputs=outputs,
            readbacks=readbacks,
            pm=pm,
            slots_left=kappa,
            cid=cid,
            t_dispatch=t_dispatch,
        )
        if self.async_depth == 0:
            # Legacy synchronous engine: block on the results right here,
            # inside the dispatch phase (the differential baseline).
            self._finalize(call)
        return call

    # ------------------------------------------------------------------
    # Commit
    # ------------------------------------------------------------------
    def _finalize(self, call: _StageCall) -> None:
        """Drain the call's deferred readbacks (the only host syncs)."""
        for dev, fin in call.readbacks:
            with span("serve.readback", lambda: {"call": call.cid}) as wait:
                host = self._read(dev)
            self.stats.traced_readback_s += wait.seconds
            fin(host)
        call.readbacks = []

    def _read(self, dev):
        """The host value of one deferred readback (overridable:
        mpserve's replies arrive from worker processes)."""
        return host_readback(dev)

    def _commit_call(self, g: int, call: _StageCall) -> None:
        self._finalize(call)
        for m, out in zip(call.members, call.outputs):
            self._commit(m, out, g, call.ready_slot)

    def _emit_token(self, req: Request, token: int, ready_slot: int | None = None) -> None:
        req.generated.append(token)
        now = self.stats.slots
        if req.t_first_token is None:
            # Stamped as the token lands in ``generated``, after its
            # readback: what a client polling between steps sees.
            # ``slot_first_token`` keeps the slot the device work finished.
            req.t_first_token = time.perf_counter()
            req.slot_first_token = ready_slot
            self.stats.first_token_steps += now - req.submit_slot
            self.stats.first_tokens += 1
        else:
            self.stats.token_gap_steps += now - req.slot_last_token
            self.stats.token_gaps += 1
        req.slot_last_token = now
        self.stats.tokens_generated += 1
        self.stats.accepted_tokens += 1

    def _commit(self, req: Request, out: tuple, g: int, ready_slot: int | None = None) -> None:
        """Apply a completed stage call's result to the request."""
        req.in_call = False
        kind, value, advance = out
        if kind == "spec_hidden":
            # Mid-stage verify handoff: the [1, v, D] hidden feeds the
            # next stage's verify; the round stays in flight.
            req.cache_ready[g] = True
            req.hidden = value
            self._advance(req)
            return
        if kind == "spec_done":
            req.cache_ready[g] = True
            self._finish_spec_round(req, value, advance, ready_slot)
            self._advance(req)
            return
        if req.spec_adv is not None and any(req.spec_adv):
            # A plain-path result landing mid-round means the round was
            # broken (a mid-pipeline failover re-prefill replaced it):
            # rewind the optimistic rows before committing plain state.
            self.scheduler.rewind_spec(req)
        if kind == "chunk_part":
            # Prefill continues at this stage next step; mid-pipeline
            # chunks accumulate for the downstream handoff.
            req.chunk_pos += advance
            if value is not None:
                req.chunk_outs.append(value)
            return
        if kind == "chunk_done":
            req.chunk_pos = 0
            req.chunk_seq = None
            req.cache_ready[g] = True
            if g == self.G - 1:
                self._emit_token(req, value, ready_slot)
            else:
                parts = req.chunk_outs + [value]
                req.hidden = (
                    parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
                )
            req.chunk_outs = []
            self._advance(req)
            return
        req.cache_ready[g] = True
        if kind == "token":
            self._emit_token(req, value, ready_slot)
        else:
            req.hidden = value
        self._advance(req)

    def _finish_spec_round(self, req, emit, v, ready_slot) -> None:
        """Commit a speculative round: accept the emitted prefix, rewind
        every stage's rejected tail, validate the draft mirror's accepted
        rows, update acceptance stats, and stream the tokens."""
        e = len(emit)
        self.stats.spec_rounds += 1
        self.stats.spec_proposed += v - 1
        self.stats.spec_accepted += e - 1
        for g in range(self.G):
            adv = req.spec_adv[g] if req.spec_adv is not None else 0
            if req.spec_adv is not None:
                req.spec_adv[g] = 0
            if not adv:
                continue
            slot = req.slot_ids[g] if req.slot_ids is not None else None
            if slot is None or req.replicas is None:
                continue
            mgr = self.managers[(g, req.replicas[g])]
            if mgr.slots[slot] == req.rid:
                mgr.rollback(req.rid, slot, adv - e)
        spec = self._spec
        if req.spec_drafts is not None and req.slot_ids is not None:
            # Draft rows are valid through the accepted prefix: the scan
            # wrote rows for [gen[-1], d_1..d_{k-1}] and d_j == t_j for
            # j < e, so next round's ingest starts after them.
            r0, slot0 = req.replicas[0], req.slot_ids[0]
            L = len(req.prompt) + len(req.generated) - 1
            if slot0 is not None and spec.rid[r0][slot0] == req.rid:
                spec.lens[r0][slot0] = L + min(e, spec.k)
        req.spec_drafts = None
        for t in emit:
            self._emit_token(req, t, ready_slot)

    def _advance(self, req: Request) -> None:
        req.stage += 1
        if req.stage >= self.G:
            if len(req.generated) >= req.n_tokens:
                req.done = True
                self.scheduler.release_all(req)
                self.stats.completed_jobs += 1
                return
            req.stage = 0

    # ------------------------------------------------------------------
    # Slot loop
    # ------------------------------------------------------------------
    def step(self) -> None:
        """Advance one slot (the paper's Algorithm 1 outer loop),
        producer (dispatch) before consumer (commit). The step and its
        three phases are the spans ``serve.step`` > ``serve.sched``,
        ``serve.dispatch``, ``serve.commit`` (:mod:`.readback`); as the
        step ends, its device->host sync bucket closes (a no-op unless a
        repro.analysis TransferSanitizer is active)."""
        self.stats.slots += 1
        with engine_step(self.stats.slots) as step_span:
            with engine_phase("sched"):
                self._schedule()
            with engine_phase("dispatch"):
                self._dispatch()
            with engine_phase("commit"):
                self._drain()
        self.stats.traced_steps += step_span.recorded

    def _schedule(self) -> None:
        sched = self.scheduler
        # 1) harvest + hysteresis + downtime telemetry (whole replica-slots)
        for g in range(self.G):
            for r in range(self.R):
                b = self.budgets[g][r]
                lo, hi = self.harvest[g, r]
                b.harvest(self._rng.uniform(lo, hi))
                if not b.available:
                    self.stats.downtime_replica_slots += 1

        # 2) abort in-flight rings on dead replicas; reroute their
        #    members. The ring entries' readbacks are never finalized —
        #    a dead dispatch's results are dropped, not committed.
        for (g, r), ring in self._calls.items():
            if ring and not self.budgets[g][r].alive:
                self._abort_ring(g, r)

        # 3) re-place parked / dead-replica requests, BEFORE queue
        #    admission (in-flight work must not be starved by fresh
        #    arrivals), then 4) drain the backpressure queue (FIFO).
        sched.replace_parked()
        sched.admit_pending()

    def _dispatch(self) -> None:
        # 5) producer: fill each energy-ready replica's in-flight ring.
        #    Members already in flight are excluded by select_members
        #    (in_call), so queued calls cover disjoint request sets.
        sched = self.scheduler
        for g in range(self.G):
            for r in range(self.R):
                ring = self._calls[(g, r)]
                while len(ring) < self._depth:
                    if not sched.can_start(g, r):
                        break  # power saving / energy gate: jobs held
                    members = sched.select_members(g, r)
                    if not members:
                        break
                    call = self._start_call(g, r, members)
                    if call is None:  # paged: every member deferred
                        break
                    ring.append(call)
                    self.stats.inflight_peak = max(
                        self.stats.inflight_peak, len(ring)
                    )

    def _drain(self) -> None:
        # 6) consumer: charge CE(PM)/kappa per slot per in-flight call
        #    (device-level, amortized over the batch), stamp readiness at
        #    the slot the device work completes, then drain the
        #    completion queue head-first in dispatch order.
        for (g, r), ring in self._calls.items():
            b = self.budgets[g][r]
            if not b.available:
                continue  # power saving: stage paused (jobs held, Sec. III)
            for call in ring:
                mode = self.pm_policy.mode(call.pm)
                b.charge(mode.ce / mode.kappa)
                # Energy is charged per *call* (a speculative verify costs
                # one call no matter how many tokens it commits) — the
                # per-accepted-token figure divides this by accepted_tokens.
                self.stats.energy_charged += mode.ce / mode.kappa
                call.slots_left -= 1
                if call.slots_left <= 0 and call.ready_slot is None:
                    call.ready_slot = self.stats.slots
            while ring and ring[0].slots_left <= 0:
                self._commit_call(g, ring.popleft())

    def _abort_ring(self, g: int, r: int) -> None:
        """Discard (g, r)'s in-flight ring: members reroute loss-free
        (re-prefill on a sibling), readbacks are never finalized, and
        the :meth:`_on_ring_abort` hook cleans up backend state."""
        ring = self._calls[(g, r)]
        for call in ring:
            for m in call.members:
                m.in_call = False
                self.scheduler.reroute_or_drop(m)
        ring.clear()
        self._on_ring_abort(g, r)

    # ------------------------------------------------------------------
    def fail_replica(self, g: int, r: int) -> None:
        self.budgets[g][r].fail()
        if self.elastic is not None:
            self.elastic.fail(g, r)

    def recover_replica(self, g: int, r: int) -> None:
        self.budgets[g][r].recover()
        if self.elastic is not None:
            self.elastic.rejoin(g, r)

    @property
    def queue_depth(self) -> int:
        return len(self.scheduler.pending)

    @property
    def _active(self) -> list[Request]:
        """The scheduler's resident set (shared reference)."""
        return self.scheduler.active

    @property
    def _pending(self):
        return self.scheduler.pending

    def run(
        self,
        n_slots: int,
        arrival_p: float = 0.4,
        prompt_len: int = 8,
        n_tokens: int = 4,
        vocab: int | None = None,
    ) -> ServerStats:
        vocab = vocab or self.cfg.vocab_size
        for _ in range(n_slots):
            if self._rng.uniform() < arrival_p:
                prompt = self._rng.integers(0, vocab, size=prompt_len)
                self.submit(prompt, n_tokens=n_tokens)
            self.step()
        return self.stats
