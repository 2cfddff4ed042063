"""Multi-process serving: pipeline stages in real worker processes.

``MPPipelineServer`` keeps the whole control plane of
:class:`~repro.serving.engine.PipelineServer` — router, scheduler,
budgets, in-flight rings — and swaps the execution substrate: every
(group, replica) cell becomes a separate OS process hosting that
stage's parameters and dense slot cache. Stage handoffs (the slimmed
``[1, D]`` decode hidden, or a ``[1, S, D]`` prefill handoff) cross
process boundaries over a length-prefixed pickle pipe.

Design points:

* **No parameter shipping.** A worker rebuilds its stage
  deterministically from the model *spec* — architecture name, config
  overrides and the init seed — via ``init_from_template`` +
  ``slice_stage_params`` (through :func:`partition_model`), exactly the
  coordinator's own construction. Spawn cost is one model init, not a
  weight transfer.
* **Dispatch stays async.** ``_RemoteExec`` writes the RPC request and
  returns immediately; the reply is wrapped in a :class:`_PendingReply`
  that rides the call's deferred ``readbacks`` and is only drained at
  *commit*, exactly like the in-process engine's device readbacks. The
  dispatch phase performs no device->host sync and no pipe read, so the
  in-flight ring overlaps compute across worker processes. Replies are
  strictly FIFO per worker (single-threaded coordinator + ordered
  pipe), matching the head-first ring drain order.
* **Per-worker tensor parallelism.** ``mesh_model > 1`` gives each
  worker its own forced-host device mesh
  (``--xla_force_host_platform_device_count``) and places its stage
  params with ``SERVE_RULES`` — tensor-parallel within the process,
  pipeline handoffs between processes.
* **Real failure semantics.** ``fail_replica`` SIGKILLs the worker;
  :class:`~repro.ft.health.ProcessMonitor` turns unexpected process
  exits into the same membership-leave path (budget fail +
  ``ElasticController.fail`` -> ``Router.on_membership_change``), and
  the loss-free re-prefill failover recovers every in-flight request.
  ``recover_replica`` respawns the process; because the fresh worker's
  cache is empty, any resident still holding stage state there is
  re-placed and re-prefills.

Scope: dense whole-prompt mode only. Paged KV, chunked prefill and
speculative decoding run in-process (their substrate is shared device
memory); requesting them here raises a clear ``ValueError``. CPU
backend only: a chip belongs to one process, so on an accelerator the
constructor refuses (one process drives every chip of a host through
:class:`~repro.serving.engine.PipelineServer`).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pickle
import struct
import subprocess
import sys
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .readback import host_readback
from ..configs import get_config, get_smoke_config
from ..core.network import DeviceSpec
from ..distributed.sharding import SERVE_RULES, param_shardings
from ..ft.elastic import ElasticController
from ..ft.health import ProcessMonitor
from ..launch.mesh import make_serving_mesh
from ..models.common import init_from_template
from ..models.registry import build_model
from .engine import PipelineServer, _group_by_len
from .partition import partition_model

__all__ = [
    "MPPipelineServer",
    "StageHost",
    "WorkerHandle",
    "WorkerDied",
    "WorkerError",
    "build_from_spec",
]


class WorkerDied(RuntimeError):
    """The worker process exited (pipe EOF / broken pipe)."""


class WorkerError(RuntimeError):
    """The worker is alive but its stage execution raised."""


# ---------------------------------------------------------------------------
# Wire protocol: 8-byte little-endian length prefix + pickle payload.
# ---------------------------------------------------------------------------

_F_SETPIPE_SZ = 1031  # Linux fcntl; pipes default to 64 KiB


def _widen_pipe(f, size: int = 1 << 20) -> None:
    """Grow a pipe so one in-flight ring of [N, 1, S, D] handoffs fits
    without write-side blocking (writer and reader are one thread)."""
    try:
        import fcntl

        fcntl.fcntl(f.fileno(), _F_SETPIPE_SZ, size)
    except (ImportError, OSError, ValueError):
        pass  # non-Linux: small handoffs still fit the default buffer


def _write_msg(stream, obj) -> None:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    stream.write(struct.pack("<Q", len(data)))
    stream.write(data)
    stream.flush()


def _read_msg(stream):
    head = stream.read(8)
    if len(head) < 8:
        raise WorkerDied("pipe closed")
    (n,) = struct.unpack("<Q", head)
    data = stream.read(n)
    if len(data) < n:
        raise WorkerDied("pipe closed mid-frame")
    return pickle.loads(data)


# ---------------------------------------------------------------------------
# Model spec: the deterministic recipe both sides build from.
# ---------------------------------------------------------------------------


def build_from_spec(spec: dict):
    """(cfg, model, params) from a JSON-serializable spec.

    ``{"arch": name, "smoke": bool, "overrides": {field: value},
    "seed": int}`` — coordinator and every worker call this with the
    same spec, so stage parameters agree bit-for-bit without ever
    crossing a pipe.
    """
    arch = spec["arch"]
    cfg = get_smoke_config(arch) if spec.get("smoke", True) else get_config(arch)
    overrides = spec.get("overrides") or {}
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    params = init_from_template(
        model.template, jax.random.PRNGKey(spec.get("seed", 0)), cfg.param_dtype
    )
    return cfg, model, params


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


class StageHost:
    """One pipeline stage's execution state inside a worker process.

    Mirrors ``_DenseExec`` exactly — same jit bodies, same full-width
    masked decode assembly, same slot-indexed prefill scatter — so the
    multi-process token stream is bit-identical to the in-process one.
    Also usable in-process (tests exercise it without a subprocess).
    """

    def __init__(
        self,
        spec: dict,
        g: int,
        n_groups: int,
        max_batch: int,
        max_len: int,
        mesh_model: int = 1,
    ):
        cfg, _, params = build_from_spec(spec)
        stages = partition_model(cfg, params, n_groups)
        model_g, params_g = stages[g]
        del stages, params  # keep only this stage's weights resident
        self.g, self.G = g, n_groups
        self.last = g == n_groups - 1
        self.max_batch = max_batch
        self.max_len = max_len
        self.d_model = cfg.d_model
        self._sharding = None
        if mesh_model > 1:
            mesh = make_serving_mesh(model_axis=mesh_model)
            self._sharding = NamedSharding(mesh, PartitionSpec())
            params_g = jax.device_put(
                params_g, param_shardings(model_g.template, mesh, SERVE_RULES)
            )
        self.params = params_g

        @partial(jax.jit, donate_argnums=(2,))
        def prefill_into(params, batch, cache, slot_idx):
            out, new = model_g.prefill_batch(params, batch, max_len)
            cache = jax.tree_util.tree_map(
                lambda big, small: big.at[slot_idx].set(small), cache, new
            )
            return out, cache

        @partial(jax.jit, donate_argnums=(2,))
        def decode_masked(params, inp, cache, mask):
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
        shapes = model_g.cache_shapes(1, max_len)
        cache = jax.tree_util.tree_map(
            lambda sh: jnp.zeros((max_batch,) + tuple(sh.shape), sh.dtype), shapes
        )
        self.cache = self._place(cache)

    def _place(self, x):
        if self._sharding is None:
            return x
        return jax.device_put(x, self._sharding)

    # -- ops -------------------------------------------------------------
    def handle(self, msg: tuple) -> dict:
        op = msg[0]
        if op == "ping":
            return {"ok": True, "n_devices": jax.device_count()}
        if op == "prefill":
            return self._prefill(msg[1], msg[2])
        if op == "decode":
            return self._decode(msg[1], msg[2])
        raise ValueError(f"unknown op {op!r}")

    def _prefill(self, slots: list[int], payload: np.ndarray) -> dict:
        # payload: [N, 1, S] int32 tokens (stage 0) / [N, 1, S, D] hidden.
        key = "tokens" if self.g == 0 else "hidden"
        stacked = self._place(jnp.asarray(payload))
        out, self.cache = self.prefill_into(
            self.params, {key: stacked}, self.cache, jnp.asarray(slots, jnp.int32)
        )
        if self.last:
            toks = np.asarray(jnp.argmax(out[:, 0, -1], axis=-1))
            return {"ok": True, "tokens": toks}
        return {"ok": True, "hidden": np.asarray(out)}

    def _decode(self, slots: list[int], payload: np.ndarray) -> dict:
        # payload: [N, 1, 1] int32 tokens (stage 0) / [N, 1, 1, D] hidden.
        W = self.max_batch
        idx = np.asarray(slots, np.int32)
        mask = np.zeros((W,), bool)
        mask[idx] = True
        if self.g == 0:
            buf = np.zeros((W, 1, 1), np.int32)
            buf[idx] = payload
            inp = jnp.asarray(buf)
        else:
            hs = self._place(jnp.asarray(payload))
            inp = (
                jnp.zeros((W, 1, 1, self.d_model), hs.dtype)
                .at[jnp.asarray(idx)]
                .set(hs)
            )
        out, self.cache = self.decode_masked(
            self.params, inp, self.cache, jnp.asarray(mask)
        )
        if self.last:
            toks = np.asarray(jnp.argmax(out[:, 0, -1], axis=-1))
            return {"ok": True, "tokens": toks[idx]}
        return {"ok": True, "hidden": np.asarray(out)[idx]}


def worker_main(args) -> int:
    host = StageHost(
        json.loads(args.spec),
        args.group,
        args.n_groups,
        args.max_batch,
        args.max_len,
        mesh_model=args.mesh_model,
    )
    stdin, stdout = sys.stdin.buffer, sys.stdout.buffer
    while True:
        try:
            msg = _read_msg(stdin)
        except WorkerDied:
            return 0  # coordinator went away: exit quietly
        if msg[0] == "exit":
            return 0
        try:
            reply = host.handle(msg)
        except Exception:  # alive-but-failed: report, keep serving
            reply = {"ok": False, "error": traceback.format_exc()}
        _write_msg(stdout, reply)


# ---------------------------------------------------------------------------
# Coordinator side
# ---------------------------------------------------------------------------


class WorkerHandle:
    """Coordinator-side endpoint of one worker process."""

    def __init__(
        self,
        g: int,
        r: int,
        spec: dict,
        *,
        n_groups: int,
        max_batch: int,
        max_len: int,
        mesh_model: int = 1,
        monitor: ProcessMonitor | None = None,
    ):
        self.key = (g, r)
        self.monitor = monitor
        self.pending = 0  # requests written whose reply is still unread
        import repro

        env = dict(os.environ)
        # repro is a namespace package (__file__ is None): locate the
        # import root from __path__ so workers resolve the same tree.
        src_root = os.path.dirname(next(iter(repro.__path__)))
        env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
        if mesh_model > 1:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={mesh_model}"
            )
        # -c (not -m): runpy would re-execute this already-imported
        # module and warn about unpredictable double-init.
        cmd = [
            sys.executable,
            "-c",
            "import sys; from repro.serving.mpserve import main; "
            "sys.exit(main(sys.argv[1:]))",
            "--worker",
            "--group",
            str(g),
            "--n-groups",
            str(n_groups),
            "--max-batch",
            str(max_batch),
            "--max-len",
            str(max_len),
            "--mesh-model",
            str(mesh_model),
            "--spec",
            json.dumps(spec),
        ]
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env
        )
        _widen_pipe(self.proc.stdin)
        _widen_pipe(self.proc.stdout)

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def request(self, msg: tuple) -> None:
        """Non-blocking dispatch: write the frame, defer the reply."""
        try:
            _write_msg(self.proc.stdin, msg)
        except (BrokenPipeError, OSError) as e:
            raise WorkerDied(f"worker {self.key}: {e}") from None
        self.pending += 1

    def response(self) -> dict:
        """Blocking commit-phase read of the oldest outstanding reply."""
        reply = _read_msg(self.proc.stdout)
        self.pending -= 1
        if self.monitor is not None:
            self.monitor.beat(self.key)
        if not reply.get("ok"):
            raise WorkerError(f"worker {self.key}: {reply.get('error')}")
        return reply

    def discard_pending(self) -> None:
        """Drain replies whose calls were aborted (ring discard): the
        pipe must re-align request<->reply before any new dispatch."""
        try:
            while self.pending > 0:
                _read_msg(self.proc.stdout)
                self.pending -= 1
        except WorkerDied:
            self.pending = 0

    def kill(self) -> None:
        if self.alive:
            self.proc.kill()
        self.proc.wait()

    def close(self) -> None:
        if not self.alive:
            return
        try:
            _write_msg(self.proc.stdin, ("exit",))
            self.proc.stdin.close()
            self.proc.wait(timeout=10)
        except Exception:
            self.proc.kill()
            self.proc.wait()


class _PendingReply:
    """A deferred RPC reply riding a call's readbacks list — the remote
    analogue of an in-flight device array. Replies are FIFO per worker,
    and readbacks drain in dispatch order, so ``result`` always reads
    this request's own frame."""

    def __init__(self, worker: WorkerHandle):
        self.worker = worker

    def result(self) -> dict:
        return self.worker.response()


class _RemoteExec:
    """Execution backend proxying one stage to its worker processes.

    Same interface as ``_DenseExec``; dispatch methods write RPC frames
    and append ``(_PendingReply, finalizer)`` readbacks — no pipe read,
    no device sync in the dispatch phase.
    """

    def __init__(self, server: "MPPipelineServer", g: int):
        self.server = server
        self.g = g

    def init_cache(self, r):
        return None  # state lives in the worker

    def run_prefill_whole(self, r, jobs, outputs, mgr, readbacks):
        s, g = self.server, self.g
        w = s._workers[(g, r)]
        last = g == s.G - 1
        for length, grp in sorted(_group_by_len(jobs).items()):
            slots = [int(m.slot_ids[g]) for _, m, _ in grp]
            payload = np.stack([np.asarray(inp) for _, _, inp in grp])
            w.request(("prefill", slots, payload))
            s.stats.prefill_calls += 1
            for _, m, _ in grp:
                mgr.lengths[m.slot_ids[g]] = length
            idxs = [i for i, _, _ in grp]
            if last:

                def fin(reply, idxs=idxs):
                    for j, i in enumerate(idxs):
                        outputs[i] = ("token", int(reply["tokens"][j]), 0)

            else:

                def fin(reply, idxs=idxs):
                    for j, i in enumerate(idxs):
                        outputs[i] = ("hidden", reply["hidden"][j], 0)

            readbacks.append((_PendingReply(w), fin))

    def run_decode(self, r, jobs, outputs, mgr, readbacks):
        s, g = self.server, self.g
        w = s._workers[(g, r)]
        last = g == s.G - 1
        slots = [int(m.slot_ids[g]) for _, m in jobs]
        if g == 0:
            payload = np.asarray(
                [[[m.generated[-1]]] for _, m in jobs], np.int32
            )  # [N, 1, 1]
        else:
            # After an upstream re-prefill the handoff carries the whole
            # prefix; a caching stage only consumes the newest position.
            payload = np.stack(
                [
                    np.asarray(m.hidden if m.hidden.shape[1] == 1 else m.hidden[:, -1:])
                    for _, m in jobs
                ]
            )  # [N, 1, 1, D]
        w.request(("decode", slots, payload))
        s.stats.decode_calls += 1
        for _, m in jobs:
            mgr.lengths[m.slot_ids[g]] += 1
        idxs = [i for i, _ in jobs]
        if last:

            def fin(reply, idxs=idxs):
                for j, i in enumerate(idxs):
                    outputs[i] = ("token", int(reply["tokens"][j]), 0)

        else:

            def fin(reply, idxs=idxs):
                for j, i in enumerate(idxs):
                    outputs[i] = ("hidden", reply["hidden"][j], 0)

        readbacks.append((_PendingReply(w), fin))

    def run_chunks(self, *a, **kw):
        raise ValueError("multi-process serving: chunked prefill is in-process only")

    def run_verify(self, *a, **kw):
        raise ValueError("multi-process serving: speculative decoding is in-process only")


class MPPipelineServer(PipelineServer):
    """PipelineServer whose stages execute in real worker processes.

    ``model_spec`` replaces the ``(model, params)`` pair — both the
    coordinator (for submit-side bookkeeping and the differential
    baseline) and every worker build from it deterministically. The
    elastic controller is wired by default, so a worker death flows
    process exit -> ``ProcessMonitor`` -> ``fail_replica`` ->
    ``ElasticController.fail`` -> ``Router.on_membership_change``.
    """

    def __init__(
        self,
        model_spec: dict,
        *,
        mesh_model: int = 1,
        n_groups: int = 2,
        n_replicas: int = 2,
        **kw,
    ):
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "MPPipelineServer is the CPU harness for the worker-kill "
                "test: each worker process would open an accelerator that "
                f"this process already holds ({jax.default_backend()}). "
                "Serve on a chip with PipelineServer (mesh= for several)."
            )
        for bad in ("paged", "prefill_chunk", "spec_draft", "kv_dtype", "mesh"):
            if kw.get(bad):
                raise ValueError(
                    "multi-process serving runs dense whole-prompt stages "
                    f"only; {bad!r} is unsupported (use PipelineServer)"
                )
        self.model_spec = dict(model_spec)
        self.mesh_model = int(mesh_model)
        self.monitor = ProcessMonitor()
        self._workers: dict[tuple[int, int], WorkerHandle] = {}
        _, model, params = build_from_spec(self.model_spec)
        super().__init__(
            model, params, n_groups=n_groups, n_replicas=n_replicas, **kw
        )
        if self.elastic is None:
            specs = [
                [DeviceSpec(6, 10, self.pm_policy) for _ in range(self.R)]
                for _ in range(self.G)
            ]
            self.elastic = ElasticController(self.router, specs)
        # Surface worker import/config errors now, not at first dispatch
        # (all workers booted concurrently above — this drains in order).
        for w in self._workers.values():
            w.request(("ping",))
            w.response()

    # -- substrate -------------------------------------------------------
    def _build_exec(self):
        if self.paged or self.prefill_chunk is not None or self._spec is not None:
            raise ValueError(
                "multi-process serving runs dense whole-prompt stages only"
            )
        for g in range(self.G):
            for r in range(self.R):
                self._workers[(g, r)] = self._spawn(g, r)
        return [_RemoteExec(self, g) for g in range(self.G)]

    def _spawn(self, g: int, r: int) -> WorkerHandle:
        w = WorkerHandle(
            g,
            r,
            self.model_spec,
            n_groups=self.G,
            max_batch=self.max_batch,
            max_len=self.max_len,
            mesh_model=self.mesh_model,
            monitor=self.monitor,
        )
        self.monitor.register((g, r), w.proc)
        return w

    def _read(self, dev):
        return dev.result() if isinstance(dev, _PendingReply) else host_readback(dev)

    def _on_ring_abort(self, g: int, r: int) -> None:
        w = self._workers.get((g, r))
        if w is not None:
            w.discard_pending()

    # -- lifecycle -------------------------------------------------------
    def step(self) -> None:
        # Real-process health sweep first: a worker that exited since the
        # last slot is a membership leave — the base step's ring abort
        # then reroutes its in-flight members loss-free.
        for (g, r) in self.monitor.poll():
            if self.budgets[g][r].alive:
                self.fail_replica(g, r)
        super().step()

    def fail_replica(self, g: int, r: int) -> None:
        """Fault injection kills the real process (and the base path
        marks the budget + elastic membership)."""
        w = self._workers.get((g, r))
        if w is not None and w.alive:
            w.kill()
        super().fail_replica(g, r)
        # Abort immediately (not at the next step): a fail->recover pair
        # with no step between them must not leave doomed calls queued.
        self._abort_ring(g, r)

    def recover_replica(self, g: int, r: int) -> None:
        w = self._workers.get((g, r))
        if w is None or not w.alive:
            # The respawned worker starts with an EMPTY cache — any
            # resident still holding stage-g state on this replica must
            # re-place and re-prefill against it.
            self.scheduler.evict_stage_residents(g, r)
            self._workers[(g, r)] = self._spawn(g, r)
        super().recover_replica(g, r)

    def close(self) -> None:
        for w in self._workers.values():
            w.close()

    def __enter__(self) -> "MPPipelineServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="mpserve worker entry point")
    ap.add_argument("--worker", action="store_true", required=True)
    ap.add_argument("--group", type=int, required=True)
    ap.add_argument("--n-groups", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--spec", type=str, required=True)
    return worker_main(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
