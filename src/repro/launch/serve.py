"""Serving driver: the paper's decentralized inference system.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --smoke --groups 3 --replicas 3 --policy adaptive --slots 60

Hosts G pipeline groups x R replicas of the (partitioned) model, routes
requests with the energy-aware scheduler, prints throughput/downtime.

At full width the model keeps its configured dtype (bf16 for the
registry configs); ``--smoke`` forces float32, where the tests compare
exact tokens. Parameters are made from ``--seed`` on the device, or,
with a serving mesh, directly in the shardings of the first replica
slice, so no chip ever holds a model the mesh spreads.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
from pathlib import Path

import jax

from ..configs import ARCH_NAMES, get_config, get_smoke_config
from ..distributed.sharding import SERVE_RULES, param_shardings, replica_submeshes
from ..models import build_model, init_from_template
from ..models.registry import default_draft_for
from ..serving import MPPipelineServer, PipelineServer
from .mesh import make_serving_mesh

_FP32 = {"dtype": "float32", "param_dtype": "float32"}


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
    repository root: a fixed path, because the path is part of what a
    later run must find again. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(model, seed: int, mesh=None):
    """The model's parameters from ``seed``, made where they will live.

    Without a mesh: on the default device. With a serving mesh: inside
    one jit whose outputs are sharded over the first replica slice with
    ``SERVE_RULES`` — the placement the engine gives that slice — so the
    whole model never lands on one chip.
    """
    init = functools.partial(
        init_from_template, model.template, param_dtype=model.cfg.param_dtype
    )
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return init(key)
    first_slice = replica_submeshes(mesh, 1)[0][0]
    shardings = param_shardings(model.template, first_slice, SERVE_RULES)
    return jax.jit(init, out_shardings=shardings)(key)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--groups", type=int, default=3)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument(
        "--policy", choices=["uniform", "long_term", "adaptive"], default="adaptive"
    )
    ap.add_argument("--slots", type=int, default=60)
    ap.add_argument("--max-len", type=int, default=128,
                    help="longest context (prompt + generated) a request may "
                         "reach; sizes each slot's KV reservation")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="continuous-batching slots per (group, replica)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="pending-queue bound (backpressure); None = unbounded")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: per-replica page pool + block tables "
                         "instead of a dense max_batch x max_len reservation")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV entries per page (paged mode)")
    ap.add_argument("--max-pages", type=int, default=None,
                    help="pool pages per (group, replica); default matches the "
                         "dense reservation (max_batch * ceil(max_len/page_size))")
    ap.add_argument("--kv-dtype", choices=["compute", "int8"], default="compute",
                    help="paged KV page dtype: 'compute' stores pages at the "
                         "model compute dtype; 'int8' quantizes at scatter "
                         "(per-row fp32 scales, dequantized in the page "
                         "gather) — 4x (fp32) / 2x (bf16) fewer KV bytes per "
                         "token, so the same pool admits more residents")
    ap.add_argument("--max-park-steps", type=int, default=32,
                    help="starvation-free aging: force-place (preempting the "
                         "youngest resident of a live sibling) any failover "
                         "victim parked slotless longer than this many slots; "
                         "<= 0 disables aging")
    ap.add_argument("--async-depth", type=int, default=2,
                    help="in-flight calls per (group, replica): the producer "
                         "dispatches up to this many jitted calls before the "
                         "committer drains results from the completion queue; "
                         "1 = commit-time readback without pipelining, "
                         "0 = legacy synchronous engine (readback at dispatch)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: split joining prompts into fixed "
                         "N-token chunks co-scheduled with decode (one compiled "
                         "prefill shape regardless of prompt lengths, bounded "
                         "per-step prefill work); None = whole-prompt prefill")
    ap.add_argument("--spec-draft", choices=ARCH_NAMES + ("auto",), default=None,
                    help="speculative decoding: draft architecture that "
                         "proposes spec-k tokens per round, verified in one "
                         "paged chunk call (bit-for-bit vs plain decode). "
                         "'auto' uses the registry pairing for --arch "
                         "(repro.models.registry.SPEC_DRAFT_PAIRS). "
                         "Requires --paged")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--mesh-model", type=int, default=None,
                    help="tensor-parallel width: shard each stage's params "
                         "over a 'model' mesh axis (SERVE_RULES), one jitted "
                         "dispatch lowering to collectives. Needs "
                         "mesh-model * mesh-data visible devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="replica slices of the serving mesh: replicas are "
                         "assigned round-robin to mesh-data disjoint "
                         "(1, mesh-model) submeshes — real replica sets")
    ap.add_argument("--multiprocess", action="store_true",
                    help="one OS process per (group, replica) stage cell "
                         "(dense whole-prompt mode): handoffs cross process "
                         "boundaries, process death is a live membership "
                         "leave. --mesh-model then gives each worker its own "
                         "forced-host TP mesh")
    ap.add_argument("--arrival-p", type=float, default=0.5)
    ap.add_argument("--harvest", type=float, nargs=2, default=(6.0, 10.0))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.multiprocess and (args.paged or args.prefill_chunk or args.spec_draft):
        ap.error("--multiprocess is dense whole-prompt only "
                 "(no --paged / --prefill-chunk / --spec-draft)")
    use_compile_cache()

    def config(name):
        if args.smoke:
            return dataclasses.replace(get_smoke_config(name), **_FP32)
        return get_config(name)

    common = dict(
        n_groups=args.groups,
        n_replicas=args.replicas,
        policy=args.policy,
        harvest_bounds=tuple(args.harvest),
        max_len=args.max_len,
        max_batch=args.max_batch,
        max_queue=args.max_queue,
        max_park_steps=args.max_park_steps if args.max_park_steps > 0 else None,
        async_depth=args.async_depth,
        seed=args.seed,
    )
    spec_draft = None
    if args.multiprocess:
        server = MPPipelineServer(
            {
                "arch": args.arch,
                "smoke": args.smoke,
                "overrides": _FP32 if args.smoke else {},
                "seed": args.seed,
            },
            mesh_model=args.mesh_model or 1,
            **common,
        )
    else:
        mesh = None
        if args.mesh_model is not None:
            mesh = make_serving_mesh(
                model_axis=args.mesh_model, data_axis=args.mesh_data
            )
        model = build_model(config(args.arch))
        if args.spec_draft is not None:
            name = (
                default_draft_for(args.arch) if args.spec_draft == "auto"
                else args.spec_draft
            )
            draft = build_model(config(name))
            spec_draft = (draft, init_params(draft, args.seed + 1, mesh))
        # The weights go straight in: the server slices its stages from
        # them, and a reference kept here would hold a second copy of
        # every layer on the first replica slice.
        server = PipelineServer(
            model,
            init_params(model, args.seed, mesh),
            mesh=mesh,
            paged=args.paged,
            page_size=args.page_size,
            max_pages=args.max_pages,
            kv_dtype=None if args.kv_dtype == "compute" else args.kv_dtype,
            prefill_chunk=args.prefill_chunk,
            spec_draft=spec_draft,
            spec_k=args.spec_k,
            **common,
        )
    if args.mesh_model is not None or args.multiprocess:
        print(
            f"substrate: {'multiprocess' if args.multiprocess else 'mesh'} "
            f"model_axis={args.mesh_model or 1} data_axis={args.mesh_data} "
            f"devices={jax.device_count()}"
        )
    stats = server.run(args.slots, arrival_p=args.arrival_p)
    if args.multiprocess:
        server.close()
    paged_info = (
        f" preempted={stats.preempted_jobs} peak_active={stats.peak_active}"
        if args.paged
        else ""
    )
    if spec_draft is not None:
        paged_info += (
            f" spec_rounds={stats.spec_rounds}"
            f" acceptance={stats.acceptance_rate:.3f}"
            f" accepted_tokens={stats.accepted_tokens}"
        )
    print(
        f"policy={args.policy}: submitted={stats.submitted} "
        f"completed={stats.completed_jobs} dropped={stats.dropped_jobs} "
        f"queued={stats.queued_jobs} tokens={stats.tokens_generated} "
        f"decode_calls={stats.decode_calls} "
        f"downtime={stats.downtime_fraction:.3f} "
        f"rerouted={stats.rerouted_stages}" + paged_info
    )


if __name__ == "__main__":
    main()
