import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell on placeholder devices and record memory/cost/collective artifacts.

The two lines above MUST precede any other import (jax locks the device
count at first init). Run:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all          # every cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ..analysis.memory import memory_report  # noqa: E402
from ..configs import ARCH_NAMES, SHAPES, cells_for, get_config  # noqa: E402
from ..distributed.sharding import (  # noqa: E402
    DECODE_RULES,
    PREFILL_RULES,
    RULE_SETS,
    TRAIN_RULES,
    divisible_spec,
    param_shardings,
    use_mesh_rules,
)
from ..models import abstract_params, build_model, count_params  # noqa: E402
from ..models.inputs import ENC_LEN_DECODE, input_specs  # noqa: E402
from ..models.transformer import cache_logical_axes  # noqa: E402
from ..roofline import hw  # noqa: E402
from ..roofline.analysis import roofline_terms  # noqa: E402
from ..training import AdamWConfig, make_train_step  # noqa: E402
from ..training.train_loop import TrainState  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def _sharding(mesh, rules, shape, axes):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, divisible_spec(shape, axes, mesh, rules))


def _tree_shardings(mesh, rules, sds_tree, axes_tree):
    return jax.tree_util.tree_map(
        lambda s, a: _sharding(mesh, rules, s.shape, a),
        sds_tree,
        axes_tree,
        is_leaf=lambda v: isinstance(v, jax.ShapeDtypeStruct),
    )


def _axes_like(template):
    return jax.tree_util.tree_map(
        lambda spec: spec.axes, template, is_leaf=lambda v: hasattr(v, "axes")
    )


def model_flops(cfg, cell) -> float:
    """Analytic MODEL_FLOPS: 6*N*D (train) / 2*N*D (forward-only), with
    N = active params (MoE counts routed experts only)."""
    model = build_model(cfg)
    n = count_params(model.template)
    if cfg.is_moe:
        # Subtract inactive expert FFN params.
        plan_experts = cfg.n_experts
        active = cfg.moe_top_k
        expert_params = (
            cfg.n_layers * cfg.n_experts * (3 * cfg.d_model * cfg.d_ff_expert)
        )
        n = n - expert_params + expert_params * active / plan_experts
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    mult = 6.0 if cell.kind == "train" else 2.0
    return mult * n * tokens


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    overrides: dict | None = None,
    rules_override=None,
    hlo_path: str | None = None,
) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    # Pin the classic pod layouts — the dry-run forces 512 host devices
    # and its artifacts are calibrated to (16, 16) / (2, 16, 16).
    mesh = make_production_mesh(
        multi_pod=multi_pod, shape=(2, 16, 16) if multi_pod else (16, 16)
    )
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    if cell.kind == "train":
        rules = TRAIN_RULES
        # 70B-class models need block remat to fit the carry.
        cfg = dataclasses.replace(cfg, remat=True, remat_block=8)
    elif cell.kind == "prefill":
        rules = PREFILL_RULES
        cfg = dataclasses.replace(cfg, remat=False)
    else:
        rules = DECODE_RULES
        cfg = dataclasses.replace(cfg, remat=False)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if rules_override is not None:
        rules = rules_override

    model = build_model(cfg)
    template = model.template
    abstract = abstract_params(template, cfg.param_dtype)
    p_shardings = param_shardings(template, mesh, rules)
    batch_specs = input_specs(cfg, cell)

    def batch_shardings(specs):
        out = {}
        for k, s in specs.items():
            if k in ("tokens", "labels", "token"):
                axes = ("batch", "seq")
            elif k == "patch_embeds":
                axes = ("batch", "patches", "frontend")
            elif k == "frames":
                axes = ("batch", "act_seq", "frontend")
            elif k == "hidden":
                axes = ("batch", "act_seq", "embed")
            else:
                axes = tuple([None] * len(s.shape))
            out[k] = _sharding(mesh, rules, s.shape, axes)
        return out

    with use_mesh_rules(mesh, rules):
        if cell.kind == "train":
            step_fn = make_train_step(model, AdamWConfig())
            opt_abs = {
                "m": jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract
                ),
                "v": jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract
                ),
                "count": jax.ShapeDtypeStruct((), jnp.int32),
            }
            state_abs = TrainState(
                params=abstract, opt=opt_abs, step=jax.ShapeDtypeStruct((), jnp.int32)
            )
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            state_sh = TrainState(
                params=p_shardings,
                opt={"m": p_shardings, "v": p_shardings, "count": rep},
                step=rep,
            )
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_shardings(batch_specs)),
            ).lower(state_abs, batch_specs)
        elif cell.kind == "prefill":
            fn = lambda p, b: model.prefill(p, b, cell.seq_len + 128)
            lowered = jax.jit(
                fn, in_shardings=(p_shardings, batch_shardings(batch_specs))
            ).lower(abstract, batch_specs)
        else:  # decode
            if cfg.is_encdec:
                cache_abs = model.cache_shapes(
                    cell.global_batch, cell.seq_len + 128, ENC_LEN_DECODE
                )
                from ..models.encdec import init_cache_shapes as _  # noqa: F401

                kv_axes = ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")
                cache_axes = {
                    "len": (),
                    "k": kv_axes,
                    "v": kv_axes,
                    "ck": kv_axes,
                    "cv": kv_axes,
                }
            else:
                cache_abs = model.cache_shapes(cell.global_batch, cell.seq_len + 128)
                cache_axes = dict(cache_logical_axes(cfg))
                cache_axes = {
                    k: (
                        v
                        if k == "len"
                        else {kk: tuple(vv) for kk, vv in v.items()}
                    )
                    for k, v in cache_axes.items()
                }
            cache_sh = jax.tree_util.tree_map(
                lambda s, a: _sharding(mesh, rules, s.shape, a),
                cache_abs,
                cache_axes,
                is_leaf=lambda v: isinstance(v, jax.ShapeDtypeStruct),
            )
            # Fill len with a concrete sharding (scalar)
            fn = lambda p, t, c: model.decode_step(p, t, c)
            lowered = jax.jit(
                fn,
                in_shardings=(
                    p_shardings,
                    batch_shardings({"token": batch_specs["token"]})["token"],
                    cache_sh,
                ),
                donate_argnums=(2,),
            ).lower(abstract, batch_specs["token"], cache_abs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if hlo_path:
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo)
    # The placeholder host devices stand in for a v5e pod.
    terms, hlo_cost = roofline_terms(hlo, chips, hw.V5E)
    mf = model_flops(get_config(arch), cell)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "param_count": count_params(template),
        # Shared byte accounting with the analysis donation gate — one
        # implementation (repro.analysis.memory.memory_report).
        "memory_analysis": memory_report(compiled),
        # XLA cost_analysis (loop bodies counted ONCE — kept for reference;
        # the roofline uses the trip-scaled HLO walker, see roofline/analysis.py)
        "xla_cost_analysis": {
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": hlo_cost.collectives,
        "roofline": terms.as_dict(),
        "model_flops": mf,
        "useful_flop_ratio": mf / max(terms.flops, 1.0),
    }
    return result


def cell_path(arch: str, shape: str, multi_pod: bool, tag: str = "") -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR, f"{arch}__{shape}__{mesh}{suffix}.json")


def run_cell(
    arch: str,
    shape: str,
    *,
    multi_pod: bool,
    force: bool = False,
    tag: str = "",
    overrides: dict | None = None,
    rules_override=None,
) -> dict:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = cell_path(arch, shape, multi_pod, tag)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    try:
        result = lower_cell(
            arch,
            shape,
            multi_pod=multi_pod,
            overrides=overrides,
            rules_override=rules_override,
            hlo_path=path.replace(".json", ".hlo.gz"),
        )
        if tag:
            result["tag"] = tag
    except Exception as e:  # record failures — they are bugs to fix
        result = {
            "arch": arch,
            "shape": shape,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for variants")
    ap.add_argument(
        "--rules", choices=list(RULE_SETS), default=None,
        help="override the sharding rule set (perf variants)",
    )
    ap.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="FIELD=VALUE", help="ModelConfig override (perf variants)",
    )
    args = ap.parse_args()

    overrides: dict = {}
    for ov in args.overrides:
        key, val = ov.split("=", 1)
        if val in ("true", "false"):
            parsed = val == "true"
        else:
            try:
                parsed = int(val)
            except ValueError:
                parsed = val
        overrides[key] = parsed
    rules_override = RULE_SETS[args.rules] if args.rules else None

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in cells_for(arch):
                cells.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(
                arch, shape, multi_pod=mp, force=args.force,
                tag=args.tag, overrides=overrides or None,
                rules_override=rules_override,
            )
            mesh = r.get("mesh")
            if "error" in r:
                n_fail += 1
                print(f"[FAIL] {arch} {shape} {mesh}: {r['error']}", flush=True)
            else:
                rt = r["roofline"]
                print(
                    f"[ok] {arch} {shape} {mesh}: dominant={rt['dominant']} "
                    f"compute={rt['compute_s']:.4f}s memory={rt['memory_s']:.4f}s "
                    f"coll={rt['collective_s']:.4f}s compile={r['compile_s']}s",
                    flush=True,
                )
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
