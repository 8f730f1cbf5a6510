import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every assigned (architecture × input shape) cell this lowers and
compiles the real step function — train_step for train shapes, prefill
for prefill shapes, decode_step for decode shapes — against the
production mesh (16×16 single-pod, 2×16×16 multi-pod), with the actual
parameter/optimizer/cache shardings, using only ShapeDtypeStructs (no
allocation).  It records, per cell:

  * memory_analysis (per-device argument/output/temp bytes — fits check),
  * cost_analysis  (per-device HLO FLOPs and bytes accessed),
  * collective bytes by op kind (parsed from the optimized HLO, scan
    trip counts folded in),

into results/dryrun.json.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--cells N]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.configs.registry import (
    cell_skip_reason,
    get_config,
    get_shape,
    list_archs,
    skipped_cells,
)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.sharding import (
    activation_sharding_ctx,
    batch_axes_for_mesh,
    param_partition_specs,
    shardings_for_tree,
)
from repro.sharding.cache_specs import (
    batch_partition_specs,
    cache_partition_specs,
    zero1_specs,
)
from repro.train.step import init_train_state, make_train_step
from repro.utils.hlo import module_costs

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results")


def _named(mesh, spec_tree):
    return shardings_for_tree(spec_tree, mesh)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               microbatches: int = 1, seq_shard: bool = False,
               extra_tags: str = ""):
    """Lower + compile one (arch × shape × mesh) cell.  Returns a record
    dict (or a skip record)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    skip = cell_skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "tags": extra_tags,
    }
    if skip:
        return {**base, "skipped": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = batch_axes_for_mesh(mesh)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    t0 = time.time()

    with mesh, activation_sharding_ctx(
            axes, seq_shard=seq_shard,
            model_size=mesh.shape.get("model", 1),
            data_size=mesh.shape.get("data", 1)):
        batch_shapes = model.input_specs(shape)
        if shape.kind == "train":
            tcfg = TrainConfig(microbatches=microbatches)
            state_shapes = jax.eval_shape(
                lambda: init_train_state(model, key, tcfg))
            pspecs = param_partition_specs(state_shapes.params, cfg, mesh)
            ospecs = zero1_specs(
                pspecs, state_shapes.opt.master, mesh, axes)
            state_specs = type(state_shapes)(
                params=pspecs,
                opt=type(state_shapes.opt)(
                    step=P(), master=ospecs, m=ospecs, v=ospecs),
                error_fb=(),
            )
            bspecs = batch_partition_specs(batch_shapes, mesh, axes)
            step = make_train_step(model, tcfg, grad_specs=ospecs)
            jf = jax.jit(
                step,
                in_shardings=(_named(mesh, state_specs),
                              _named(mesh, bspecs)),
                out_shardings=(_named(mesh, state_specs), None),
                donate_argnums=(0,),
            )
            lowered = jf.lower(state_shapes, batch_shapes)
        elif shape.kind == "prefill":
            params_shapes = jax.eval_shape(model.init, key)
            pspecs = param_partition_specs(params_shapes, cfg, mesh)
            bspecs = batch_partition_specs(batch_shapes, mesh, axes)
            jf = jax.jit(
                model.prefill,
                in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)),
            )
            lowered = jf.lower(params_shapes, batch_shapes)
        else:  # decode / long_decode
            params_shapes = jax.eval_shape(model.init, key)
            pspecs = param_partition_specs(params_shapes, cfg, mesh)
            cache_shapes = batch_shapes["cache"]
            cspecs = cache_partition_specs(cache_shapes, cfg, mesh, axes)
            tok_spec = batch_partition_specs(
                {"tokens": batch_shapes["tokens"],
                 "pos": batch_shapes["pos"]}, mesh, axes)
            jf = jax.jit(
                model.decode_step,
                in_shardings=(
                    _named(mesh, pspecs), _named(mesh, cspecs),
                    _named(mesh, tok_spec["tokens"]),
                    _named(mesh, tok_spec["pos"]),
                ),
                donate_argnums=(1,),
            )
            lowered = jf.lower(params_shapes, cache_shapes,
                               batch_shapes["tokens"], batch_shapes["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax < 0.6 returns [dict]
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    parsed = module_costs(hlo)   # trip-count-folded (utils/hlo.py)
    n_chips = 512 if multi_pod else 256

    record = {
        **base,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        # raw XLA numbers (while bodies counted once — see utils/hlo.py)
        "cost_raw": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        # trip-count-folded per-device numbers used by the roofline
        "cost": {
            "flops": parsed["flops"],
            "bytes_accessed": parsed["bytes"],
            "dot_bytes": parsed["dot_bytes"],
        },
        "collectives": parsed["collectives"],
    }
    return record


def print_record(r):
    if "skipped" in r:
        print(f"[SKIP] {r['arch']} × {r['shape']} ({r['mesh']}): "
              f"{r['skipped']}")
        return
    m = r["memory"]
    c = r["cost"]
    coll_total = sum(v["bytes"] for v in r["collectives"].values())
    print(
        f"[ OK ] {r['arch']} × {r['shape']} ({r['mesh']}): "
        f"compile={r['compile_s']:.1f}s "
        f"args/dev={m['argument_bytes'] / 2**30:.2f}GiB "
        f"temp/dev={m['temp_bytes'] / 2**30:.2f}GiB "
        f"flops/dev={c['flops']:.3e} "
        f"coll/dev={coll_total / 2**30:.3f}GiB"
    )
    sys.stdout.flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--fsdp", action="store_true",
                    help="perf flag: shard params over the data axis too")
    ap.add_argument("--moe2d", action="store_true",
                    help="perf flag: 2D (C×f) MoE dispatch layout")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="perf flag: group-local MoE dispatch (G groups)")
    ap.add_argument("--rglru-chunk", type=int, default=0,
                    help="perf flag: chunked RG-LRU associative scan")
    ap.add_argument("--rglru-block-gates", action="store_true",
                    help="perf flag: block-local RG-LRU gate matrices")
    ap.add_argument("--tags", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.sharding.flags import set_flags

    set_flags(fsdp=args.fsdp, moe_2d=args.moe2d,
              moe_groups=args.moe_groups,
              rglru_chunk=args.rglru_chunk,
              rglru_block_gates=args.rglru_block_gates,
              seq_shard=args.seq_shard)
    if not args.tags:
        auto = []
        if args.fsdp:
            auto.append("fsdp")
        if args.moe2d:
            auto.append("moe2d")
        if args.moe_groups:
            auto.append(f"moeg{args.moe_groups}")
        if args.rglru_chunk:
            auto.append(f"rglru{args.rglru_chunk}")
        if args.rglru_block_gates:
            auto.append("blockgates")
        if args.seq_shard:
            auto.append("seqshard")
        if args.microbatches > 1:
            auto.append(f"mb{args.microbatches}")
        args.tags = "+".join(auto)

    out_path = args.out or os.path.abspath(
        os.path.join(RESULTS, "dryrun.json"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    existing = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            existing = {(r["arch"], r["shape"], r["mesh"], r.get("tags", "")):
                        r for r in json.load(f)}

    if args.all:
        cells = [(a, s.name) for a in list_archs()
                 for s in __import__("repro.configs.base",
                                     fromlist=["ALL_SHAPES"]).ALL_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = list(existing.values())
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            cell_key = (arch, shape, mesh_name, args.tags)
            if cell_key in existing:
                print(f"[CACHED] {arch} × {shape} ({mesh_name})")
                continue
            try:
                r = lower_cell(arch, shape, multi_pod=mp,
                               microbatches=args.microbatches,
                               seq_shard=args.seq_shard,
                               extra_tags=args.tags)
            except Exception as e:  # record the failure — it's a bug to fix
                r = {"arch": arch, "shape": shape, "mesh": mesh_name,
                     "tags": args.tags,
                     "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {arch} × {shape} ({mesh_name}): "
                      f"{r['error'][:200]}")
                records.append(r)
                _write(out_path, records)
                continue
            print_record(r)
            records.append(r)
            _write(out_path, records)
    _write(out_path, records)


def _write(path, records):
    with open(path, "w") as f:
        json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
