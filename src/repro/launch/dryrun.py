import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with ShapeDtypeStruct stand-ins (no allocation).

For each cell this prints/records ``compiled.memory_analysis()`` (proves the
per-device footprint) and ``compiled.cost_analysis()`` (FLOPs/bytes for the
roofline), plus the per-collective byte counts parsed from the SPMD HLO.
Results land in ``results/dryrun/<arch>__<shape>__<mesh>.json`` and feed
EXPERIMENTS.md SDry-run / SRoofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, input_specs, shape_applicable
from repro.distributed.sharding import cache_specs, data_specs, param_specs
from repro.launch import roofline as RL
from repro.launch.mesh import HW, make_production_mesh
from repro.models.model import init_params
from repro.serve.engine import cache_shape, make_decode_step, make_prefill_step
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig, init_train_state, make_train_step

# Per-arch training memory plan: (gradient-accumulation steps, Adam moment
# dtype).  Chosen so params + moments + grads + remat'd activations fit the
# 16 GiB/chip of a v5e at train_4k (see EXPERIMENTS.md SDry-run).
TRAIN_SETTINGS: dict[str, tuple[int, str]] = {
    "gemma2-9b": (1, "float32"),
    "llama3.2-3b": (1, "float32"),
    "mistral-large-123b": (16, "bfloat16"),
    "deepseek-67b": (8, "bfloat16"),
    "rwkv6-1.6b": (1, "float32"),
    "grok-1-314b": (4, "bfloat16"),  # SPerf: accum 16->4 (expert-weight regather / accum), SP covers activations
    "qwen3-moe-235b-a22b": (4, "bfloat16"),  # SPerf: accum 16->4 (param regather / accum), SP covers activations
    "qwen2-vl-72b": (8, "bfloat16"),
    "recurrentgemma-2b": (1, "float32"),
    "hubert-xlarge": (1, "float32"),
}


def _json_memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    fields = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    return {f: int(getattr(ma, f, 0)) for f in fields}


def lower_cell(arch: str, shape_name: str, multi_pod: bool, cfg=None, accum_override: int | None = None):
    """Build (lowered, cfg, shape, mesh) for one cell.  ``cfg`` overrides the
    registry config and ``accum_override`` the accumulation steps (the
    cost-mode measurement lowers depth/accum-reduced variants)."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(cfg, shape)
    key = jax.random.PRNGKey(0)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            accum, mdt = TRAIN_SETTINGS[arch]
            # mesh-aware clamp: the microbatch must fill the data axes, or
            # each device carries multiple rows while axes idle (measured 5x
            # regression on the multipod dense trains — SPerf iteration 4)
            dp = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    dp *= mesh.shape[a]
            while accum > 1 and (shape.global_batch // accum) % dp != 0:
                accum //= 2
            accum = accum_override or accum
            opt_cfg = OptimizerConfig(moment_dtype=mdt)
            params_shape = jax.eval_shape(functools.partial(init_params, cfg=cfg), key)
            state_shape = jax.eval_shape(functools.partial(init_train_state, opt_cfg=opt_cfg), params_shape)
            p_sh = param_specs(params_shape, cfg, mesh)
            state_sh = {
                "params": p_sh,
                "opt": {
                    "mu": param_specs(state_shape["opt"]["mu"], cfg, mesh),
                    "nu": param_specs(state_shape["opt"]["nu"], cfg, mesh),
                    "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                },
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            }
            d_sh = data_specs(mesh, specs, cfg)
            step_fn = make_train_step(cfg, TrainConfig(accum_steps=accum, optimizer=opt_cfg))
            jitted = jax.jit(step_fn, in_shardings=(state_sh, d_sh))
            lowered = jitted.lower(state_shape, specs)
        elif shape.kind == "prefill":
            params_shape = jax.eval_shape(functools.partial(init_params, cfg=cfg), key)
            p_sh = param_specs(params_shape, cfg, mesh)
            d_sh = data_specs(mesh, specs, cfg)
            fn = make_prefill_step(cfg, cache_len=shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(p_sh, d_sh))
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            params_shape = jax.eval_shape(functools.partial(init_params, cfg=cfg), key)
            p_sh = param_specs(params_shape, cfg, mesh)
            if cfg.parallelism == "fsdp":
                # serving a <=3B model from FSDP shards all-gathers the whole
                # model every token (measured: llama decode collective-bound
                # at 3.6 s/step).  Replicate params for decode instead — they
                # fit HBM, and the collective term drops to ~0 (SPerf it. 7).
                p_sh = jax.tree.map(
                    lambda _: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()), p_sh
                )
            c_shape = cache_shape(cfg, shape.global_batch, shape.seq_len)
            c_sh = cache_specs(mesh, c_shape, cfg)
            d_sh = data_specs(mesh, specs, cfg)
            fn = make_decode_step(cfg)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, d_sh))
            lowered = jitted.lower(params_shape, c_shape, specs)
    return lowered, cfg, shape, mesh


def _depth_reduced(cfg, n_rep: int):
    plen = len(cfg.block_pattern)
    n_tail = cfg.num_layers % plen
    return dataclasses.replace(cfg, num_layers=plen * n_rep + n_tail)


def _extrapolate(points: dict, axes: list) -> float:
    """Multilinear extrapolation: two measurements per axis.  Costs are
    multilinear in every loop trip count (scan bodies are homogeneous), so
    iterated linear extrapolation is exact."""
    if not axes:
        return points[()]
    (_, lo, hi, full) = axes[0]
    plo = _extrapolate({k[1:]: v for k, v in points.items() if k[0] == lo}, axes[1:])
    phi = _extrapolate({k[1:]: v for k, v in points.items() if k[0] == hi}, axes[1:])
    return plo + (phi - plo) * (full - lo) / (hi - lo)


def measure_cost(arch: str, shape_name: str, multi_pod: bool) -> dict:
    """Roofline-grade cost measurement.

    XLA's cost analysis counts a while-loop body once, so the production
    (scan-based) lowering undercounts FLOPs/bytes/collectives by the trip
    counts.  Costs are MULTILINEAR in every trip count, so we lower unrolled
    (flags.cost_mode) reduced variants at two points per loop axis and
    extrapolate:

      depth axis  — 1 vs 2 pattern repetitions -> full n_rep;
      accum axis  — 2 vs 4 microbatches -> the production accumulation
                    (skipped when production accum <= 2, which lowers exact);
      chunk axis  — inner chunk scans (RWKV time chunks) unroll to a cap of
                    16 vs 32 bodies -> the true chunk count (only the
                    attention-free archs exceed the cap).
    """
    import itertools

    from repro.models import flags

    cfg_full = get_config(arch)
    shape = SHAPES[shape_name]
    plen = len(cfg_full.block_pattern)
    n_rep_full = cfg_full.num_layers // plen
    accum_full, _ = TRAIN_SETTINGS[arch]

    axes = [("depth", 1, 2, n_rep_full)]
    if shape.kind == "train" and accum_full > 2:
        axes.append(("accum", 2, 4, accum_full))
    if "rwkv" in cfg_full.block_pattern and shape.kind in ("train", "prefill"):
        from repro.models.recurrent import RWKV_CHUNK

        n_chunks = -(-shape.seq_len // RWKV_CHUNK)
        if n_chunks > 32:
            axes.append(("chunks", 16, 32, n_chunks))

    points = {}
    with flags.cost_mode():
        for combo in itertools.product(*[(a[1], a[2]) for a in axes]):
            vals = dict(zip([a[0] for a in axes], combo))
            flags.COST_CHUNK_CAP = vals.get("chunks", 32)
            try:
                lowered, *_ = lower_cell(
                    arch,
                    shape_name,
                    multi_pod,
                    cfg=_depth_reduced(cfg_full, vals["depth"]),
                    accum_override=vals.get("accum"),
                )
                compiled = lowered.compile()
            finally:
                flags.COST_CHUNK_CAP = 32
            ca = compiled.cost_analysis() or {}
            coll = RL.collective_bytes(compiled.as_text())
            points[combo] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "coll": coll,
            }

    def ext(metric):
        return _extrapolate({k: v[metric] for k, v in points.items()}, axes)

    coll_types = next(iter(points.values()))["coll"].keys()
    coll = {
        t: int(max(_extrapolate({k: v["coll"][t] for k, v in points.items()}, axes), 0.0))
        for t in coll_types
    }
    return {
        "flops_per_device": max(ext("flops"), 0.0),
        "bytes_per_device": max(ext("bytes"), 0.0),
        "collective_by_type": coll,
        "points": {str(k): {"flops": v["flops"], "bytes": v["bytes"]} for k, v in points.items()},
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str, *, verbose: bool = True) -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record.update({"status": "skipped", "reason": reason})
        _write(out_dir, record)
        return record
    try:
        t0 = time.time()
        lowered, cfg, shape, mesh = lower_cell(arch, shape_name, multi_pod)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = _json_memory(compiled)
        ca = compiled.cost_analysis() or {}
        t0 = time.time()
        cost = measure_cost(arch, shape_name, multi_pod)
        t_cost = time.time() - t0
        rf = RL.Roofline(
            flops_per_device=cost["flops_per_device"],
            bytes_per_device=cost["bytes_per_device"],
            collective_bytes_per_device=float(sum(cost["collective_by_type"].values())),
            collective_by_type=cost["collective_by_type"],
            model_flops_global=RL.model_flops(cfg, shape),
            chips=mesh.size,
        )
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] lower {t_lower:.1f}s compile {t_compile:.1f}s cost-measure {t_cost:.1f}s")
            print("  memory_analysis:", mem)
            print(f"  cost_analysis: flops/dev={rf.flops_per_device:.3e} bytes/dev={rf.bytes_per_device:.3e}")
            print(f"  collectives/dev: {rf.collective_by_type}")
            print(f"  roofline: {rf.summary()}")
        record.update(
            {
                "status": "ok",
                "chips": mesh.size,
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "memory_analysis": mem,
                "flops_per_device": rf.flops_per_device,
                "bytes_per_device": rf.bytes_per_device,
                "collective_by_type": rf.collective_by_type,
                "collective_bytes_per_device": rf.collective_bytes_per_device,
                "model_flops_global": rf.model_flops_global,
                "roofline": rf.summary(),
                "cost_points": cost["points"],
                "scan_cost_analysis": {"flops": float(ca.get("flops", 0.0)), "bytes": float(ca.get("bytes accessed", 0.0))},
            }
        )
    except Exception as e:  # a failing cell is a bug; record it loudly
        record.update({"status": "failed", "error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: {e}")
    _write(out_dir, record)
    return record


def _write(out_dir: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{record['arch']}__{record['shape']}__{record['mesh']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
                path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    if rec.get("status") in ("ok", "skipped"):
                        results.append(rec)
                        continue
                results.append(run_cell(arch, shape, mp, args.out))
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skipped")
    n_fail = sum(1 for r in results if r["status"] == "failed")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), {n_fail} FAILED")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
