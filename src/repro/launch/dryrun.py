import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production meshes and record memory / cost / collective
analysis for the roofline report.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax

from repro.common.config import ModelConfig, ShapeConfig, shapes_for
from repro.configs import ASSIGNED, get_config
from repro.distributed import context as dist_ctx
from repro.distributed import sharding
from repro.launch import steps as steps_lib
from repro.launch.mesh import batch_axes, make_production_mesh

REPLICATED_OK = ("pos",)

# hardware constants (TPU v5e)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "pred": 1,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str):
    """Sum output-operand bytes of every collective op in the HLO."""
    totals = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dtype, dims = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES.get(dtype, 4)
        totals[op] = totals.get(op, 0) + b
    totals["total"] = sum(totals.values())
    return totals


def _optimizer_for(cfg: ModelConfig) -> str:
    # Adam moments in f32 do not fit the 314B cell on 256 chips; use the
    # factored optimizer there (standard production practice at this
    # scale-per-chip).
    return "adafactor" if cfg.count_params() > 1e11 else "adamw"


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, multi_pod: bool):
    data_shards = mesh.shape["data"]
    ctx = dist_ctx.ParallelContext(
        mesh=mesh, batch_axes=batch_axes(mesh), model_axis="model",
        ep_axes=("data",), seq_axis=None)
    mode = "train" if shape.kind == "train" else "serve"
    p_specs = steps_lib.param_specs(cfg, data_shards)
    p_shard = sharding.params_shardings(cfg, p_specs, mesh, mode)
    batch = steps_lib.input_specs(cfg, shape)
    b_shard = sharding.input_shardings(cfg, mesh, batch)
    with dist_ctx.use(ctx), jax.set_mesh(mesh):
        if shape.kind == "train":
            optname = _optimizer_for(cfg)
            step = steps_lib.build_train_step(cfg, optname)
            o_specs = steps_lib.opt_specs(cfg, data_shards, optname)
            o_shard = sharding.params_shardings(
                cfg, o_specs, mesh, mode)
            fn = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_specs, o_specs, batch)
        elif shape.kind == "prefill":
            step = steps_lib.build_prefill_step(cfg, shape)
            fn = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = fn.lower(p_specs, batch)
        else:
            step = steps_lib.build_serve_step(cfg)
            c_specs = steps_lib.cache_specs(cfg, shape)
            seq_par = shape.global_batch < data_shards
            c_shard = sharding.cache_shardings(cfg, mesh, c_specs, seq_par)
            fn = jax.jit(step,
                         in_shardings=(p_shard, c_shard, b_shard),
                         donate_argnums=(1,))
            lowered = fn.lower(p_specs, c_specs, batch)
        compiled = lowered.compile()
    return lowered, compiled


def analyse(cfg: ModelConfig, shape: ShapeConfig, mesh, lowered, compiled,
            multi_pod: bool):
    n_chips = mesh.devices.size
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    # cost_analysis() reports the PER-DEVICE SPMD program (verified against
    # an analytic sharded matmul), so roofline terms divide by per-chip
    # peak numbers, not by (chips x peak).
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    n = cfg.count_params()
    n_active = cfg.count_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = {"train": 6, "prefill": 2, "decode": 2}[shape.kind]
    model_flops = mult * n_active * tokens
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_hbm / HBM_BW
    t_coll = coll["total"] / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    out = {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": int(n_chips),
        "params": int(n), "active_params": int(n_active),
        "argument_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes_per_device": int(
            getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes_per_device": int(
            getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)),
        "hlo_flops_per_device": flops, "hlo_bytes_per_device": bytes_hbm,
        "collective_bytes": coll,
        "model_flops_global": model_flops,
        "useful_flop_ratio": (model_flops / n_chips) / flops
        if flops else 0.0,
        **terms,
        "dominant": dominant,
    }
    return out


def _shallow(cfg: ModelConfig, mult: int,
             shape: ShapeConfig) -> ModelConfig:
    n = cfg.period * mult + (1 if cfg.dense_first_layer else 0)
    changes = dict(n_layers=n, scan_unroll=True)
    if cfg.mamba is not None and shape.kind != "decode":
        # keep the unrolled chunk count bounded (compile time): the chunk
        # size doesn't change flops, only transient memory
        changes["mamba"] = dataclasses.replace(
            cfg.mamba, chunk=max(cfg.mamba.chunk, shape.seq_len // 8))
    return dataclasses.replace(cfg, **changes)


def _cost_of(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            collective_bytes(compiled.as_text()))


def extrapolate_costs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      multi_pod: bool):
    """XLA counts a while-loop body once regardless of trip count, so the
    scanned layer stack's flops/bytes/collectives are invisible to
    cost_analysis.  Compile the model at 1 and 2 periods with every scan
    unrolled, then extrapolate linearly to full depth."""
    f, b, c = [], [], []
    for mult in (1, 2):
        _, comp = lower_cell(_shallow(cfg, mult, shape), shape, mesh,
                             multi_pod)
        fi, bi, ci = _cost_of(comp)
        f.append(fi)
        b.append(bi)
        c.append(ci)
    n = cfg.n_periods
    flops = max(f[0] + (f[1] - f[0]) * (n - 1), f[1])
    bytes_ = max(b[0] + (b[1] - b[0]) * (n - 1), b[1])
    coll = {}
    for k in set(c[0]) | set(c[1]):
        v0, v1 = c[0].get(k, 0), c[1].get(k, 0)
        coll[k] = max(int(v0 + (v1 - v0) * (n - 1)), v1)
    return flops, bytes_, coll


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             verbose: bool = True, analysis: bool = True):
    cfg = get_config(arch)
    shape = {s.name: s for s in shapes_for(cfg)}.get(shape_name)
    if shape is None:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": "long_500k requires sub-quadratic attention"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, compiled = lower_cell(cfg, shape, mesh, multi_pod)
    result = analyse(cfg, shape, mesh, lowered, compiled, multi_pod)
    if analysis:
        flops, bytes_, coll = extrapolate_costs(cfg, shape, mesh, multi_pod)
        n_chips = mesh.devices.size
        result.update({
            "hlo_flops_per_device": flops,
            "hlo_bytes_per_device": bytes_,
            "collective_bytes": coll,
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_ / HBM_BW,
            "collective_s": coll["total"] / ICI_BW,
            "useful_flop_ratio": (result["model_flops_global"] / n_chips)
            / flops if flops else 0.0,
        })
        terms = {k: result[k] for k in ("compute_s", "memory_s",
                                        "collective_s")}
        result["dominant"] = max(terms, key=terms.get)
    result["compile_s"] = time.time() - t0
    if verbose:
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print({k: ca[k] for k in ("flops", "bytes accessed")
               if k in ca})
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape.name}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    if verbose:
        print(f"[{result['mesh']}] {arch} x {shape.name}: "
              f"peak/dev={result['peak_bytes_per_device']/2**30:.2f}GiB "
              f"compute={result['compute_s']*1e3:.2f}ms "
              f"memory={result['memory_s']*1e3:.2f}ms "
              f"coll={result['collective_s']*1e3:.2f}ms "
              f"dom={result['dominant']} "
              f"useful={result['useful_flop_ratio']:.2f} "
              f"({result['compile_s']:.0f}s compile)", flush=True)
    return result


ALL_SHAPE_NAMES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    archs = list(ASSIGNED) if args.all or not args.arch else [args.arch]
    shapes = ALL_SHAPE_NAMES if args.all or not args.shape \
        else [args.shape]
    failures = []
    for mp in meshes:
        sub = os.path.join(args.out, "multi" if mp else "single")
        for arch in archs:
            cfg = get_config(arch)
            valid = {s.name for s in shapes_for(cfg)}
            for sh in shapes:
                if sh not in valid:
                    if sh in ALL_SHAPE_NAMES:
                        print(f"[skip] {arch} x {sh}: requires "
                              "sub-quadratic attention", flush=True)
                    continue
                key = os.path.join(sub, f"{arch}__{sh}.json")
                if os.path.exists(key):
                    print(f"[cached] {arch} x {sh}", flush=True)
                    continue
                try:
                    # roofline analysis (extrapolation compiles) only on
                    # the single-pod mesh; multi-pod proves the "pod" axis
                    # shards and fits.
                    run_cell(arch, sh, mp, sub, analysis=not mp)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, sh, mp, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
