import os
# 512 virtual host devices, and never the accelerator: this driver (and
# every ``--all`` child, which inherits the environment) compiles on the
# CPU, so on a machine with a chip it can never take the chip.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. assembles ShapeDtypeStruct stand-ins for params, optimizer state,
     batch, and decode caches (no device allocation),
  3. jits the right step (train/prefill/serve) with explicit in/out
     shardings, ``.lower()``s and ``.compile()``s it,
  4. records memory_analysis(), cost_analysis(), and the collective-byte
     census parsed from the compiled HLO into results/dryrun/<cell>.json —
     the single source of truth for EXPERIMENTS.md §Dry-run/§Roofline.

``--all`` runs every cell in a fresh subprocess (compiles of 200B-class
models should not share a heap).
"""
import argparse
import json
import math
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import SHAPES, get_config, active_param_count, param_count
from repro.launch import train as train_lib
from repro.launch.mesh import make_production_mesh
from repro.models import ParallelContext, init_caches, init_params
from repro.parallel import sharding as shard_lib

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\("
)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(type_str: str) -> int:
    nbytes = 0
    for dm in _SHAPE_RE.finditer(type_str):
        dt, dims = dm.group(1), dm.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def _group_size(line: str) -> int:
    m = _GROUPS_BRACKET_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def _link_bytes(kind: str, result_bytes: int, g: int) -> float:
    """Per-device bytes over the slowest link, ring-algorithm estimates.

    all-gather: result is the gathered buffer R; ring receives R(g-1)/g.
    all-reduce: result R; ring reduce-scatter + all-gather = 2R(g-1)/g.
    reduce-scatter: result is the shard r = R/g; traffic r(g-1).
    all-to-all: result R holds 1/g local; (g-1)/g of R crosses links.
    collective-permute: the whole result hops once.
    """
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return float(result_bytes) * (g - 1)
    if kind == "collective-permute":
        return float(result_bytes)
    return float(result_bytes) * (g - 1) / g   # all-gather / all-to-all


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective census from the partitioned HLO.

    The final HLO print elides operand types, so each collective is sized
    by its RESULT type (tuple types summed); the replica-group size on the
    same line gives the ring factor for the link-byte estimate.
    """
    result: dict[str, int] = {}
    link: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        nbytes = _shape_bytes(m.group(1))
        g = _group_size(line)
        result[kind] = result.get(kind, 0) + nbytes
        link[kind] = link.get(kind, 0.0) + _link_bytes(kind, nbytes, g)
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "result_bytes": result,
        "link_bytes": {k: round(v) for k, v in link.items()},
        "counts": counts,
        "total": sum(result.values()),
        "total_link": round(sum(link.values())),
    }


def _guard_spec(spec: P, shape: tuple, mesh) -> P:
    """Drop mesh axes that don't divide the corresponding dim."""
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        sz = 1
        for a in axes:
            sz *= mesh.shape[a]
        if i < len(shape) and shape[i] % sz == 0 and shape[i] >= sz:
            out.append(ax)
        else:
            out.append(None)
    out += [None] * (len(shape) - len(out))
    return P(*out[: len(shape)])


def _shardings(tree_shapes, tree_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s, sp: NamedSharding(mesh, _guard_spec(sp, s.shape, mesh)),
        tree_shapes, tree_specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )


# ---------------------------------------------------------------------------
# input specs (deliverable: weak-type-correct, shardable, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg, shape_cfg) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    i32 = jnp.int32
    if shape_cfg.kind in ("train", "prefill"):
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        if cfg.is_encoder_decoder:
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        if cfg.mrope:
            specs["mrope_positions"] = jax.ShapeDtypeStruct((3, b, s), i32)
        if shape_cfg.kind == "prefill":
            specs.pop("labels")
        return specs
    # decode: one new token against an S-long cache
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, 1), i32),
        "pos": jax.ShapeDtypeStruct((b,), i32),
    }
    if cfg.mrope:
        specs["mrope_positions"] = jax.ShapeDtypeStruct((3, b, 1), i32)
    return specs


# §Perf variants: per-arch beyond-baseline optimizations (EXPERIMENTS.md)
VARIANTS = {
    "opt": {
        "deepseek-v2-236b": {"moe_dispatch": "all_to_all"},
        "moonshot-v1-16b-a3b": {"moe_dispatch": "all_to_all"},
        "xlstm-350m": {"prefer_pure_dp": True},
        # decode cells additionally switch the aggregated cache layout
        "_agg_layout": "bucket_major",
    },
}


def cell_config(arch: str, shape_name: str, variant: str | None = None):
    """Arch config specialized for the shape cell (DESIGN.md §5)."""
    cfg = get_config(arch)
    if shape_name == "long_500k":
        has_attention = any(
            k in ("attn", "attn_local", "attn_global", "shared_attn")
            for k in cfg.block_kinds()
        )
        if has_attention:
            # the paper's technique provides the sub-quadratic decode path
            cfg = cfg.with_(agg_kv=True)
    if variant:
        over = VARIANTS[variant].get(arch, {})
        if over:
            cfg = cfg.with_(**over)
        if cfg.agg_kv and "_agg_layout" in VARIANTS[variant]:
            cfg = cfg.with_(agg_layout=VARIANTS[variant]["_agg_layout"])
    return cfg


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             *, save: bool = True, verbose: bool = True,
             variant: str | None = None) -> dict:
    shape_cfg = SHAPES[shape_name]
    cfg = cell_config(arch, shape_name, variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    pure_dp = getattr(cfg, "prefer_pure_dp", False)
    data_axes = tuple(mesh.axis_names) if pure_dp else tuple(
        a for a in mesh.axis_names if a != "model"
    )
    parallel = ParallelContext(
        mesh=mesh, data_axes=data_axes, model_axis="model",
        use_ep=cfg.n_experts > 0, pure_dp=pure_dp,
    )

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: init_params(k, cfg), key)
    p_specs = shard_lib.param_specs(params_shape, cfg, mesh)
    p_sh = _shardings(params_shape, p_specs, mesh)
    b_specs_all = shard_lib.batch_specs(cfg, mesh, kind=shape_cfg.kind)
    batch_shape = input_specs(cfg, shape_cfg)
    b_sh = {
        k: NamedSharding(
            mesh,
            _guard_spec(
                b_specs_all.get(k, P(*([None] * len(v.shape)))),
                v.shape, mesh,
            ),
        )
        for k, v in batch_shape.items()
    }

    t0 = time.perf_counter()
    if shape_cfg.kind == "train":
        opt_cfg = optim.AdamWConfig()
        opt_shape = jax.eval_shape(optim.init_state, params_shape)
        opt_specs = optim.AdamState(step=P(), m=p_specs, v=p_specs)
        opt_sh = _shardings(opt_shape, opt_specs, mesh)
        step = train_lib.make_train_step(cfg, opt_cfg, parallel)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, opt_sh, b_sh),
            out_shardings=(p_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_shape, opt_shape, batch_shape)
    elif shape_cfg.kind == "prefill":
        step = train_lib.make_prefill_step(cfg, parallel)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(params_shape, batch_shape)
    else:  # decode
        caches_shape = jax.eval_shape(
            lambda k: init_caches(
                k, cfg, batch=shape_cfg.global_batch,
                s_max=shape_cfg.seq_len,
            ),
            key,
        )
        c_specs = shard_lib.cache_specs(caches_shape, cfg, mesh)
        c_sh = _shardings(caches_shape, c_specs, mesh)
        step = train_lib.make_serve_step(cfg, parallel)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, c_sh, b_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_shape, caches_shape, batch_shape)

    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                if hasattr(ma, attr):
                    mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in ca:
                cost[k] = float(ca[k])
        # per-memory-space bytes when present
        for k, v in ca.items():
            if k.startswith("bytes accessed"):
                cost[k] = float(v)
    except Exception as e:  # pragma: no cover
        cost["error"] = str(e)

    coll = collective_bytes(compiled.as_text())

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape_cfg.kind,
        "agg_kv": cfg.agg_kv,
        "tokens": shape_cfg.tokens,
        "params": param_count(cfg),
        "active_params": active_param_count(cfg),
        "compile_seconds": round(compile_s, 1),
        "memory": mem,
        "cost": cost,
        "collectives": coll,
    }
    if verbose:
        print(json.dumps(result, indent=2))
        print(f"memory_analysis: {mem}")
        print(f"cost_analysis: {cost}")
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        tag = "multi" if multi_pod else "single"
        if variant:
            tag = f"{tag}__{variant}"
        out = RESULTS_DIR / f"{arch}__{shape_name}__{tag}.json"
        out.write_text(json.dumps(result, indent=2))
        if verbose:
            print(f"wrote {out}")
    return result


CALIB_DIR = Path(__file__).resolve().parents[3] / "results" / "calib"


def _calib_cfg(cfg, n_units: int):
    """Reduced-depth UNROLLED config: head blocks + n_units pattern units.

    XLA's cost analysis counts while-loop bodies once, so scanned-layer
    metrics undercount depth; two unrolled depths give exact per-unit
    deltas for extrapolation (see benchmarks/roofline.py).
    """
    pat_len = len(cfg.pattern)
    kw = dict(
        n_layers=cfg.first_k_dense + pat_len * n_units,
        scan_layers=False,
    )
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = n_units
    return cfg.with_(**kw)


def effective_units(cfg) -> float:
    """Full depth in pattern units (tail blocks count fractionally)."""
    pat_len = len(cfg.pattern)
    rest = cfg.n_layers - cfg.first_k_dense
    return rest / pat_len


def run_calibration(arch: str, shape_name: str, multi_pod: bool,
                    *, save: bool = True, variant: str | None = None) -> dict:
    """Lower the cell at unrolled depths 1 and 2; record exact metrics."""
    shape_cfg = SHAPES[shape_name]
    base_cfg = cell_config(arch, shape_name, variant)
    points = {}
    for n_units in (1, 2):
        cfg = _calib_cfg(base_cfg, n_units)
        metrics = _lower_and_measure(cfg, shape_cfg, multi_pod)
        points[str(n_units)] = metrics
    m1, m2 = points["1"], points["2"]
    per_unit = {k: m2[k] - m1[k] for k in m1}
    base = {k: m1[k] - per_unit[k] for k in m1}
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "points": points,
        "per_unit": per_unit,
        "base": base,
        "effective_units": effective_units(base_cfg),
    }
    if save:
        CALIB_DIR.mkdir(parents=True, exist_ok=True)
        tag = "multi" if multi_pod else "single"
        if variant:
            tag = f"{tag}__{variant}"
        out = CALIB_DIR / f"{arch}__{shape_name}__{tag}.json"
        out.write_text(json.dumps(result, indent=2))
        print(f"wrote {out}")
    return result


def _lower_and_measure(cfg, shape_cfg, multi_pod: bool) -> dict:
    """Shared lower+compile path returning scalar metrics only."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    pure_dp = getattr(cfg, "prefer_pure_dp", False)
    data_axes = tuple(mesh.axis_names) if pure_dp else tuple(
        a for a in mesh.axis_names if a != "model"
    )
    parallel = ParallelContext(
        mesh=mesh, data_axes=data_axes, model_axis="model",
        use_ep=cfg.n_experts > 0, pure_dp=pure_dp,
    )
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: init_params(k, cfg), key)
    p_specs = shard_lib.param_specs(params_shape, cfg, mesh)
    p_sh = _shardings(params_shape, p_specs, mesh)
    b_specs_all = shard_lib.batch_specs(cfg, mesh, kind=shape_cfg.kind)
    batch_shape = input_specs(cfg, shape_cfg)
    b_sh = {
        k: NamedSharding(
            mesh,
            _guard_spec(
                b_specs_all.get(k, P(*([None] * len(v.shape)))),
                v.shape, mesh,
            ),
        )
        for k, v in batch_shape.items()
    }
    if shape_cfg.kind == "train":
        opt_cfg = optim.AdamWConfig()
        opt_shape = jax.eval_shape(optim.init_state, params_shape)
        opt_specs = optim.AdamState(step=P(), m=p_specs, v=p_specs)
        opt_sh = _shardings(opt_shape, opt_specs, mesh)
        step = train_lib.make_train_step(cfg, opt_cfg, parallel)
        jitted = jax.jit(
            step, in_shardings=(p_sh, opt_sh, b_sh),
            out_shardings=(p_sh, opt_sh, None), donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_shape, opt_shape, batch_shape)
    elif shape_cfg.kind == "prefill":
        step = train_lib.make_prefill_step(cfg, parallel)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(params_shape, batch_shape)
    else:
        caches_shape = jax.eval_shape(
            lambda k: init_caches(
                k, cfg, batch=shape_cfg.global_batch,
                s_max=shape_cfg.seq_len,
            ), key,
        )
        c_specs = shard_lib.cache_specs(caches_shape, cfg, mesh)
        c_sh = _shardings(caches_shape, c_specs, mesh)
        step = train_lib.make_serve_step(cfg, parallel)
        jitted = jax.jit(
            step, in_shardings=(p_sh, c_sh, b_sh),
            out_shardings=(None, c_sh), donate_argnums=(1,),
        )
        lowered = jitted.lower(params_shape, caches_shape, batch_shape)
    compiled = lowered.compile()
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        cost = ca
    except Exception:
        pass
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "link_bytes": float(coll.get("total_link", 0)),
    }


def run_all(meshes=("single", "multi"), archs=None, shapes=None,
            skip_existing=True):
    """Drive every cell in a fresh subprocess; resumable."""
    from repro.configs import ARCH_NAMES
    archs = archs or ARCH_NAMES
    shapes = shapes if shapes is not None else list(SHAPES)
    failures = []
    for mesh_tag in meshes:
        for arch in archs:
            for shape in shapes:
                out = RESULTS_DIR / f"{arch}__{shape}__{mesh_tag}.json"
                if skip_existing and out.exists():
                    print(f"skip {out.name} (exists)")
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh_tag,
                ]
                print(">>>", " ".join(cmd), flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mesh_tag))
                    print(f"FAIL {arch} {shape} {mesh_tag}:\n"
                          f"{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
                else:
                    print(r.stdout.strip().splitlines()[-1]
                          if r.stdout.strip() else "ok")
    print(f"\n{'='*60}\nfailures: {failures if failures else 'none'}")
    return failures


def run_all_calibration(archs=None, shapes=None, skip_existing=True):
    from repro.configs import ARCH_NAMES
    archs = archs or ARCH_NAMES
    shapes = shapes if shapes is not None else list(SHAPES)
    failures = []
    for arch in archs:
        for shape in shapes:
            out = CALIB_DIR / f"{arch}__{shape}__single.json"
            if skip_existing and out.exists():
                print(f"skip {out.name} (exists)")
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--calibrate",
            ]
            print(">>>", " ".join(cmd), flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((arch, shape))
                print(f"FAIL {arch} {shape}:\n{r.stderr[-3000:]}")
    print(f"calibration failures: {failures if failures else 'none'}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--calibrate", action="store_true")
    ap.add_argument("--variant", choices=list(VARIANTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.all and args.calibrate:
        fails = run_all_calibration(
            archs=[args.arch] if args.arch else None,
            shapes=[args.shape] if args.shape else None,
            skip_existing=not args.force,
        )
        sys.exit(1 if fails else 0)
    if args.all:
        archs = [args.arch] if args.arch else None
        shapes = [args.shape] if args.shape else None
        fails = run_all(archs=archs, shapes=shapes,
                        skip_existing=not args.force)
        sys.exit(1 if fails else 0)
    if args.calibrate:
        run_calibration(args.arch, args.shape, args.mesh == "multi",
                        variant=args.variant)
        return
    run_cell(args.arch, args.shape, args.mesh == "multi",
             variant=args.variant)


if __name__ == "__main__":
    main()
