import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
# This module (and ONLY this module) fakes the 512-chip fleet; tests and
# benchmarks see the single real CPU device.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape × mesh) cell:
  lower + compile the step function (train_step / prefill / decode_step)
  with ShapeDtypeStruct inputs (zero allocation), print memory_analysis()
  (fits-in-HBM proof) and cost_analysis() (FLOPs/bytes for §Roofline), and
  parse the post-SPMD HLO for collective bytes.

Artifacts land in artifacts/dryrun/<mesh>/<arch>__<shape>.json and feed
launch/roofline.py and benchmarks/roofline_report.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh single,multi
"""

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.models import inputs as inputs_lib
from repro.models.model import Model, model_flops, matmul_param_count, count_params_analytic
from repro.launch.hlo_cost import module_cost
from repro.launch.mesh import make_production_mesh, make_small_mesh
from repro.launch.sharding import Policy
from repro.launch.train import make_train_step
from repro.optim import adamw

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every `dtype[dims]` occurrence in an HLO type string."""
    total = 0
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective-type result-operand byte totals + op counts.

    Works on the post-optimization SPMD module, so shapes are per-device.
    Async pairs (`-start`/`-done`) are counted once, at the start op.
    """
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*?) (all-reduce|all-gather|reduce-scatter|"
                     r"all-to-all|collective-permute)(-start)?\(", line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        # group size (best effort, both replica_groups syntaxes)
        g = 0
        mg = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mg = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
            if mg:
                g = int(mg.group(2))
        rec = out.setdefault(kind, {"count": 0, "bytes": 0, "ring_bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        # ring-model per-device link bytes
        frac = (g - 1) / g if g > 1 else 1.0
        if kind == "all-reduce":
            rec["ring_bytes"] += 2 * nbytes * frac
        elif kind == "all-gather":
            rec["ring_bytes"] += nbytes * frac        # result-size based
        elif kind == "reduce-scatter":
            rec["ring_bytes"] += nbytes * g * frac if g else nbytes
        elif kind == "all-to-all":
            rec["ring_bytes"] += nbytes * frac
        else:  # collective-permute
            rec["ring_bytes"] += nbytes
    return out


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    # CPU-backend peak_memory only covers arguments; the HBM-fit proof uses
    # args + outputs + temps − donated aliases (conservative upper bound).
    out["hbm_estimate_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    out.setdefault("peak_memory_in_bytes", out["hbm_estimate_bytes"])
    return out


def build_mesh(name: str):
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    if name == "small":
        return make_small_mesh()
    raise ValueError(name)


def lower_cell(arch: str, shape_name: str, mesh, verbose: bool = True):
    """Lower + compile one cell.  Returns the artifact dict."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True, "reason": why}

    policy = Policy(cfg, mesh, shape.kind, global_batch=shape.global_batch)
    model = Model(cfg)
    key = jax.random.key(0)
    params_shapes = jax.eval_shape(model.init, key)
    param_sh = policy.param_shardings(params_shapes)

    t0 = time.monotonic()
    if shape.kind == "train":
        ctx = policy.ctx()
        opt = adamw(3e-4, keep_master=(cfg.opt_precision == "fp32"))
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        opt_sh = policy.opt_state_shardings(opt_shapes, param_sh)
        batch_shapes = inputs_lib.train_batch_shapes(
            cfg, shape.global_batch, shape.seq_len)
        batch_sh = policy.batch_shardings(batch_shapes)
        step = make_train_step(model, opt, ctx)
        state_sh = {"params": param_sh, "opt": opt_sh}
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        lowered = jitted.lower({"params": params_shapes, "opt": opt_shapes},
                               batch_shapes)
    elif shape.kind == "prefill":
        ctx = policy.ctx()
        plan = policy.decode_plan(shape.global_batch)
        batch_shapes = inputs_lib.prefill_batch_shapes(
            cfg, shape.global_batch, shape.seq_len)
        batch_sh = policy.batch_shardings(batch_shapes)

        def step(params, batch):
            return model.prefill(params, batch, ctx, cache_len=shape.seq_len)

        _, cache_shapes = jax.eval_shape(step, params_shapes, batch_shapes)
        cache_sh = policy.cache_shardings(cache_shapes, plan)
        jitted = jax.jit(step, in_shardings=(param_sh, batch_sh),
                         out_shardings=(None, cache_sh))
        lowered = jitted.lower(params_shapes, batch_shapes)
    elif shape.kind == "decode":
        ctx = policy.ctx(decode=True, batch=shape.global_batch)
        plan = ctx.decode_plan
        tokens, cache_shapes, pos = inputs_lib.decode_input_shapes(
            cfg, shape.global_batch, shape.seq_len)
        cache_sh = policy.cache_shardings(cache_shapes, plan)
        tok_sh = policy.batch_shardings({"t": tokens})["t"]

        def step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos, ctx)

        jitted = jax.jit(step,
                         in_shardings=(param_sh, cache_sh, tok_sh, None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shapes, cache_shapes, tokens, pos)
    else:
        raise ValueError(shape.kind)
    t_lower = time.monotonic() - t0

    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    cost = compiled.cost_analysis() or {}
    mem = _mem_dict(compiled)
    hlo_text = compiled.as_text()
    # loop-aware exact cost (cost_analysis counts while bodies once — see
    # launch/hlo_cost.py); both are recorded, the loop-aware one is primary.
    lc = module_cost(hlo_text, n_devices=int(mesh.size))

    art = {
        "arch": arch,
        "shape": shape_name,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "kind": shape.kind,
        "skipped": False,
        "n_devices": int(mesh.size),
        "params_total": count_params_analytic(cfg),
        "params_matmul_active": matmul_param_count(cfg),
        "model_flops": model_flops(cfg, shape),
        "hlo_flops_per_device": lc.flops,
        "hlo_bytes_per_device": lc.bytes,
        "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        "memory": mem,
        "collectives": lc.collectives,
        "collective_bytes_total": float(
            sum(c["bytes"] for c in lc.collectives.values())),
        "collective_ring_bytes": float(
            sum(c["ring_bytes"] for c in lc.collectives.values())),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {art['mesh']}: "
              f"hbm={mem['hbm_estimate_bytes']/2**30:.2f}GiB/dev "
              f"flops/dev={art['hlo_flops_per_device']:.3e} "
              f"coll={art['collective_bytes_total']/2**20:.1f}MiB "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        print("  memory_analysis:", mem)
    return art


def cell_path(mesh_name: str, arch: str, shape_name: str) -> str:
    d = os.path.abspath(os.path.join(ART_DIR, mesh_name))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def run_cells(archs, shapes, mesh_names, force: bool = False):
    results = []
    for mesh_name in mesh_names:
        mesh = build_mesh(mesh_name)
        for arch in archs:
            for shape_name in shapes:
                path = cell_path(mesh_name, arch, shape_name)
                if os.path.exists(path) and not force:
                    print(f"[dryrun] cached: {path}")
                    continue
                try:
                    art = lower_cell(arch, shape_name, mesh)
                except Exception as e:  # record failures — they are bugs
                    art = {"arch": arch, "shape": shape_name, "skipped": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[dryrun] FAIL {arch} x {shape_name} x {mesh_name}: {e}")
                art["mesh_name"] = mesh_name
                with open(path, "w") as f:
                    json.dump(art, f, indent=1)
                results.append(art)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    mesh_names = args.mesh.split(",")
    archs = ARCH_IDS if (args.all or not args.arch) else args.arch.split(",")
    shapes = list(SHAPES) if (args.all or not args.shape) else args.shape.split(",")
    arts = run_cells(archs, shapes, mesh_names, force=args.force)
    n_fail = sum(1 for a in arts if a.get("error"))
    print(f"[dryrun] done: {len(arts)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
