import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs on the production meshes, record memory analysis,
cost analysis, and the collective schedule (EXPERIMENTS.md §Dry-run).

    PYTHONPATH=src python -m repro.launch.dryrun --arch dbrx-132b \
        --shape train_4k --mesh single --out results/dryrun.json

Skips (recorded, per DESIGN.md §Arch-applicability):
  * long_500k on pure full-attention archs (needs sub-quadratic decode)
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import SHAPES, TrainConfig, get_config
from repro.configs import ALL_LM_ARCHS, SUBQUADRATIC
from repro.distributed.sharding import batch_shardings, cache_shardings, param_shardings
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, input_specs
from repro.train import make_train_step

COLLECTIVE_RE = re.compile(
    r"=\s*\S*\s*(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
TYPE_RE = re.compile(r"(f8e4m3fn|f8e5m2|bf16|f16|f32|f64|u8|u16|u32|u64|s8|s16|s32|s64|pred)\[([0-9,]*)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "u8": 1, "s8": 1, "u16": 2, "s16": 2,
         "u32": 4, "s32": 4, "u64": 8, "s64": 8, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def parse_collectives(hlo_text: str, body_multipliers: dict[str, int]) -> dict:
    """Sum per-device collective payload bytes from compiled (post-SPMD) HLO.

    Ops inside a while-loop body computation execute once per trip; we scale
    them with `body_multipliers` {computation-name-substring: trips} (layer
    scans are the only loops in these models — see EXPERIMENTS.md §Method).
    all-reduce counts 2x (ring reduce+broadcast); others 1x payload.
    """
    per_op: dict[str, float] = {}
    total = 0.0
    comp_mult = 1
    for line in hlo_text.splitlines():
        # top-level computation definitions are unindented "name (...) -> ... {"
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split("(")[0].strip().lstrip("%")
            comp_mult = 1
            for key, mult in body_multipliers.items():
                if key in name:
                    comp_mult = mult
                    break
        cm = COLLECTIVE_RE.search(line)
        if not cm:
            continue
        kind = cm.group(1)
        types = TYPE_RE.findall(line)
        if not types:
            continue
        # payload: largest tensor named in the op line (operand or result)
        size = max(
            BYTES[t] * (np.prod([int(x) for x in dims.split(",") if x]) if dims else 1)
            for t, dims in types
        )
        factor = 2.0 if kind == "all-reduce" else 1.0
        contrib = factor * float(size) * comp_mult
        per_op[kind] = per_op.get(kind, 0.0) + contrib
        total += contrib
    return {"total_bytes": total, "by_kind": per_op}


def body_multipliers_for(cfg) -> dict[str, int]:
    """while-body trip counts for the layer scans (name -> trips)."""
    if cfg.family == "hybrid":
        stages = cfg.n_layers // cfg.attn_every
        return {"while": stages, "body": stages}  # outer scan; inner handled as x attn_every below
    return {"while": cfg.n_layers, "body": cfg.n_layers}


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool, tiny: bool = False,
                layout: str = "default") -> dict:
    cfg = get_config(arch)
    if os.environ.get("DRYRUN_KV_INT8"):
        import dataclasses as _dc

        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    if tiny:
        cfg = cfg.reduced()
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and arch not in SUBQUADRATIC and not tiny:
        return {"status": "skipped", "reason": "full-attention arch; long_500k needs "
                "sub-quadratic decode (DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.distributed.sharding import set_activation_mesh

    set_activation_mesh(mesh)
    model = build_model(cfg)
    t0 = time.time()

    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(params_sds, mesh, layout=layout)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        tcfg = TrainConfig(microbatch=int(os.environ.get("DRYRUN_MICROBATCH", "0")))
        step_fn, opt = make_train_step(model.loss, tcfg)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        o_sh = {"mu": p_sh, "nu": p_sh,
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        b_sh = batch_shardings(specs, mesh)
        lowered = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
        ).lower(params_sds, opt_sds, specs)
    elif shape.kind == "prefill":
        b_sh = batch_shardings(specs, mesh)
        cache_sds = jax.eval_shape(lambda: model.init_cache(shape.global_batch, shape.seq_len))
        c_sh = cache_shardings(cache_sds, mesh)

        def prefill_step(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        lowered = jax.jit(
            prefill_step, in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh)
        ).lower(params_sds, specs)
    else:  # decode
        c_sh = cache_shardings(specs["cache"], mesh)
        b_sh = batch_shardings({"tokens": specs["tokens"], "pos": specs["pos"]}, mesh)

        def serve_step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        lowered = jax.jit(
            serve_step,
            in_shardings=(p_sh, c_sh, b_sh["tokens"], b_sh["pos"]),
            out_shardings=(None, c_sh),
            donate_argnums=(1,),
        ).lower(params_sds, specs["cache"], specs["tokens"], specs["pos"])

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo, body_multipliers_for(cfg))
    n_dev = int(np.prod(mesh.devices.shape))
    rec = {
        "status": "ok",
        "layout": layout,
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "cost": {"flops_per_device": ca.get("flops"),
                 "bytes_per_device": ca.get("bytes accessed")},
        "collectives": coll,
        "hlo_lines": hlo.count("\n"),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--tiny", action="store_true", help="reduced configs (CI)")
    ap.add_argument("--layout", default="default",
                    help="sharding layout variant (default|dp_heavy|moe_expert_tp)")
    ap.add_argument("--resume", action="store_true", help="skip cells already in --out")
    args = ap.parse_args()
    enable_compile_cache()

    archs = ALL_LM_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if args.resume and os.path.exists(args.out):
        results = json.load(open(args.out))

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
                if args.layout != "default":
                    key += f"|{args.layout}"
                if key in results and results[key].get("status") in ("ok", "skipped"):
                    continue
                print(f"=== {key}", flush=True)
                try:
                    rec = dryrun_cell(arch, shape, mp, tiny=args.tiny, layout=args.layout)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = rec
                print(json.dumps({k: v for k, v in rec.items() if k != "trace"})[:600],
                      flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in results.values() if r.get("status") == "skipped")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"DONE ok={n_ok} skipped={n_skip} errors={n_err}")


if __name__ == "__main__":
    main()
