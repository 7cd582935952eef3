"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 1000 --ckpt /data/run1 [--supervise] [--mesh-data 16 ...]

Wires together: arch config, mesh + shardings, sharded jit train step,
resumable data pipeline, async checkpoints, heartbeat, SIGTERM checkpoint,
straggler monitor, and (with --supervise) restart-from-latest with backoff —
the single-binary entry a cluster scheduler would run on every host.

Recommended XLA flags for real TPU runs (collective/compute overlap — the
latency-hiding scheduler needs these; harmless elsewhere):
    --xla_tpu_enable_data_parallel_all_reduce_opt=true
    --xla_tpu_data_parallel_opt_different_sized_ops=true
    --xla_enable_async_all_gather=true
    --xla_enable_async_collective_permute=true
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import time

import numpy as np


def run_once(args) -> int:
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.config import TrainConfig, get_config
    from repro.data import LMTokenPipeline
    from repro.distributed.sharding import batch_shardings, param_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.train import train_loop

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh(data=args.mesh_data, model=args.mesh_model)
    params = model.init(jax.random.PRNGKey(args.seed))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n/1e6:.1f}M mesh={mesh.devices.shape}")

    pipe = LMTokenPipeline(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                           seed=args.seed)
    tcfg = TrainConfig(lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps,
                       checkpoint_every=args.ckpt_every, microbatch=args.microbatch,
                       log_every=args.log_every)

    shardings = None
    if np.prod(mesh.devices.shape) > 1:
        p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
        from repro.optim import adamw, cosine_schedule

        opt = adamw(cosine_schedule(tcfg.lr, tcfg.warmup_steps, tcfg.total_steps))
        o_sds = jax.eval_shape(opt.init, jax.eval_shape(lambda: params))
        o_sh = {"mu": p_sh, "nu": p_sh,
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        b_sh = batch_shardings(
            {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), np.int32),
             "labels": jax.ShapeDtypeStruct((args.batch, args.seq), np.int32)}, mesh)
        shardings = {"params": p_sh, "opt": o_sh, "batch": b_sh}

    state, hist = train_loop(
        model.loss, params, pipe, tcfg, ckpt_dir=args.ckpt, mesh=mesh,
        shardings=shardings,
        hooks={"log": lambda m: print(f"[train] step {m['step']} loss {m['loss']:.4f}"),
               "heartbeat_path": f"{args.ckpt}/heartbeat.json" if args.ckpt else None}
        if args.ckpt else {"log": lambda m: print(m)},
    )
    print(f"[train] done at step {state.step}; loss {hist[-1]['loss']:.4f}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--supervise", action="store_true",
                    help="restart from latest checkpoint on failure (backoff)")
    ap.add_argument("--max-restarts", type=int, default=5)
    args = ap.parse_args()

    if not args.supervise:
        sys.exit(run_once(args))

    # supervisor: restart the worker process on crash, resuming from ckpt
    child_args = [a for a in sys.argv[1:] if a not in ("--supervise",)]
    backoff = 2.0
    for attempt in range(args.max_restarts + 1):
        code = subprocess.call([sys.executable, "-m", "repro.launch.train", *child_args])
        if code == 0:
            sys.exit(0)
        print(f"[supervise] worker exited {code}; restart {attempt + 1} "
              f"in {backoff:.0f}s", file=sys.stderr)
        time.sleep(backoff)
        backoff = min(backoff * 2, 60)
    sys.exit(1)


if __name__ == "__main__":
    main()
