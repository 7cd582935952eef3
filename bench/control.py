#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
faults', over many seeds in one process (the benchmark's own runs never
run this).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--mode program|control|half_batch|frozen_step] [--rate R]

``program`` runs the cell as `bench/run.py` does.  ``control`` puts the
plain reference, computed one precision below the configuration's
(`CONTROL_DTYPE`: three-pass bfloat16 for float32 at the highest matmul
precision, bfloat16 for other float32), in the program's place: the served
energy, or the training loss, is the reference's.
``half_batch`` leaves half of each training batch out (the mean is taken
over the rest); ``frozen_step`` makes the training step return its state
unchanged.  ``--rate`` replaces an open-loop mix's arrival rate, for the
sweep that finds the rate the chip sustains.  Each run prints one JSON line
with the numbers compared and the end-to-end metrics.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's matmul precision -> the reference's control dtype
CONTROL_DTYPE = {"highest": "bf16x3", "high": "bfloat16",
                 "default": "bfloat16"}


@contextlib.contextmanager
def planted(cell, mode: str):
    """The program with ``mode`` planted in it (no-op for 'program')."""
    import bench.harness.train as TN

    model_cfg = cell.config["model"]
    program = type(cell.program.build(cell.config))
    saved = {}

    def swap(obj, name, new):
        saved[(obj, name)] = getattr(obj, name)
        setattr(obj, name, new)

    if mode == "control":
        fam = cell.family
        dt = CONTROL_DTYPE[cell.config["precision"]["matmul"]]
        swap(program, "energy_masked",
             lambda self, p, s, x, m: fam.energy(p, s, x, m, model_cfg, dt))
        swap(program, "loss", lambda self, p, b: fam.loss(p, b, model_cfg, dt))
    elif mode in ("half_batch", "frozen_step"):
        real = TN.make_train_step

        def faulty(loss_fn, tcfg, optimizer=None):
            step, opt = real(loss_fn, tcfg, optimizer)

            def half(params, opt_state, batch):
                b = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(params, opt_state, b)

            def frozen(params, opt_state, batch):
                _, _, m = step(params, opt_state, batch)
                return params, opt_state, m

            return (half if mode == "half_batch" else frozen), opt

        swap(TN, "make_train_step", faulty)
    elif mode != "program":
        raise ValueError(f"unknown mode {mode!r}")
    try:
        yield
    finally:
        for (obj, name), v in saved.items():
            setattr(obj, name, v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--mode", default="program",
                    choices=("program", "control", "half_batch", "frozen_step"))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import jax

    from bench.harness.cell import BENCH_DIR, load_cell, run_cell

    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU", file=sys.stderr)
        return 2
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    cell = load_cell(args.workload)
    if args.rate is not None:
        cell.mix = dict(cell.mix, rate_per_s=args.rate)
    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(cell, args.mode):
            r = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                         peaks)
        print(json.dumps({"mode": args.mode, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "failed": r["failed"],
                          "metrics": {k: m["value"]
                                      for k, m in r["metrics"].items()},
                          "checks": {k: c["value"]
                                     for k, c in r["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
