#!/usr/bin/env python3
"""One traced run of one cell, its trace kept, split by model layer and by
the serving loop's spans.

    python bench/trace_layers.py --workload <cell> --seed <n> --seconds <s> \
        --trace-dir <dir>

Runs the cell as ``bench/run.py --trace 1`` does (the same set-up, window
and reference), but keeps the profiler trace of the window in
``--trace-dir`` and reduces it with `bench.harness.scopes`: device seconds
by model layer (the ``<family>.<layer>`` scopes of the family the cell's
configuration names, the force backward and what no scope covers), idle
seconds by the innermost ``serve.*`` or benchmark span and by the pool
whose span covers it.  The last line of standard output is one JSON object;
``device_ms`` holds each layer's device milliseconds per answered
evaluation (per structure in a training cell), which add up to
``busy_ms``.  It exits 2, with no result, where the chips the cell asks for
are not there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", required=True,
                    help="where the trace is written and kept")
    return ap.parse_args(argv)


def trace_cell(cell, seed: int, seconds: float, trace_dir: str, devices,
               t_start: float,
               log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """One run of ``cell`` on ``devices`` with its window traced into
    ``trace_dir``.  -> the reductions, the numbers compared with the
    reference beside their limits, and ``correct``."""
    import jax

    from bench.harness import scopes
    from bench.harness import trace as TR
    from bench.harness.cell import Env

    os.makedirs(trace_dir, exist_ok=True)
    env = Env(seed, seconds, t_start, True, devices,
              lambda: jax.profiler.start_trace(trace_dir),
              jax.profiler.stop_trace, log)
    with cell.program.deployed(cell.config, devices):
        ctx = cell.driver.run(cell, env)
    ev = TR.load(trace_dir)
    # a trace with no chip plane (the CPU) has no device time to split
    busy = TR.reduce_events(ev) if ev.device_ops else None
    red = scopes.reduce(trace_dir, cell.config["family"])
    answered = (ctx.served.completed_in_window() if ctx.served is not None
                else ctx.train_structs)
    window_s = (ev.window[1] - ev.window[0]) * 1e-9
    idle = red["idle_by_program_span"]
    checks = {k: {"value": float(v), "limit": cell.limits.get(k)}
              for k, v in ctx.gaps.items()}
    out = {"workload": cell.name, "seed": seed, "window_s": window_s,
           "answered": answered,
           "busy_s": None if busy is None else busy["busy_s"],
           "busy_ms": None if busy is None or not answered
           else busy["busy_s"] / answered * 1e3,
           "device_ms": None, **red,
           "host_idle_share": None if idle is None else 100 * sum(
               v for k, v in idle.items()
               if k.startswith(scopes.PROGRAM_SPAN_PREFIX)) / window_s}
    if red["device_by_layer"] is not None and answered:
        out["device_ms"] = {k: v / answered * 1e3
                            for k, v in red["device_by_layer"].items()}
    if ctx.serve_metrics is not None:
        s = ctx.serve_metrics.summary()
        out.update(step_compiles=s.get("step_compiles"),
                   warmup_s=s.get("warmup_s"))
    out["correct"] = ctx.failed == 0 and bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness.cell import load_cell

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"FAIL: the cell asks for {chips} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform!r} devices",
              file=sys.stderr, flush=True)
        return 2
    out = trace_cell(cell, args.seed, args.seconds, args.trace_dir,
                     devices[:chips], T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
