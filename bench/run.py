#!/usr/bin/env python3
"""Chip benchmark of the Gaunt-MACE force field: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json`` at the checkout's
root.  A run makes its weights and traffic from ``--seed``, warms up every
shape the cell uses (set-up), measures for ``--seconds``, then checks what
the timed path produced against the plain reference.  With ``--trace 0`` it
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics and the device's busy time from a profiler trace of the window.
The last line of standard output is one JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error and the result's last key, ``checks``.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is kept in
``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, require_tpu: bool = True) -> dict:
    """Set up and run one cell; -> the result object.  Raises `NoChip`
    where the chips the cell asks for are not there."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.harness.cell import BENCH_DIR, load_cell, run_cell

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    return run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    T_START, peaks)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
