#!/usr/bin/env python3
"""Readings that set the limits of a cell whose model is served on a
neighbour graph (the ``eqv2`` family): `bench/control.py`, whose
``control`` mode puts the plain reference, one precision below the
configuration's, in the place of the program's ``energy_graph``.

    python bench/control_graph.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--mode program|control]
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control as C  # noqa: E402

_planted = C.planted


@contextlib.contextmanager
def planted(cell, mode: str):
    """``control``: the served energy is the reference's, computed in
    `CONTROL_DTYPE` on the graph the pool built; other modes as
    `bench.control.planted`."""
    if mode != "control":
        with _planted(cell, mode):
            yield
        return
    program = type(cell.program.build(cell.config))
    real = program.energy_graph
    fam, model_cfg = cell.family, cell.config["model"]
    dt = C.CONTROL_DTYPE[cell.config["precision"]["matmul"]]
    program.energy_graph = lambda self, p, *a: fam.energy(p, *a, model_cfg, dt)
    try:
        yield
    finally:
        program.energy_graph = real


if __name__ == "__main__":
    C.planted = planted
    sys.exit(C.main())
