"""One run of one cell: set-up, the measured window, the readings, and the
comparison with the plain reference that decides ``correct``.

Everything that belongs to a cell is found by name, and the harness holds
nothing of any one cell:

- ``bench/configs/<config>.json``: sizes, precision, deployment, and the
  family it belongs to; ``bench/configs/<family>.py``: the family's plain
  reference, weights and FLOP count; ``bench/programs/<family>.py``: how
  the system under test is built and deployed for it;
- ``bench/traffic/<mix>.json``: the mix, whose ``kind`` names its driver
  ``bench/drivers/<kind>.py`` (set-up, window and reference of that kind);
- ``bench/limits/<cell>.json``: the limit of each number compared;
- ``bench/metrics/<metric>.py``: one reader per metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

__all__ = ["Cell", "Context", "Env", "load_cell", "run_cell", "make_params",
           "pairs_within", "BENCH_DIR"]

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict           # the BENCHMARK.json entry
    config: dict             # bench/configs/<config>.json
    family: object           # bench/configs/<family>.py
    program: object          # bench/programs/<family>.py
    mix: dict                # bench/traffic/<mix>.json
    driver: object           # bench/drivers/<kind>.py
    limits: dict             # bench/limits/<cell>.json ({} where none yet)
    end_to_end: list         # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def kind(self) -> str:
        return self.mix["kind"]


def _reported(metrics: list, cell: str, e2e_names=None) -> list:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m["moves"] in e2e_names:
            out.append(m)
    return out


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else _json(os.pardir, "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(BENCH_DIR, os.pardir, cfg_entry["file"])) as f:
        config = json.load(f)
    fam = config["family"]
    family = _module(os.path.join(BENCH_DIR, "configs", fam + ".py"),
                     "bench_family_" + fam)
    program = _module(os.path.join(BENCH_DIR, "programs", fam + ".py"),
                      "bench_program_" + fam)
    mix = _json("traffic", w["traffic"] + ".json")
    driver = _module(os.path.join(BENCH_DIR, "drivers", mix["kind"] + ".py"),
                     "bench_driver_" + mix["kind"])
    lim_path = os.path.join(BENCH_DIR, "limits", name + ".json")
    limits = {}
    if os.path.exists(lim_path):
        with open(lim_path) as f:
            limits = json.load(f)
    e2e = _reported(spec["end_to_end"], name)
    per_layer = _reported(spec["per_layer"], name, {m["name"] for m in e2e})
    return Cell(name, w, config, family, program, mix, driver, limits, e2e,
                per_layer)


def make_params(cell: Cell, seed: int):
    """The benchmark's weights, made on the device in one jitted call."""
    import jax

    from .traffic import rng_for

    key = jax.random.PRNGKey(int(rng_for(seed, 0).integers(0, 2 ** 31)))
    params = jax.jit(lambda k: cell.family.init_params(cell.config["model"], k))(key)
    return jax.block_until_ready(params)


def pairs_within(pos: np.ndarray, cutoff: float) -> int:
    """Ordered pairs of distinct atoms closer than ``cutoff``."""
    d = np.linalg.norm(pos[None] - pos[:, None], axis=-1)
    return int(np.sum((d < cutoff) & ~np.eye(len(pos), dtype=bool)))


# ---------------------------------------------------------------- context

@dataclasses.dataclass
class Env:
    """What a driver is handed for one run."""
    seed: int
    seconds: float
    t_start: float          # process start (perf_counter)
    trace: bool
    devices: list           # the cell's chips
    open_window: object     # call when the window opens: starts the trace
    close_window: object    # call when it closes: stops the trace
    log: object


@dataclasses.dataclass
class Context:
    """What a driver hands back, and what the metric readers read."""
    cell: Cell
    setup_s: float
    window_s: float                 # length of the measured window
    work_flops: float               # operations of the work done in it
    chips: int
    attempted: int
    failed: int
    gaps: dict                      # the numbers compared, by name
    served: object = None           # serve.Served
    serve_metrics: object = None    # ServeMetrics of the engine
    train_steps: int = 0
    train_structs: int = 0
    peak: dict | None = None        # the device's row of peaks.json
    trace: dict | None = None       # trace.reduce_events(...)
    device: dict | None = None      # the result's "device" object


def _readers(metrics):
    out = {}
    for m in metrics:
        mod = _module(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
                      "bench_metric_" + m["name"].replace(".", "_"))
        out[m["name"]] = (m, mod.read)
    return out


def device_info(devices) -> dict:
    """The result's ``device`` object; read after the window, before the
    reference runs, so that its peak is the program's."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


# -------------------------------------------------------------- the run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """One run; -> the result object (without printing it)."""
    import jax

    chips = int(cell.workload["chips"])
    devices = jax.devices()[:chips]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def open_window():
        if trace:
            jax.profiler.start_trace(trace_dir)

    def close_window():
        if trace:
            jax.profiler.stop_trace()

    env = Env(seed, seconds, t_start, trace, devices, open_window,
              close_window, log)
    try:
        with cell.program.deployed(cell.config, devices):
            ctx = cell.driver.run(cell, env)
        ctx.peak = peaks[kind]
        if trace:
            from . import trace as TR

            ctx.trace = TR.reduce_events(TR.load(trace_dir))
            ctx.device.update(busy_s=ctx.trace["busy_s"],
                              window_s=ctx.trace["window_s"])
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for name, (m, read) in _readers(wanted).items():
        v = read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": m["unit"]}
    checks = {}
    for name, value in ctx.gaps.items():
        lim = cell.limits.get(name)
        value = float(value)
        checks[name] = {"value": value if np.isfinite(value) else None,
                        "limit": None if lim is None else float(lim)}
    correct = (ctx.failed == 0 and bool(checks) and all(
        c["limit"] is not None and c["value"] is not None
        and c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": int(ctx.attempted),
              "failed": int(ctx.failed), "metrics": metrics,
              "device": ctx.device}
    if trace:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = checks
    return result
