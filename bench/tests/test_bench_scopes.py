"""The reduction of a trace to device time by model layer and idle time by
the serving loop's spans (`bench/harness/scopes.py`): on hand-made events,
on a hand-written trace that carries op paths the way a TPU trace does
(bench/tests/data/tpu_paths.xspace.txt), and the readers of the program's
warm-up counters, which report nothing for a program without them."""
import glob
import importlib.util
import os
import shutil
import types

import pytest

from bench.harness import scopes as S
from bench.harness import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
FWD = "jit(batched)/vmap(jvp(mace.{}))/mul"
BWD = "jit(batched)/vmap(transpose(jvp(mace.{})))/mul"

# window 0..100 ns, one chip.  A while loop [10,50) encloses its body: a
# conv op [12,30) and a chain op [30,45); a readout op [60,70), the
# backward of conv [70,80) and of the edge geometry [80,85), an op with no
# scope [85,90)
OPS = {DEV: [(10, 50, "while.3"), (12, 30, "fusion.1"), (30, 45, "fusion.2"),
             (60, 70, "fusion.3"), (70, 80, "fusion.4"), (80, 85, "fusion.5"),
             (85, 90, "copy.1")]}
PATHS = {DEV: ["jit(batched)/vmap(jvp(mace.conv))/while", FWD.format("conv"),
               FWD.format("chain"), FWD.format("readout"), BWD.format("conv"),
               BWD.format("edge"), "jit(batched)/neg"]}
HOST = [(0, 100, "pump"), (50, 60, "wait")]
PROGRAM = [(0, 10, "serve.admit", None), (50, 58, "serve.block", "b16"),
           (90, 98, "serve.retire", "b32")]


def test_device_time_by_layer_charges_each_stretch_once():
    by, backward = S.device_by_layer(OPS, PATHS, 0, 100, "mace")
    # the while keeps [10,12) and [45,50): 7 ns of conv
    assert by == {"conv": pytest.approx(25e-9), "chain": pytest.approx(15e-9),
                  "readout": pytest.approx(10e-9),
                  "force_backward": pytest.approx(15e-9),
                  "unscoped": pytest.approx(5e-9)}
    busy = TR.reduce_events(TR.Events(OPS, HOST, (0, 100)))["busy_s"]
    assert sum(by.values()) == pytest.approx(busy)
    assert backward == {"conv": pytest.approx(10e-9),
                        "edge": pytest.approx(5e-9)}
    # clipped to a window: only [0,40) counts
    by, _ = S.device_by_layer(OPS, PATHS, 0, 40, "mace")
    assert by == {"conv": pytest.approx(20e-9), "chain": pytest.approx(10e-9)}


def test_idle_by_program_span():
    """Each stretch of a gap goes to the innermost span covering it: a
    serve.* span inside the benchmark's pump, else the benchmark's span,
    else host:other."""
    gaps = S.idle_gaps(OPS, 0, 100)
    assert gaps == [(0, 10), (50, 60), (90, 100)]
    idle = S.idle_by_program_span(gaps, PROGRAM, HOST, 1)
    # [0,10) serve.admit; [50,60) serve.block to 58, then wait; [90,100)
    # serve.retire to 98, then pump
    assert idle == {"serve.admit": pytest.approx(10e-9),
                    "serve.block": pytest.approx(8e-9),
                    "wait": pytest.approx(2e-9),
                    "serve.retire": pytest.approx(8e-9),
                    "pump": pytest.approx(2e-9)}
    idle = S.idle_by_program_span(gaps, [(52, 54, "serve.stage", None)],
                                  [(0, 40, "pump"), (50, 60, "wait")], 1)
    assert idle == {"pump": pytest.approx(10e-9), "wait": pytest.approx(8e-9),
                    "serve.stage": pytest.approx(2e-9),
                    "host:other": pytest.approx(10e-9)}


def test_nested_program_spans():
    """A serve.* span nested in another takes what it covers; a program
    span as long as the benchmark's span around it is the inner one."""
    idle = S.idle_by_program_span(
        [(10, 90)], [(10, 90, "serve.retire", None),
                     (20, 80, "serve.stage", None)],
        [(10, 90, "pump")], 1)
    assert idle == {"serve.retire": pytest.approx(20e-9),
                    "serve.stage": pytest.approx(60e-9)}


def test_layers_are_the_scopes_of_the_cells_family():
    """The layer scopes are named by the family the configuration names:
    another family's scopes are charged by its own prefix, and a path
    that holds none of them is unscoped."""
    paths = {DEV: [p.replace("mace.", "equiformer.") for p in PATHS[DEV]]}
    assert S.device_by_layer(OPS, paths, 0, 100, "equiformer") == \
        S.device_by_layer(OPS, PATHS, 0, 100, "mace")
    by, backward = S.device_by_layer(OPS, PATHS, 0, 100, "equiformer")
    assert by == {"unscoped": pytest.approx(55e-9),
                  "force_backward": pytest.approx(15e-9)}
    assert backward == {"unscoped": pytest.approx(15e-9)}
    assert S.layer_of("jit(f)/jvp(mace.conv)/transpose(jvp(mace.chain))/mul",
                      "mace") == ("chain", True)
    # a family name is matched as it is written, not as a pattern
    assert S.layer_of("jit(f)/maceXconv/mul", "mace") == (None, False)


def test_idle_by_pool():
    """Idle charged to a pool's span goes to that pool; idle under a span
    without a pool or under the benchmark's spans goes to none."""
    gaps = S.idle_gaps(OPS, 0, 100)
    idle = S.idle_by_pool(gaps, PROGRAM, HOST, 1)
    assert idle == {"b16": pytest.approx(8e-9), "b32": pytest.approx(8e-9)}
    # two chips: the mean over them
    assert S.idle_by_pool(gaps, PROGRAM, HOST, 2) == {
        "b16": pytest.approx(4e-9), "b32": pytest.approx(4e-9)}


def test_program_without_scopes_or_spans_reduces_to_none():
    """The program before it named its work: no op path, no serve.* span."""
    assert S.device_by_layer(OPS, {}, 0, 100, "mace") == (None, None)
    assert S.device_by_layer(OPS, {DEV: [""] * 7}, 0, 100, "mace") == (
        None, None)
    assert S.idle_by_program_span([(0, 10)], [], HOST, 1) is None
    assert S.idle_by_pool([(0, 10)], [], HOST, 1) is None
    assert S.idle_by_pool([(0, 10)], [(0, 5, "serve.admit", None)], HOST,
                          1) is None


def test_charge_innermost():
    # overlapping, not nested: the later start takes the overlap
    assert S.charge_innermost([(0, 30, "a"), (20, 40, "b")]) == {"a": 20,
                                                                 "b": 20}
    # nested, and two that start together: the shorter is inner
    got = S.charge_innermost([(0, 100, "loop"), (0, 10, "x"), (50, 60, "y"),
                              (55, 58, "z")])
    assert got == {"loop": 80, "x": 10, "y": 7, "z": 3}
    assert S.charge_innermost([]) == {}


def _tpu_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "tpu_paths.xspace.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return ProfileData.from_serialized_xspace(raw), raw


def test_tpu_trace_paths_from_event_metadata():
    """Op paths as a TPU trace carries them: in the event metadata, read
    from the trace file; an op without one takes that of the op it was
    deduplicated from or of the value it moves; a parameter copy stays
    unscoped."""
    pd, raw = _tpu_trace()
    paths = S.metadata_paths(raw)[DEV]
    assert paths[next(p for p in paths if p.startswith("%fusion.10 "))] == (
        "jit(batched)/vmap(transpose(jvp(mace.conv)))/mul")
    red = S.reduce_profile(pd, raw, "mace")
    # busy [0,40) + [50,80) ns; the while keeps [0,5) and [25,40)
    assert TR.reduce_events(TR.from_profile(pd))["busy_s"] == pytest.approx(
        70e-9)
    assert red["device_by_layer"] == {"conv": pytest.approx(40e-9),
                                      "force_backward": pytest.approx(20e-9),
                                      "unscoped": pytest.approx(10e-9)}
    assert red["force_backward_by_layer"] == {"conv": pytest.approx(20e-9)}
    # idle [40,50) under serve.block; [80,82) block, [82,90) retire,
    # [90,100) pump
    assert red["idle_by_program_span"] == {
        "serve.block": pytest.approx(12e-9),
        "serve.retire": pytest.approx(8e-9), "pump": pytest.approx(10e-9)}
    # only serve.block names its pool
    assert red["idle_by_pool"] == {"b16": pytest.approx(12e-9)}


def test_reduce_reads_the_newest_trace_of_a_directory(tmp_path):
    """A trace recorded on the CPU holds the benchmark's spans and no
    device: nothing to charge, no program span."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "cpu_sample.xplane.pb"),
                d / "host.xplane.pb")
    assert S.reduce(str(tmp_path), "mace") == {
        "device_by_layer": None, "force_backward_by_layer": None,
        "idle_by_program_span": None, "idle_by_pool": None}
    with pytest.raises(FileNotFoundError):
        S.reduce(str(tmp_path / "plugins"), "mace")


def _reader(name):
    path = os.path.join(os.path.dirname(DATA), os.pardir, "metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_warmup_counter_readers():
    from repro.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.observe_warmup("b16", 2.0)
    m.observe_warmup("b32", 3.5)
    ctx = types.SimpleNamespace(serve_metrics=m)
    assert _reader("warmup_s.serve")(ctx) == 5.5
    assert _reader("step_compiles.serve")(ctx) == 0
    m.observe_step_compile("b32")
    assert _reader("step_compiles.serve")(ctx) == 1
    # a program whose metrics count neither reports nothing
    old = types.SimpleNamespace(counters={"steps": 3})
    for name in ("warmup_s.serve", "step_compiles.serve"):
        assert _reader(name)(types.SimpleNamespace(serve_metrics=old)) is None
        assert _reader(name)(types.SimpleNamespace(serve_metrics=None)) is None


def test_trace_layers_keeps_and_splits_the_trace(tmp_path):
    """`bench/trace_layers.py` runs a cell with its window traced into the
    directory it is given, keeps that trace and reduces it; on the CPU the
    trace holds the serving loop's spans and no chip plane."""
    import time

    import jax

    from bench.tests.test_bench_faults import SEED, _tiny
    from bench.trace_layers import trace_cell

    out = trace_cell(_tiny("mace3bpa.screen"), SEED, 0.5, str(tmp_path),
                     jax.devices()[:1], time.perf_counter(),
                     log=lambda s: None)
    assert out["correct"], out["checks"]
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))
    assert out["answered"] > 0 and out["window_s"] > 0
    assert out["busy_s"] is None and out["device_ms"] is None
    assert out["device_by_layer"] is None
    # no chip plane, so no idle gap to charge, but the spans were written
    assert out["idle_by_program_span"] == {} and out["idle_by_pool"] == {}
    assert out["host_idle_share"] == 0
    assert out["step_compiles"] == 0 and out["warmup_s"] > 0


def test_trace_layers_needs_the_chip():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(DATA))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/trace_layers.py",
                        "--workload", "mace3bpa.screen", "--seed", "1",
                        "--seconds", "1", "--trace-dir", "unused"],
                       cwd=os.path.dirname(root), env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert not os.path.exists(os.path.join(os.path.dirname(root), "unused"))
