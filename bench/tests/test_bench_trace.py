"""The trace-to-metrics reduction: busy union, idle share, heaviest device
operations and idle gaps labelled by the benchmark's host spans, on
hand-made events, on a hand-written trace in the profiler's format and on a
small trace recorded on the CPU (bench/tests/data)."""
import os

import pytest

from bench.harness import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev():
    # window 0..100 ns; device 0 runs [10,30) and [20,40) (overlap) and
    # [60,70); device 1 runs [0,50) only, plus an op that starts before
    # the window opens
    ops = {"/device:TPU:0": [(10, 30, "fusion.1"), (20, 40, "fusion.2"),
                             (60, 70, "fusion.1")],
           "/device:TPU:1": [(-20, 50, "convolution.3")]}
    spans = [(0, 12, "client"), (40, 58, "pump"), (45, 50, "wait"),
             (70, 100, "wait")]
    return TR.Events(ops, spans, (0, 100))


def test_union_and_busy():
    assert TR.union_length([(0, 10), (5, 20), (30, 40)]) == 30
    red = TR.reduce_events(_ev())
    # device 0: [10,40) + [60,70) = 40; device 1: [0,50) = 50; mean 45
    assert red["busy_s"] == pytest.approx(45e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(0.55)


def test_device_ops_sum_inside_the_window():
    red = TR.reduce_events(_ev())
    ops = dict(red["device_ops"])
    # per-op device time summed over devices, over the device count
    assert ops["convolution.3"] == pytest.approx(50e-9 / 2)
    assert ops["fusion.1"] == pytest.approx(30e-9 / 2)
    assert ops["fusion.2"] == pytest.approx(20e-9 / 2)
    assert [n for n, _ in red["device_ops"]][0] == "convolution.3"


def test_idle_gaps_labelled_by_host_spans():
    red = TR.reduce_events(_ev())
    gaps = red["idle_gaps"]
    # device 0 idles [0,10) client, [40,60) pump, [70,100) wait;
    # device 1 idles [50,100), mostly under wait (30 of 50 ns)
    assert gaps[0] == ["wait", pytest.approx(50e-9 / 2)]
    labels = sorted(g[0] for g in gaps)
    assert labels == ["client", "pump", "wait", "wait"]
    assert len(gaps) <= 10
    by = red["idle_by_host_span"]
    assert by["pump"] == pytest.approx(20e-9 / 2)


def test_unlabelled_gap_and_no_device_work():
    ev = TR.Events({"/device:TPU:0": [(0, 10, "a")]}, [], (0, 20))
    assert TR.reduce_events(ev)["idle_gaps"] == [["host:other",
                                                  pytest.approx(10e-9)]]
    with pytest.raises(ValueError):
        TR.reduce_events(TR.Events({"/device:TPU:0": [(30, 40, "a")]}, [],
                                   (0, 20)))


def test_trace_in_the_profilers_format():
    """A hand-written two-plane XSpace (bench/tests/data) read through
    `ProfileData`: only the device's "XLA Ops" line counts as busy."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_plane.xspace.txt")) as f:
        pd = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    ev = TR.from_profile(pd)
    assert list(ev.device_ops) == ["/device:TPU:0"]
    assert ev.window == (1000, 1100)
    red = TR.reduce_events(ev)
    # ops [10,30) [25,35) [60,70) ns after the window opens: busy 35 of 100
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["idle_gaps"] == [["wait", pytest.approx(30e-9)],
                                ["pump", pytest.approx(25e-9)],
                                ["host:other", pytest.approx(10e-9)]]
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(30e-9)


def test_recorded_cpu_trace_has_spans_and_no_device():
    """A trace recorded by `jax.profiler` on the CPU with the benchmark's
    spans: the host spans are read, and since no device plane holds an
    operation the reduction refuses it (a traced run must drive a device)."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "cpu_sample.xplane.pb"), "rb") as f:
        ev = TR.from_profile(ProfileData.from_serialized_xspace(f.read()))
    names = sorted(n for _, _, n in ev.host_spans)
    assert names == ["pump"] * 3 + ["wait"] * 3
    assert ev.window[1] > ev.window[0]
    with pytest.raises(ValueError):
        TR.reduce_events(ev)


def _xspace(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_plane_without_op_line_reads_its_programs():
    """A device plane with no "XLA Ops" line is read from "XLA Modules":
    the same busy union, named by program; a sub-plane of a chip is not a
    chip, and a device plane with neither line is refused."""
    with open(os.path.join(DATA, "two_plane.xspace.txt")) as f:
        text = f.read()
    no_ops = text.replace('name: "XLA Ops"', 'name: "Other Ops"')
    red = TR.reduce_events(TR.from_profile(_xspace(no_ops)))
    assert red["busy_s"] == pytest.approx(100e-9)
    assert red["device_ops"][0][0] == "jit_step"
    sub = text.replace('name: "/device:TPU:0"', 'name: "/device:TPU:0 SparseCore 0"')
    assert TR.from_profile(_xspace(sub)).device_ops == {}
    neither = no_ops.replace('name: "XLA Modules"', 'name: "Steps"')
    with pytest.raises(ValueError):
        TR.from_profile(_xspace(neither))


def test_tpu_op_named_by_its_instruction():
    """A TPU names an op event by its whole HLO instruction; the reduction
    keeps the instruction's name."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_plane.xspace.txt")) as f:
        text = f.read().replace(
            'name: "fusion.1"',
            'name: "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128] %p), kind=kLoop"')
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    red = TR.reduce_events(TR.from_profile(pd))
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(30e-9)
