"""BENCHMARK.json and the files it names: every name, file, reader and
limit that a run looks up is there and well formed; the command refuses to
run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _text(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert all(_text(w) for w in spec["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(spec):
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _text(c["why"]) and _text(c["source"])
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"] == []
        assert os.path.exists(os.path.join(ROOT, "bench", "configs",
                                           config["family"] + ".py"))


def test_workloads(spec):
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _text(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        with open(os.path.join(ROOT, "bench", "limits",
                               w["name"] + ".json")) as f:
            limits = json.load(f)
        assert limits and all(v > 0 for v in limits.values())


def test_metrics(spec):
    from bench.harness.cell import load_cell

    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _text(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for w in spec["workloads"]:
        cell = load_cell(w["name"], spec)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in got


def test_deployment_matches_the_cells(spec):
    """Each configuration's deployment covers the chips of every cell that
    runs it, and names a driver and a program module that exist."""
    from bench.harness.cell import load_cell

    for w in spec["workloads"]:
        cell = load_cell(w["name"], spec)
        assert cell.config["deployment"]["chips"] == w["chips"]
        assert callable(cell.driver.run) and callable(cell.program.build)


@pytest.mark.parametrize("deployment,program", [
    ({"chips": 1, "mesh": {}}, {"shard_data": True}),
    ({"chips": 4, "mesh": {"data": 2}}, {"shard_data": True}),
    ({"chips": 4, "mesh": {"data": 4}}, {"shard_data": True}),
])
def test_deployment_refuses_what_it_cannot_run(spec, deployment, program):
    """Row sharding without a mesh, a mesh that does not cover the chips,
    and more chips than there are devices are refused, never run on one
    device."""
    import jax

    from bench.harness.cell import load_cell

    cell = load_cell("mace3bpa.screen", spec)
    config = dict(cell.config, deployment=deployment,
                  program=dict(cell.config["program"], **program))
    with pytest.raises(ValueError):
        with cell.program.deployed(config, jax.devices()[:1]):
            pass


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mace3bpa.screen", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
