"""The `eqv2.relax` cell at a tiny size on the CPU (the look for a chip is
skipped): a sound run comes out correct, the control (the reference one
precision below the configuration's, in the program's place) departs from
the reference, and an answer altered where the model produces it is not
correct, all under the cell's own limits.  Beside them the family's FLOP
count by hand, its weights' layout against the program's, and its float64
neighbour builder against the serving pools'.  The readings at the cell's
own size on the chip, which set the limits, are in PERF.md."""
import copy
import importlib.util
import json
import os
import time

import jax
import numpy as np
import pytest

from bench.control_graph import planted
from bench.harness.cell import load_cell, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 4243
TINY_MODEL = dict(n_blocks=1, lmax=2, mmax=1, sphere_channels=8,
                  attn_hidden_channels=4, num_heads=2, attn_alpha_channels=4,
                  attn_value_channels=2, ffn_hidden_channels=8,
                  edge_channels=8, max_neighbors=4, num_distance_basis=16,
                  grid={"theta": "gauss_legendre", "phi": "uniform",
                        "n_theta": 6, "n_phi": 6})
TINY_MIX = dict(clients=2, atoms=10, buckets=[[10, 2]], reference_sample=2)


def _family():
    path = os.path.join(HERE, os.pardir, "configs", "eqv2.py")
    spec = importlib.util.spec_from_file_location("bench_family_eqv2_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny():
    cell = load_cell("eqv2.relax")
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL)
    cell.mix = dict(copy.deepcopy(cell.mix), **TINY_MIX)
    return cell


def _run(cell):
    peaks = {jax.devices()[0].device_kind: {"flops_bf16": 1e12}}
    return run_cell(cell, SEED, 0.5, False, time.perf_counter(), peaks,
                    log=lambda s: None)


@pytest.fixture(scope="module")
def sound():
    return _run(_tiny())


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert list(sound)[-1] == "checks"
    assert set(sound["checks"]) == {"energy_gap", "force_gap"}
    assert sound["metrics"]["evals_per_s"]["value"] > 0


def test_control_departs_from_the_reference(sound):
    """Three-pass bfloat16 departs from the reference by well over what the
    program does (about 30 times in force at this size on the CPU)."""
    cell = _tiny()
    with planted(cell, "control"):
        r = _run(cell)
    gap = r["checks"]["force_gap"]["value"]
    assert gap > 10 * sound["checks"]["force_gap"]["value"]


def test_altered_answer_is_not_correct(monkeypatch):
    """Every served energy 10% off where the model produces it."""
    from repro.models.equiformer_v2 import EquiformerV2

    real = EquiformerV2.energy_graph
    monkeypatch.setattr(EquiformerV2, "energy_graph",
                        lambda self, *a: 1.1 * real(self, *a))
    r = _run(_tiny())
    assert not r["correct"]
    assert r["checks"]["energy_gap"]["value"] > r["checks"]["energy_gap"]["limit"]


def test_graph_reader_reads_the_pools_counter():
    """`graph_ms.serve`: host ms per step building graphs; None for a
    program that builds none or counts no graphs."""
    from bench.harness.cell import Context
    from repro.serve.metrics import ServeMetrics

    path = os.path.join(HERE, os.pardir, "metrics", "graph_ms.serve.py")
    spec = importlib.util.spec_from_file_location("graph_ms_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = ServeMetrics()
    ctx = Context(None, 0.0, 1.0, 0.0, 1, 1, 0, {}, serve_metrics=m)
    assert mod.read(ctx) is None                    # no step yet
    m.counters["steps"] = 4
    m.observe_graph(0.002, 1600, 6400)
    assert mod.read(ctx) == pytest.approx(0.5)

    class Old:
        counters = {"steps": 4}
    assert mod.read(Context(None, 0.0, 1.0, 0.0, 1, 1, 0, {},
                            serve_metrics=Old())) is None


TINY = dict(TINY_MODEL, n_blocks=1, lmax=1, mmax=1, sphere_channels=2,
            attn_hidden_channels=2, num_heads=1, attn_alpha_channels=2,
            attn_value_channels=2, ffn_hidden_channels=2, edge_channels=2,
            num_distance_basis=2, grid={"theta": "gauss_legendre",
                                        "phi": "uniform", "n_theta": 2,
                                        "n_phi": 3})


def test_forward_flops_by_hand():
    """L=1, M=1, 2 channels, one head: 3 atoms, 4 edges."""
    fam = _family()
    K, C, H, V, F, Ec, G, R = 4, 2, 2, 2, 2, 2, 6, 4
    d_in = 2 + 2 * Ec
    rot = 1 * 1 + 3 * 3                        # rows |m| <= 1 by degree
    radial = lambda d_out: 2 * (d_in * Ec + Ec * Ec + Ec * d_out)  # noqa: E731
    conv1 = 2 * 2 * (2 * C) * (2 * H + 2 + H) + 8 * (1 * 2 * C) * (1 * H)
    conv2 = 2 * 2 * H * (2 * V) + 8 * H * V
    so2_edge = (2 * rot * 2 * C + radial(3 * 2 * C) + R * 2 * C + conv1
                + conv2 + 2 * rot * V + K * V)
    so2_atom = 2 * K * V * C
    assert fam.so2_conv_flops(TINY, 3, 4) == 4 * so2_edge + 3 * so2_atom
    edge = 2 * 2 * R * G * H + 2 * 1 * 2 + R * V
    ffn = (2 * K * C * F + 2 * C * F + 2 * K * G * F + 6 * G * F * F
           + 2 * G * K * F + 2 * K * F * C)
    nnz = fam.gaunt_nnz(1, 1, 1)
    assert nnz == 10
    atom = 3 * 3 * K * C + ffn + C * (3 * K + 2 * nnz) + 2 * K * C * C \
        + 3 * K * C
    embed = radial(2 * C) + 2 * 4 * C + K * C
    readout = 3 * K * C + 2 * C * F + 2 * F
    want = (4 * so2_edge + 3 * so2_atom + 4 * edge + 3 * atom + 4 * embed
            + 3 * readout)
    assert fam.forward_flops(TINY, 3, 4) == want


def test_forward_flops_at_the_cells_widths():
    """About 0.44 TFLOP a forward pass for 80 atoms on 1,600 edges, most of
    it in the SO(2) attention."""
    fam = _family()
    with open(os.path.join(HERE, os.pardir, "configs",
                           "eqv2-l6m2-selfmix.json")) as f:
        cfg = json.load(f)["model"]
    total = fam.forward_flops(cfg, 80, 1600)
    assert 3.5e11 < total < 5.5e11
    assert 0.6 < fam.so2_conv_flops(cfg, 80, 1600) / total < 0.9


def test_weights_in_the_programs_layout():
    from repro.configs.gaunt_ff import equiformer_v2_tiny
    from repro.models.equiformer_v2 import EquiformerV2

    cell = _tiny()
    cfg = cell.program.program_config(cell.config)
    ref = jax.eval_shape(lambda k: _family().init_params(
        cell.config["model"], k), jax.random.PRNGKey(0))
    prog = jax.eval_shape(EquiformerV2(cfg).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(ref) == jax.tree.structure(prog)
    assert [a.shape for a in jax.tree.leaves(ref)] == \
        [a.shape for a in jax.tree.leaves(prog)]
    assert equiformer_v2_tiny.max_neighbors == cfg.max_neighbors


def test_program_config_carries_the_files_sizes():
    import dataclasses

    cell = load_cell("eqv2.relax")
    pc = dataclasses.asdict(cell.program.program_config(cell.config))
    for k, v in cell.config["model"].items():
        if k == "grid":
            assert (pc["grid_theta"], pc["grid_phi"]) == (v["n_theta"],
                                                          v["n_phi"])
        else:
            assert pc[k] == v, k


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_graph_is_the_pools_graph(seed):
    """Two builders, one graph: on a lattice with many equal distances (ties
    broken by index), with a ghost atom and a cutoff that leaves some atoms
    fewer than k neighbours."""
    from repro.serve.pools import neighbour_graph

    fam = _family()
    rng = np.random.default_rng(seed)
    pos = np.stack(np.meshgrid(*[np.arange(3.0)] * 3), -1).reshape(-1, 3)
    pos = pos[rng.permutation(len(pos))[:14]] * 1.5
    mask = np.ones(len(pos), np.float32)
    mask[rng.integers(len(pos))] = 0.0
    for cutoff, k in ((2.2, 8), (3.0, 5)):
        a = neighbour_graph(pos, mask, cutoff, k)
        b = fam.neighbours(pos, mask, cutoff, k)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
