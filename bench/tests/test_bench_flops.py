"""The benchmark's FLOP count: hand-checked at a tiny configuration, and
blind to which backend the program would use."""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _family():
    path = os.path.join(HERE, os.pardir, "configs", "mace.py")
    spec = importlib.util.spec_from_file_location("bench_family_mace_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = dict(L=1, L_edge=1, channels=2, n_layers=1, nu=2, n_species=2,
            cutoff=5.0, n_radial=2, hidden=2)


def test_gaunt_nnz_by_hand():
    fam = _family()
    # degrees <= 1 times degrees <= 1, projected to <= 1: Y0*Y0->Y0 (1),
    # Y0*Y1m->Y1m (3), Y1m*Y0->Y1m (3), Y1m*Y1m->Y0 (3); Y1*Y1->Y1 is odd
    assert fam.gaunt_nnz(1, 1, 1) == 10
    assert fam.gaunt_nnz(0, 0, 0) == 1
    # Y0 times anything of degree <= 2 gives itself: 9 couplings
    assert fam.gaunt_nnz(0, 2, 2) == 9


def test_forward_flops_by_hand():
    """3 atoms, all 6 ordered pairs inside the cutoff, L=1, 2 channels."""
    fam = _family()
    K, C = 4, 2
    radial = 2 * 2 * 32 + 2 * 32 * C * 2          # 8 -> 32 -> C(L+1), R=2
    conv = C * (K + 2 * 10 + K)                    # weight, Gaunt, sum
    many = C * (2 * K + 2 * 10)                    # nu=2: one contraction
    mixes = 2 * 2 * K * C * C
    gate = 2 * C * 32 * 2 + K * C
    residual = 2 * K * C
    readout = 3 * (2 * C * 2 + 2 * 2)
    want = 6 * (radial + conv) + 3 * (mixes + many + gate + residual) + readout
    assert want == 3876
    assert fam.forward_flops(TINY, 3, 6) == want


@pytest.mark.parametrize("program", [
    {"tp_impl": "gaunt", "chain_tune": "heuristic"},
    {"tp_impl": "gaunt_fused", "chain_tune": "measure"},
    {"tp_impl": "cg", "conv_impl": "general"},
])
def test_forward_flops_ignore_backend(program):
    """The count reads the model's sizes only: a configuration naming
    another backend gives the same total."""
    fam = _family()
    cfg = dict(TINY, L=2, L_edge=3, channels=8, nu=3, **program)
    base = dict(TINY, L=2, L_edge=3, channels=8, nu=3)
    assert fam.forward_flops(cfg, 27, 400) == fam.forward_flops(base, 27, 400)


def test_forward_flops_scale_with_pairs():
    fam = _family()
    a, b = fam.forward_flops(TINY, 3, 6), fam.forward_flops(TINY, 3, 0)
    radial = 2 * 2 * 32 + 2 * 32 * 2 * 2
    conv = 2 * (4 + 2 * 10 + 4)
    assert a - b == 6 * (radial + conv)
    assert np.isfinite(a)


def test_program_config_fields_are_model_sizes():
    """The count's inputs are the configuration's model sizes, which the
    program's config carries unchanged."""
    from bench.harness.cell import load_cell

    cell = load_cell("mace3bpa.screen")
    pc = dataclasses.asdict(cell.program.program_config(cell.config))
    for k, v in cell.config["model"].items():
        assert pc[k] == v
