"""The plain reference of the `mace-*` configurations against the served
model at a tiny size on the CPU, and the reference's own building blocks."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(*parts):
    path = os.path.join(HERE, os.pardir, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_r_" + "_".join(parts).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _family():
    return _load("configs", "mace.py")


@pytest.mark.parametrize("L", [1, 2, 3])
def test_sh_orthonormal_on_the_grid(L):
    fam = _family()
    pts, w = fam._sphere_grid(2 * L)
    Y = fam._real_sh_np(L, pts)
    np.testing.assert_allclose(Y.T @ (Y * w[:, None]), np.eye((L + 1) ** 2),
                               atol=1e-12)


def test_filter_addition_theorem():
    """sum_m Y_lm(a) Y_lm(b) = (2l+1)/(4 pi) P_l(a.b), the identity the
    reference's edge filter rests on."""
    fam = _family()
    rng = np.random.default_rng(0)
    a, b = rng.normal(size=(2, 5, 3))
    a /= np.linalg.norm(a, axis=1, keepdims=True)
    b /= np.linalg.norm(b, axis=1, keepdims=True)
    ya, yb = fam._real_sh_np(3, a), fam._real_sh_np(3, b)
    P = fam._legendre(3, jnp.asarray(np.sum(a * b, 1)))
    for l in range(4):
        s = slice(l * l, (l + 1) ** 2)
        np.testing.assert_allclose(np.sum(ya[:, s] * yb[:, s], 1),
                                   (2 * l + 1) / (4 * np.pi) * np.asarray(P[l]),
                                   atol=1e-6)


@pytest.mark.parametrize("L,L_edge", [(1, 3), (2, 3)])
def test_reference_matches_served_model(L, L_edge):
    """Energy and forces of the reference and of `MaceGaunt` (both at the
    highest precision on the CPU) on a molecule-like geometry with a padded
    atom, under the same benchmark-made weights."""
    from bench.harness import traffic as T
    from bench.harness.geometry import molecule

    fam = _family()
    cfg = dict(L=L, L_edge=L_edge, channels=4, n_layers=2, nu=3, n_species=4,
               cutoff=5.0, n_radial=8, hidden=16)
    config = {"name": "t", "model": cfg,
              "program": {"tp_impl": "gaunt", "conv_impl": "escn",
                          "chain_tune": "heuristic"}}
    params = jax.jit(lambda k: fam.init_params(cfg, k))(jax.random.PRNGKey(3))
    sp, pos = molecule(T.rng_for(5), 7, [0.4, 0.3, 0.2, 0.1])
    # one ghost atom, parked far away and masked, as a serving slot pads
    sp = np.concatenate([sp, [0]]).astype(np.int32)
    pos = np.concatenate([pos, [[1e4, 0, 0]]]).astype(np.float32)
    mask = np.asarray([1] * 7 + [0], np.float32)
    model = _load("programs", "mace.py").build(config)
    with jax.default_matmul_precision("highest"):
        e1, g1 = jax.value_and_grad(
            lambda p: model.energy_masked(params, sp, p, mask))(pos)
    e2, f2 = fam.energy_forces(params, sp, pos, mask, cfg)
    f2 = np.asarray(f2)
    assert abs(float(e1) - float(e2)) <= 1e-5 * abs(float(e2))
    scale = np.abs(f2[:7]).max()
    assert scale > 0
    np.testing.assert_allclose(-np.asarray(g1)[:7], f2[:7], atol=1e-4 * scale)
    np.testing.assert_array_equal(f2[7], 0.0)


def test_bf16_control_departs_from_reference():
    """The bfloat16 control is a different computation, not a relabelling."""
    fam = _family()
    cfg = dict(L=2, L_edge=3, channels=8, n_layers=2, nu=3, n_species=4,
               cutoff=5.0, n_radial=8, hidden=16)
    params = jax.jit(lambda k: fam.init_params(cfg, k))(jax.random.PRNGKey(4))
    from bench.harness import traffic as T

    from bench.harness.geometry import molecule

    sp, pos = molecule(T.rng_for(6), 10, [0.4, 0.3, 0.2, 0.1])
    mask = np.ones(10, np.float32)
    e32, f32 = fam.energy_forces(params, sp, pos, mask, cfg)
    e16, f16 = fam.energy_forces(params, sp, pos, mask, cfg, "bfloat16")
    gap = np.abs(np.asarray(f16, np.float64) - np.asarray(f32)).max()
    assert gap > 1e-3 * np.abs(np.asarray(f32)).max()
