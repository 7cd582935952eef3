"""E(3) symmetry + trainability of the paper-side models."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gaunt_ff import EquivariantConfig
from repro.core import constants, so3
from repro.core.gaunt import expand_degree_weights
from repro.core.irreps import l_array, num_coeffs
from repro.data import lj_dataset, nbody_dataset
from repro.models.equivariant import (MaceGaunt, SegnnNBody, SelfmixLayer,
                                      _quad_grid_size)

CFG_MACE = EquivariantConfig(name="t", kind="mace", L=1, L_edge=1, channels=8,
                             n_layers=1, n_species=4, nu=2, hidden=16, n_radial=4)
CFG_SEGNN = EquivariantConfig(name="t", kind="segnn", L=1, L_edge=1, channels=8,
                              n_layers=2, hidden=16, n_radial=4)


def _rot():
    return 0.5, 1.1, -0.8


def test_mace_energy_invariance():
    m = MaceGaunt(CFG_MACE)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    species = jnp.asarray(rng.integers(0, 4, 6), jnp.int32)
    pos = jnp.asarray(rng.normal(size=(6, 3)) * 1.5, jnp.float32)
    e1 = m.energy(params, species, pos)
    assert bool(jnp.isfinite(e1)), "energy is not finite"
    a, b, g = _rot()
    R = jnp.asarray(so3.rotation_matrix_zyz(a, b, g), jnp.float32)
    e2 = m.energy(params, species, pos @ R.T)
    np.testing.assert_allclose(float(e1), float(e2), atol=1e-3, rtol=1e-4)
    # translation invariance
    e3 = m.energy(params, species, pos + jnp.asarray([1.0, -2.0, 0.5]))
    np.testing.assert_allclose(float(e1), float(e3), atol=1e-3, rtol=1e-4)


def test_mace_forces_equivariance():
    m = MaceGaunt(CFG_MACE)
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    species = jnp.asarray(rng.integers(0, 4, 5), jnp.int32)
    pos = jnp.asarray(rng.normal(size=(5, 3)) * 1.5, jnp.float32)
    _, f1 = m.energy_forces(params, species, pos)
    assert bool(jnp.all(jnp.isfinite(f1)))
    a, b, g = _rot()
    R = jnp.asarray(so3.rotation_matrix_zyz(a, b, g), jnp.float32)
    _, f2 = m.energy_forces(params, species, pos @ R.T)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1) @ np.asarray(R).T,
                               atol=2e-3, rtol=1e-3)


def test_mace_trains_on_lj():
    m = MaceGaunt(CFG_MACE)
    params = m.init(jax.random.PRNGKey(2))
    data = lj_dataset(8, n_atoms=6, n_species=4, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.items()}

    loss_fn = jax.jit(m.loss)
    grad_fn = jax.jit(jax.grad(m.loss))
    l0 = float(loss_fn(params, batch))
    for _ in range(8):
        g = grad_fn(params, batch)
        params = jax.tree.map(lambda p, gg: p - 3e-3 * gg, params, g)
    l1 = float(loss_fn(params, batch))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0, (l0, l1)


def test_segnn_equivariance():
    m = SegnnNBody(CFG_SEGNN)
    params = m.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    charge = jnp.asarray(rng.choice([-1.0, 1.0], 5), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)
    vel = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)
    out1 = m.forward(params, charge, pos, vel)
    assert bool(jnp.all(jnp.isfinite(out1)))
    a, b, g = _rot()
    R = jnp.asarray(so3.rotation_matrix_zyz(a, b, g), jnp.float32)
    out2 = m.forward(params, charge, pos @ R.T, vel @ R.T)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out1) @ np.asarray(R).T,
                               atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("impl", ["gaunt", "cg"])
def test_segnn_trains_nbody(impl):
    cfg = dataclasses.replace(CFG_SEGNN, tp_impl=impl)
    m = SegnnNBody(cfg)
    params = m.init(jax.random.PRNGKey(4))
    data = nbody_dataset(6, horizon=200, seed=1)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    loss_fn = jax.jit(m.loss)
    grad_fn = jax.jit(jax.grad(m.loss))
    l0 = float(loss_fn(params, batch))
    for _ in range(6):
        g = grad_fn(params, batch)
        params = jax.tree.map(lambda p, gg: p - 1e-2 * gg, params, g)
    l1 = float(loss_fn(params, batch))
    assert l1 < l0, (impl, l0, l1)


@pytest.mark.parametrize("impl", ["gaunt", "gaunt_fused", "cg", "quad"])
def test_selfmix_layer_equivariance(impl):
    L, C = 2, 4
    layer = SelfmixLayer(L=L, channels=C, tp_impl=impl)
    params = layer.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(3, C, num_coeffs(L))), jnp.float32)
    a, b, g = _rot()
    D = jnp.asarray(so3.wigner_D_real_packed(L, a, b, g), jnp.float32)
    y1 = layer(params, x)
    y2 = layer(params, jnp.einsum("ij,ncj->nci", D, x))
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("ij,ncj->nci", D, y1)), np.asarray(y2),
        atol=3e-3, rtol=1e-3)


def test_selfmix_gaunt_equals_fused():
    L, C = 2, 4
    a = SelfmixLayer(L=L, channels=C, tp_impl="gaunt")
    b = SelfmixLayer(L=L, channels=C, tp_impl="gaunt_fused")
    params = a.init(jax.random.PRNGKey(6))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, C, num_coeffs(L))), jnp.float32)
    np.testing.assert_allclose(np.asarray(a(params, x)), np.asarray(b(params, x)),
                               atol=2e-4, rtol=2e-4)


def _selfmix_case(L, C=3, n=4):
    """Random features and non-uniform per-degree weights for a Selfmix."""
    rng = np.random.default_rng(100 + L)
    layer = SelfmixLayer(L=L, channels=C)
    params = layer.init(jax.random.PRNGKey(L))
    for k, size in (("w1", L + 1), ("w2", L + 1), ("w3", 2 * L + 1)):
        params[k] = jnp.asarray(1 + 0.3 * rng.normal(size=size), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, C, num_coeffs(L))), jnp.float32)
    return params, x


@pytest.mark.parametrize("L", [1, 2, 4, 6])
def test_selfmix_quad_equals_dense_gaunt(L):
    """The quadrature route's product is the dense real-Gaunt contraction of
    (w1 . x) and (w2 . x), scaled by w3 and channel-mixed."""
    params, x = _selfmix_case(L)
    e = [np.asarray(expand_degree_weights(params[k][: L + 1], L), np.float64)
         for k in ("w1", "w2", "w3")]
    xn = np.asarray(x, np.float64)
    G = constants.gaunt_dense(L, L, L, "float64")
    prod = np.einsum("nca,ncb,abk->nck", xn * e[0], xn * e[1], G) * e[2]
    wl = np.asarray(params["mix"], np.float64)[l_array(L)]   # [K, C, C']
    want = np.einsum("nck,kcd->ndk", prod, wl)
    with jax.default_matmul_precision("highest"):
        got = SelfmixLayer(L=L, channels=3, tp_impl="quad").interaction(
            params, x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("L", [2, 6])
def test_selfmix_quad_gradients_match_tree(L):
    """d/dx, d/dw1, d/dw2 and d/dw3 of the quadrature route equal the
    spectral chain's."""
    params, x = _selfmix_case(L)
    probe = jnp.asarray(np.random.default_rng(L).normal(size=x.shape),
                        jnp.float32)

    def grads(impl):
        layer = SelfmixLayer(L=L, channels=3, tp_impl=impl)
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda p, x: jnp.sum(
                layer.interaction(p, x) * probe), argnums=(0, 1))(params, x)

    (gp_q, gx_q), (gp_t, gx_t) = grads("quad"), grads("gaunt")
    for k in ("w1", "w2", "w3"):
        scale = np.abs(np.asarray(gp_t[k])).max()
        assert scale > 0
        np.testing.assert_allclose(np.asarray(gp_q[k]), np.asarray(gp_t[k]),
                                   atol=1e-4 * scale, err_msg=k)
    np.testing.assert_allclose(np.asarray(gx_q), np.asarray(gx_t),
                               atol=1e-4 * np.abs(np.asarray(gx_t)).max())


def test_selfmix_quad_grid_is_the_smallest_exact():
    """At L = 6 the 10 x 19 grid reproduces the real-Gaunt tensor to float64
    rounding; one phi point fewer, or one theta node fewer, does not."""
    L = 6
    G = constants.gaunt_dense(L, L, L, "float64")

    def quad_gaunt(nt, nph):
        A = constants.quad_sample_sh(L, nt, nph)
        P = constants.quad_project_sh(L, nt, nph)
        return np.einsum("ag,bg,gk->abk", A, A, P)

    def rel(nt, nph):
        return np.abs(quad_gaunt(nt, nph) - G).max() / np.abs(G).max()

    assert _quad_grid_size(L) == (10, 19)
    assert rel(10, 19) < 1e-12
    assert rel(10, 18) > 1e-2
    assert rel(9, 19) > 1e-2


def test_no_duplicate_random_init_leaves():
    """PRNG-key hygiene regression (PR 4): MaceGaunt.init reused k4 for
    mb_mix AND gate (with ks[3] never consumed), SegnnNBody.init reused k3
    for mix AND self_mix and the radial key for gate — bitwise-correlated
    parameters at init.  Every random leaf must now be unique; constant
    leaves (ones-initialized weights) are exempt by construction."""
    models = [
        MaceGaunt(dataclasses.replace(CFG_MACE, n_layers=2)),
        SegnnNBody(dataclasses.replace(CFG_SEGNN, n_layers=2)),
        SelfmixLayer(L=2, channels=4),
    ]
    for i, m in enumerate(models):
        params = m.init(jax.random.PRNGKey(i))
        rand = [np.asarray(leaf) for leaf in jax.tree.leaves(params)
                if np.unique(np.asarray(leaf)).size > 1]
        assert rand, f"{type(m).__name__}: no random leaves found"
        blobs = [leaf.tobytes() for leaf in rand]
        assert len(blobs) == len(set(blobs)), (
            f"{type(m).__name__}: two random init leaves are bitwise-identical")
