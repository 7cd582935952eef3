"""Equivariant convolution (general + eSCN-sparsity) and many-body products."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import constants, so3
from repro.core.cg import gaunt_einsum_reference
from repro.core.conv import (
    EquivariantConv,
    align_rotation,
    apply_wigner_blocks,
    wigner_blocks_from_rotmat,
)
from repro.core.irreps import m_array, num_coeffs
from repro.core.manybody import manybody_gaunt_product, manybody_selfmix
from repro.core.rep import conversion_stats
from repro.core.so3 import real_sph_harm, real_sph_harm_jax
from repro.testing import assert_close, random_array, random_unit_vectors


def _rand(shape, seed=0):
    return jnp.asarray(random_array(shape, seed))


def _rand_dirs(n, seed=0):
    return jnp.asarray(random_unit_vectors((n,), seed))


def test_align_rotation():
    r = _rand_dirs(32, 1)
    R = align_rotation(r)
    z = jnp.einsum("...ij,...j->...i", R, r)
    np.testing.assert_allclose(np.asarray(z), np.tile([0, 0, 1.0], (32, 1)), atol=1e-5)
    det = np.linalg.det(np.asarray(R))
    np.testing.assert_allclose(det, 1.0, atol=1e-5)


def test_wigner_blocks_from_rotmat_vs_exact():
    rng = np.random.default_rng(2)
    a, b, g = 0.4, 1.0, -0.8
    R = so3.rotation_matrix_zyz(a, b, g).astype(np.float32)
    Ds = wigner_blocks_from_rotmat(4, jnp.asarray(R))
    for l in range(5):
        ref = so3.wigner_D_real(l, a, b, g)
        np.testing.assert_allclose(np.asarray(Ds[l]), ref, atol=1e-4)


def test_apply_wigner_matches_sh_rotation():
    r = _rand_dirs(8, 3)
    R = align_rotation(r)
    Ds = wigner_blocks_from_rotmat(3, R)
    S = real_sph_harm_jax(3, r)
    S_rot = apply_wigner_blocks(Ds, S)
    ref = real_sph_harm_jax(3, jnp.einsum("...ij,...j->...i", R, r))
    np.testing.assert_allclose(np.asarray(S_rot), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("L1,L2,Lout", [(2, 2, 4), (3, 2, 3), (2, 3, 5), (1, 4, 5),
                                        (1, 3, 1), (2, 3, 2)])
def test_escn_conv_matches_general_and_oracle(L1, L2, Lout):
    x = _rand((16, num_coeffs(L1)), 4)
    r = _rand_dirs(16, 5)
    general = EquivariantConv(L1, L2, Lout, method="general")
    escn = EquivariantConv(L1, L2, Lout, method="escn")
    filt = real_sph_harm_jax(L2, r).astype(jnp.float32)
    ref = gaunt_einsum_reference(x, filt, L1, L2, Lout)
    np.testing.assert_allclose(np.asarray(general(x, r)), np.asarray(ref), atol=3e-4)
    np.testing.assert_allclose(np.asarray(escn(x, r)), np.asarray(ref), atol=3e-4)


def test_escn_conv_weights():
    L1, L2, Lout = 2, 2, 3
    x = _rand((6, num_coeffs(L1)), 6)
    r = _rand_dirs(6, 7)
    w1 = _rand((6, L1 + 1), 8)
    w2 = _rand((6, L2 + 1), 9)
    w3 = _rand((6, Lout + 1), 10)
    escn = EquivariantConv(L1, L2, Lout, method="escn")
    general = EquivariantConv(L1, L2, Lout, method="general")
    np.testing.assert_allclose(
        np.asarray(escn(x, r, w1, w2, w3)),
        np.asarray(general(x, r, w1, w2, w3)),
        atol=3e-4,
    )


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_escn_conv_grad_matches_general(dtype):
    """The force path differentiates the conv through x and the edge
    direction: eSCN and general give the same gradients.  rhat is the
    normalised edge vector, as in the models, so both see only its
    tangential change."""
    L1, L2, Lout = 2, 3, 2
    with jax.enable_x64(dtype == "float64"):
        rd = jnp.dtype(dtype)
        cd = jnp.complex128 if dtype == "float64" else jnp.complex64
        x = jnp.asarray(random_array((12, num_coeffs(L1)), 20), rd)
        v = jnp.asarray(random_array((12, 3), 21), rd)
        g = jnp.asarray(random_array((12, num_coeffs(Lout)), 22), rd)
        w1 = jnp.asarray(random_array((12, L1 + 1), 23), rd)

        def grads(method):
            conv = EquivariantConv(L1, L2, Lout, method=method, cdtype=cd, rdtype=rd)

            def loss(x, v):
                rhat = v / jnp.linalg.norm(v, axis=-1, keepdims=True)
                return jnp.sum(conv(x, rhat, w1=w1) * g)

            return jax.grad(loss, argnums=(0, 1))(x, v)

        (gx_e, gv_e), (gx_g, gv_g) = grads("escn"), grads("general")
        assert gx_e.dtype == gv_e.dtype == rd
        assert_close(gx_e, gx_g, dtype=dtype, tier="loose")
        assert_close(gv_e, gv_g, dtype=dtype, tier="loose")


@pytest.mark.parametrize("L", [1, 2])
def test_escn_coupling_conserves_m_and_skips_fourier(L):
    """The aligned coupling only joins input and output coefficients of the
    same m, and an escn_aligned call runs no SH<->Fourier conversion; the
    general conv on the same operands does, so the counter is live."""
    C = constants.escn_coupling(L, 3, L)
    assert C.shape == (4, num_coeffs(L), num_coeffs(L))
    m = m_array(L)
    nz = np.abs(C) > 1e-12
    assert nz.any()
    assert not (nz & (m[:, None] != m[None, :])).any()
    # fresh operand shapes: a warm bucket jit would count nothing either way
    x = _rand((23, 3, num_coeffs(L)), 30 + L)
    r = _rand_dirs(23, 32 + L)[:, None, :]
    with conversion_stats(fresh=True) as c:
        out = EquivariantConv(L, 3, L, method="escn")(x, r)
    assert (c["sh_to_fourier"], c["fourier_to_sh"]) == (0, 0)
    with conversion_stats(fresh=True) as c:
        ref = EquivariantConv(L, 3, L, method="general")(x, r)
    assert c["sh_to_fourier"] > 0 and c["fourier_to_sh"] > 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)


def test_conv_equivariance():
    """Rotating inputs (feature + geometry) rotates the output."""
    L1, L2 = 2, 2
    Lout = 3
    from repro.testing import random_angles, rotation_matrix, wigner_D

    conv = EquivariantConv(L1, L2, Lout, method="escn")
    x = random_array((num_coeffs(L1),), seed=11)
    r = np.asarray(random_unit_vectors((), seed=11), np.float64)
    angles = random_angles(seed=11)
    Rg = rotation_matrix(angles)
    D1 = wigner_D(L1, angles)
    D3 = wigner_D(Lout, angles)
    out = np.asarray(conv(jnp.asarray(x)[None], jnp.asarray(r, dtype=jnp.float32)[None])[0])
    out_rot = np.asarray(
        conv(jnp.asarray(D1 @ x)[None], jnp.asarray(Rg @ r, dtype=jnp.float32)[None])[0]
    )
    np.testing.assert_allclose(out_rot, D3 @ out, atol=5e-4)


def test_manybody_matches_fold():
    L = 2
    nu = 3
    xs = [_rand((4, num_coeffs(L)), 20 + i) for i in range(nu)]
    got = manybody_gaunt_product(xs, [L] * nu)
    acc = gaunt_einsum_reference(xs[0], xs[1], L, L)
    acc = gaunt_einsum_reference(acc, xs[2], 2 * L, L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc), atol=1e-3)


def test_manybody_four_operands_batched_tree():
    L = 1
    xs = [_rand((3, num_coeffs(L)), 30 + i) for i in range(4)]
    got = manybody_gaunt_product(xs, [L] * 4)
    acc = gaunt_einsum_reference(xs[0], xs[1], L, L)
    acc = gaunt_einsum_reference(acc, xs[2], 2 * L, L)
    acc = gaunt_einsum_reference(acc, xs[3], 3 * L, L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc), atol=1e-3)


def test_manybody_truncated_output():
    L, nu, Lout = 2, 3, 2
    x = _rand((5, num_coeffs(L)), 40)
    got = manybody_selfmix(x, L, nu, Lout=Lout)
    acc = gaunt_einsum_reference(x, x, L, L)
    acc = gaunt_einsum_reference(acc, x, 2 * L, L, Lout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc), atol=1e-3)
    assert got.shape == (5, num_coeffs(Lout))


def test_manybody_weights():
    L, nu = 2, 2
    x = _rand((3, num_coeffs(L)), 41)
    w = [_rand((3, L + 1), 42 + i) for i in range(nu)]
    got = manybody_gaunt_product([x, x], [L, L], weights=w)
    from repro.core.gaunt import expand_degree_weights

    ref = gaunt_einsum_reference(
        x * expand_degree_weights(w[0], L), x * expand_degree_weights(w[1], L), L, L
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-3)
