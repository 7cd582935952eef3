"""EquiformerV2 + Gaunt Selfmix (`repro.models.equiformer_v2`) at a tiny size
on the CPU: agreement with the plain reference of the benchmark
(`bench/configs/eqv2.py`) on seeded random weights, rotation invariance of
the energy and equivariance of the forces, the SO(2) convolution's
commutation with rotations about z, the host-built neighbour graph, and the
serving pools' graph path beside MACE's unchanged one."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gaunt_ff import equiformer_v2_tiny, gaunt_mace_ff
from repro.core import rep
from repro.core.conv import reduced_rows, so2_conv, wigner_blocks_from_rotmat
from repro.models.equiformer_v2 import EquiformerV2
from repro.models.equivariant import MaceGaunt
from repro.serve import pools
from repro.serve.engine import EquivariantRequest, EquivariantServeEngine
from repro.serve.pools import neighbour_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "bench", "configs", "eqv2.py")
    spec = importlib.util.spec_from_file_location("eqv2_reference_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(cfg):
    """The reference's configuration dict for a program config."""
    d = {k: v for k, v in dataclasses.asdict(cfg).items()
         if k not in ("name", "grid_theta", "grid_phi")}
    d["grid"] = {"theta": "gauss_legendre", "phi": "uniform",
                 "n_theta": cfg.grid_theta, "n_phi": cfg.grid_phi}
    return d


def _cluster(n, seed, ghost=True):
    """n atoms about 2 A apart, the last one a ghost parked far away when
    ``ghost`` (as a serving slot pads)."""
    rng = np.random.default_rng(seed)
    pos = (rng.normal(size=(n, 3)) * 1.5).astype(np.float32)
    sp = rng.integers(0, 10, n).astype(np.int32)
    mask = np.ones(n, np.float32)
    if ghost:
        mask[-1], pos[-1], sp[-1] = 0.0, (1e4, 0.0, 0.0), 0
    return sp, pos, mask


@pytest.mark.parametrize("L,M", [(2, 1), (3, 2)])
def test_matches_the_reference(L, M):
    """Energy and forces of the program and of the plain reference (both
    at the highest precision) agree on seeded weights, on a graph with a
    ghost atom; the ghost feels no force."""
    cfg = dataclasses.replace(equiformer_v2_tiny, lmax=L, mmax=M)
    ref = _reference()
    rc = _ref_cfg(cfg)
    params = jax.jit(lambda k: ref.init_params(rc, k))(jax.random.PRNGKey(3))
    sp, pos, mask = _cluster(9, L)
    nbr, nbr_mask = neighbour_graph(pos, mask, cfg.max_radius, cfg.max_neighbors)
    model = EquiformerV2(cfg)
    with jax.default_matmul_precision("highest"):
        e1, g1 = jax.jit(jax.value_and_grad(lambda p: model.energy_graph(
            params, sp, p, mask, nbr, nbr_mask)))(pos)
    e2, f2 = jax.jit(lambda *a: ref.energy_forces(*a, rc))(
        params, sp, pos, mask, nbr, nbr_mask)
    f2 = np.asarray(f2)
    assert abs(float(e1) - float(e2)) <= 1e-5 * abs(float(e2))
    scale = np.abs(f2).max()
    assert scale > 0
    np.testing.assert_allclose(-np.asarray(g1), f2, atol=3e-5 * scale)
    np.testing.assert_array_equal(np.asarray(g1)[-1], 0.0)


def test_selfmix_runs_on_the_sphere_quadrature():
    """The energy-and-force function's Selfmix takes the quadrature route at
    the served degree L = 6: its lowered program holds no FFT (the chain
    engine's route there holds one), and tracing it converts SH to the
    quadrature grid but never to the Fourier basis (the block scan's body
    is traced once, not once per block)."""
    cfg = dataclasses.replace(equiformer_v2_tiny, lmax=6, mmax=2)
    model = EquiformerV2(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sp, pos, mask = _cluster(6, 0)
    nbr, nbr_mask = neighbour_graph(pos, mask, cfg.max_radius, cfg.max_neighbors)
    force = jax.jit(jax.grad(lambda p: model.energy_graph(
        params, sp, p, mask, nbr, nbr_mask)))
    with rep.conversion_stats(fresh=True) as c:
        text = force.lower(pos).as_text()
    assert "fft" not in text.lower()
    assert c["sh_to_fourier"] == 0
    assert c["sh_to_quad"] >= 1


def _rotation(rng):
    q, r = np.linalg.qr(rng.normal(size=(3, 3)))
    q = q * np.sign(np.diag(r))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    return q


def test_energy_invariant_forces_equivariant():
    """Under a random rotation of the structure.  The S^2 nonlinearities
    are equivariant up to the aliasing of their grid, so this runs the tiny
    preset on a 24 x 24 grid, where that error is far below the check."""
    cfg = dataclasses.replace(equiformer_v2_tiny, grid_theta=24, grid_phi=24)
    model = EquiformerV2(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sp, pos, mask = _cluster(8, 1, ghost=False)
    nbr, nbr_mask = neighbour_graph(pos, mask, cfg.max_radius, cfg.max_neighbors)
    with jax.default_matmul_precision("highest"):
        f = jax.jit(jax.value_and_grad(lambda p: model.energy_graph(
            params, sp, p, mask, nbr, nbr_mask)))
        e, g = f(pos)
        q = _rotation(np.random.default_rng(7))
        e_r, g_r = f(jnp.asarray(pos @ q.T, jnp.float32))
    assert abs(float(e_r) - float(e)) <= 1e-4 * abs(float(e))
    g, g_r = np.asarray(g), np.asarray(g_r)
    np.testing.assert_allclose(g_r, g @ q.T, atol=1e-3 * np.abs(g).max())


@pytest.mark.parametrize("L,M", [(2, 1), (4, 2)])
def test_so2_conv_commutes_with_rotations_about_z(L, M):
    """In the edge frame the convolution commutes with every rotation about
    the edge (the z axis); its extra outputs are invariant."""
    rng = np.random.default_rng(L)
    rows = reduced_rows(L, M)
    ci, co, extra = 3, 2, 4
    p = {"w0": rng.normal(size=((L + 1) * ci, extra + (L + 1) * co)),
         "b0": rng.normal(size=(extra + (L + 1) * co,))}
    for m in range(1, M + 1):
        p[f"w{m}"] = rng.normal(size=((L - m + 1) * ci, 2 * (L - m + 1) * co))
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
    u = jnp.asarray(rng.normal(size=(5, len(rows), ci)), jnp.float32)
    rad = jnp.asarray(rng.normal(size=(5, sum(L - m + 1 for m in range(M + 1)) * ci)),
                      jnp.float32)
    g = 0.7
    Rz = jnp.asarray([[np.cos(g), -np.sin(g), 0], [np.sin(g), np.cos(g), 0],
                      [0, 0, 1]], jnp.float32)
    K = (L + 1) ** 2
    D = jnp.zeros((K, K))
    for l, blk in enumerate(wigner_blocks_from_rotmat(L, Rz)):
        D = D.at[l * l:(l + 1) ** 2, l * l:(l + 1) ** 2].set(blk)
    Dr = D[rows][:, rows]
    # a rotation about z keeps every order m: no row leaves the kept set
    np.testing.assert_allclose(np.abs(np.asarray(D[rows])).sum(),
                               np.abs(np.asarray(Dr)).sum(), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        y, ex = so2_conv(p, u, L, M, co, extra, rad)
        y_r, ex_r = so2_conv(p, jnp.einsum("ab,nbc->nac", Dr, u), L, M, co,
                             extra, rad)
        np.testing.assert_allclose(np.asarray(y_r),
                                   np.asarray(jnp.einsum("ab,nbc->nac", Dr, y)),
                                   atol=1e-4 * float(jnp.abs(y).max()))
    np.testing.assert_allclose(np.asarray(ex_r), np.asarray(ex), atol=1e-4)


def test_graph_breaks_ties_by_index():
    """Atoms 1..6 all 1 A from atom 0: with k=4 atom 0 keeps 1, 2, 3, 4;
    atom 1 sees atom 0 first (nearest), then its tied neighbours by index."""
    axes = np.asarray([[1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0],
                       [0, 0, 1], [0, 0, -1]], np.float64)
    pos = np.concatenate([[[0.0, 0, 0]], axes])
    nbr, nbr_mask = neighbour_graph(pos, np.ones(7), 5.0, 4)
    np.testing.assert_array_equal(nbr[0], [1, 2, 3, 4])
    assert nbr[1, 0] == 0 and list(nbr[1, 1:]) == [3, 4, 5]
    assert nbr_mask.all()


def test_graph_with_fewer_than_k_neighbours_and_ghosts():
    pos = np.asarray([[0, 0, 0], [1.0, 0, 0], [5.0, 0, 0], [1e4, 0, 0]])
    mask = np.asarray([1, 1, 1, 0], np.float32)
    nbr, nbr_mask = neighbour_graph(pos, mask, 2.0, 3)
    np.testing.assert_array_equal(nbr_mask[0], [1, 0, 0])
    assert nbr[0, 0] == 1 and nbr[1, 0] == 0
    np.testing.assert_array_equal(nbr_mask[2], 0)      # nothing within 2 A
    np.testing.assert_array_equal(nbr_mask[3], 0)      # the ghost: no edges
    # and no one's neighbour, even within the cutoff
    pos[3] = (0.5, 0, 0)
    nbr, nbr_mask = neighbour_graph(pos, mask, 2.0, 3)
    assert 3 not in nbr[nbr_mask > 0]
    np.testing.assert_array_equal(nbr_mask[3], 0)


def test_graph_is_float64():
    """Two distances that float32 cannot tell apart are ordered right."""
    pos = np.asarray([[0.0, 0, 0], [1.0 + 3e-9, 0, 0], [0, 1.0, 0]])
    nbr, _ = neighbour_graph(pos, np.ones(3), 2.0, 1)
    assert nbr[0, 0] == 2


def test_mace_pool_stages_no_graph(monkeypatch):
    """A model without ``max_neighbors`` stages species, positions and mask
    only, its step takes no graph, and no graph is ever built."""
    monkeypatch.setattr(pools, "neighbour_graph", lambda *a: 1 / 0)
    cfg = dataclasses.replace(gaunt_mace_ff, channels=4, n_layers=1, L=1,
                              L_edge=1, n_species=4)
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = EquivariantServeEngine(model, params, n_slots=2, max_atoms=4)
    rng = np.random.default_rng(0)
    req = EquivariantRequest(species=rng.integers(0, 4, 3),
                             pos=(rng.normal(size=(3, 3)) * 1.5).astype(np.float32))
    assert eng.run([req])[0].forces.shape == (3, 3)
    pool = next(iter(eng.pools))
    assert pool.graph is None and len(pool._host_inputs()) == 3
    pool.stage()
    assert len(pool._staged) == 3
    assert eng.metrics.counters["graph_edges"] == 0


def test_served_on_the_graph():
    """EquiformerV2 through `EquivariantServeEngine`: each answer equals the
    model evaluated directly on its own graph; a graph is built per new
    geometry, a relaxation step's moved geometry included."""
    cfg = dataclasses.replace(equiformer_v2_tiny, n_blocks=1)
    model = EquiformerV2(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = EquivariantServeEngine(model, params, n_slots=2, max_atoms=8)
    reqs = []
    for n, seed in ((6, 0), (8, 1), (7, 2)):
        sp, pos, _ = _cluster(n, seed, ghost=False)
        reqs.append(EquivariantRequest(species=sp, pos=pos, rid=seed))
    relax = EquivariantRequest(species=reqs[0].species, pos=reqs[0].pos.copy(),
                               steps=2, step_size=0.01, rid=9)
    done = eng.run(reqs + [relax])
    assert all(r.done and not r.rejected for r in done)
    direct = jax.jit(jax.value_and_grad(
        lambda p, sp, m, nb, nm: model.energy_graph(params, sp, p, m, nb, nm)))
    for r in done:
        n = len(r.species)
        sp, mask = np.zeros(8, np.int32), np.zeros(8, np.float32)
        pos = np.stack([1e4 * (1.0 + np.arange(8)), np.zeros(8), np.zeros(8)],
                       -1).astype(np.float32)
        sp[:n], pos[:n], mask[:n] = r.species, r.pos, 1.0
        nbr, nm = neighbour_graph(pos, mask, cfg.max_radius, cfg.max_neighbors)
        e, g = direct(pos, sp, mask, nbr, nm)
        assert abs(r.energy - float(e)) <= 1e-4 * abs(float(e))
        g = np.asarray(g)[:n]
        np.testing.assert_allclose(r.forces, -g, atol=1e-4 * np.abs(g).max())
    pool = next(iter(eng.pools))
    assert pool.graph is not None and len(pool._staged) == 5
    c = eng.metrics.counters
    # every real atom has k neighbours among >= 6 atoms 2-3 A apart
    assert c["graph_edges"] >= (6 + 8 + 7 + 6 + 6) * cfg.max_neighbors
    assert c["graph_edge_slots"] % (8 * cfg.max_neighbors) == 0
    assert c["graph_s"] > 0
