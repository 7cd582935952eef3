"""Basis residency (DESIGN.md §6): conversion elision is REAL, counted, and
numerically free.

The conversion counters in `repro.core.rep` tick every time a
`sh_to_fourier` / `fourier_to_sh` code path runs (once per eager call, once
per jit trace).  These tests pin the acceptance criteria: every chained
workload — many-body trees, selfmix (shared operand), conv filter stacks —
eliminates at least one interior conversion pair versus the looped
per-product path, and the resident results match the looped ones.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, rep
from repro.core.cg import gaunt_einsum_reference
from repro.core.conv import EquivariantConv
from repro.core.irreps import num_coeffs
from repro.core.manybody import manybody_gaunt_product, manybody_selfmix
from repro.core.rep import Rep


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _count(fn):
    """Run ``fn`` and return (s2f, f2s) conversion deltas.

    `conversion_stats(fresh=True)` scopes the counters to the block
    (snapshot/restore — robust to other tests' leftovers) and drops the
    cached `ChainPlan.apply_jit` dispatches so every counted chain traces
    fresh (warm jit caches tick zero)."""
    with rep.conversion_stats(fresh=True) as c:
        fn()
    return c["sh_to_fourier"], c["fourier_to_sh"]


# --------------------------------------------------------------------------
# counters: chains beat the looped path by >= 1 interior pair
# --------------------------------------------------------------------------


@pytest.mark.parametrize("conversion", ["dense", "half"])
def test_manybody_chain_eliminates_interior_pairs(conversion):
    nu, L = 3, 2
    xs = [_rand((4, num_coeffs(L)), i) for i in range(nu)]

    def looped():
        acc, La = xs[0], L
        for x in xs[1:]:
            acc = engine.plan(La, L, La + L, backend="fft").apply(acc, x)
            La += L

    def chained():
        engine.plan_chain((L,) * nu, conversion=conversion).apply(xs)

    s2f_loop, f2s_loop = _count(looped)
    s2f_chain, f2s_chain = _count(chained)
    assert (s2f_loop, f2s_loop) == (2 * (nu - 1), nu - 1)
    assert (s2f_chain, f2s_chain) == (nu, 1)
    # >= 1 interior fourier_to_sh . sh_to_fourier pair eliminated
    pairs_eliminated = min(s2f_loop - s2f_chain, f2s_loop - f2s_chain)
    assert pairs_eliminated >= 1
    cp = engine.plan_chain((L,) * nu, conversion=conversion)
    assert cp.interior_pairs_eliminated == nu - 2 >= 1


def test_selfmix_shared_operand_single_conversion():
    """MACE-style B_nu = A (x) A (x) A with per-operand weights: ONE
    degree-resolved conversion serves all nu operands."""
    L, nu = 2, 3
    x = _rand((3, num_coeffs(L)), 10)
    ws = [_rand((3, L + 1), 20 + i) for i in range(nu)]
    s2f, f2s = _count(lambda: manybody_selfmix(x, L, nu, Lout=L, weights=ws))
    assert (s2f, f2s) == (1, 1)
    # looped path would pay 2(nu-1) + (nu-1) = 3(nu-1) conversions
    cc = engine.plan_chain((L,) * nu, L).conversion_counts(n_unique=1)
    assert cc["looped"] == (2 * (nu - 1), nu - 1)
    assert cc["chain"] == (1, 1)


def test_conv_filter_rep_converts_once_across_layers():
    """A layer stack over fixed edge geometry: the filter converts once."""
    L, n_layers = 2, 3
    conv = EquivariantConv(L, L, L, method="general")
    x = _rand((8, num_coeffs(L)), 30)
    v = np.random.default_rng(31).normal(size=(8, 3))
    r = jnp.asarray(v / np.linalg.norm(v, axis=-1, keepdims=True), jnp.float32)

    def per_layer():
        # the eager per-product path (conv.plan is the conv_filter plan;
        # the batched route jit-caches its bucket, hiding executions from
        # the trace-time counters, so count the raw applies)
        for _ in range(n_layers):
            conv.plan.apply(x, r)

    def resident():
        filt = conv.filter_rep(r)
        for _ in range(n_layers):
            conv(x, filt)

    s2f_loop, f2s_loop = _count(per_layer)
    s2f_res, f2s_res = _count(resident)
    assert s2f_loop == 2 * n_layers and f2s_loop == n_layers
    # 1 filter conversion + n_layers x-conversions; projections unchanged
    assert s2f_res == n_layers + 1 and f2s_res == n_layers
    assert s2f_loop - s2f_res == n_layers - 1 >= 1
    # and the outputs agree
    filt = conv.filter_rep(r)
    np.testing.assert_allclose(np.asarray(conv(x, filt)),
                               np.asarray(conv(x, r)), atol=1e-4)


def test_boundary_plan_resident_output_feeds_next_product():
    """A resident output Rep enters the next chain with no round trip."""
    L = 2
    x1, x2, x3 = (_rand((4, num_coeffs(L)), 40 + i) for i in range(3))
    p = engine.plan(L, L, 2 * L, backend="fft",
                    options={"boundary": ("sh", "sh", "fourier")})

    def resident():
        mid = p.apply(x1, x2)           # Rep, stays in the Fourier basis
        engine.plan_chain((2 * L, L), Lout=L).apply([mid, x3])

    s2f, f2s = _count(resident)
    assert (s2f, f2s) == (3, 1)  # looped would be (4, 2)
    mid = p.apply(x1, x2)
    got = engine.plan_chain((2 * L, L), Lout=L).apply([mid, x3])
    acc = gaunt_einsum_reference(x1, x2, L, L)
    acc = gaunt_einsum_reference(acc, x3, 2 * L, L, L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc), atol=2e-3)


# --------------------------------------------------------------------------
# models: the resident path is numerically the same as the legacy path
# --------------------------------------------------------------------------


def test_segnn_resident_matches_nonresident():
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import SegnnNBody

    cfg = EquivariantConfig(name="t", kind="segnn", L=1, L_edge=1, channels=4,
                            n_layers=2)
    n = 5
    rng = np.random.default_rng(50)
    charge = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    vel = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    model_on = SegnnNBody(cfg)
    params = model_on.init(jax.random.PRNGKey(0))
    out_on = model_on.forward(params, charge, pos, vel)
    model_off = SegnnNBody(dataclasses.replace(cfg, fourier_resident=False))
    out_off = model_off.forward(params, charge, pos, vel)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               atol=1e-4)

    # and the resident forward converts the edge filter ONCE for the whole
    # stack: n_layers x-side conversions + 1 filter conversion.  (The legacy
    # path converts the filter inside every layer's product — its per-product
    # cost is pinned by the plan-level counter tests above; its model-level
    # count is invisible here because plan_batch jit-caches its buckets.)
    s2f_on, f2s_on = _count(lambda: model_on.forward(params, charge, pos, vel))
    assert s2f_on == cfg.n_layers + 1
    assert f2s_on == cfg.n_layers


def test_selfmix_layer_resident_matches_batched():
    from repro.models.equivariant import SelfmixLayer

    L, C = 2, 3
    x = _rand((6, C, num_coeffs(L)), 60)
    layer_on = SelfmixLayer(L=L, channels=C, tp_impl="gaunt")
    params = layer_on.init(jax.random.PRNGKey(1))
    params = jax.tree.map(
        lambda a: a * (1 + 0.1 * jnp.arange(a.size).reshape(a.shape)), params)
    layer_off = SelfmixLayer(L=L, channels=C, tp_impl="gaunt", resident=False)
    out_on = layer_on(params, x)
    out_off = layer_off(params, x)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               atol=1e-4)
    s2f_on, _ = _count(lambda: layer_on(params, x))
    assert s2f_on == 1  # shared operand: one degree-resolved conversion


def test_mace_resident_matches_nonresident_general_conv():
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import MaceGaunt

    cfg = EquivariantConfig(name="t", kind="mace", L=1, L_edge=1, channels=4,
                            n_layers=2, nu=3, conv_impl="general")
    n = 4
    rng = np.random.default_rng(70)
    species = jnp.asarray(rng.integers(0, cfg.n_species, size=(n,)))
    pos = jnp.asarray(rng.normal(size=(n, 3)) * 1.5, jnp.float32)
    model_on = MaceGaunt(cfg)
    params = model_on.init(jax.random.PRNGKey(2))
    e_on = model_on.energy(params, species, pos)
    model_off = MaceGaunt(dataclasses.replace(cfg, fourier_resident=False))
    e_off = model_off.energy(params, species, pos)
    np.testing.assert_allclose(float(e_on), float(e_off), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# grid-resident gates (DESIGN.md §6.5): the nonlinearity is chain-interior
# --------------------------------------------------------------------------


def _gate_params(C, seed):
    rng = np.random.default_rng(seed)
    return {"w1": jnp.asarray(rng.normal(size=(C, 16)) * 0.3, jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(16, C)) * 0.3, jnp.float32)}


def test_grid_gate_region_single_entry_exit_pair():
    """THE elision proof: a whole TP -> gate -> selfmix layer plans as ONE
    grid-resident region.  The gated TP exits resident (the gate rides the
    grid), the selfmix re-enters for free, so the region pays one entry
    group + one exit — the SH-side gate forces a full exit -> gate ->
    re-entry in the middle and pays >= 2 conversion pairs."""
    L, B, C = 1, 4, 3
    Ltot = 2 * L
    x1 = _rand((B, C, num_coeffs(L)), 500)
    x2 = _rand((B, C, num_coeffs(L)), 501)
    gp = _gate_params(C, 502)
    tp_g = engine.plan_chain((L, L), Ltot, backend="tree", gate=True)
    tp = engine.plan_chain((L, L), Ltot, backend="tree")
    mix = engine.plan_chain((Ltot, Ltot), Ltot, backend="tree")

    def grid_region():
        mid = tp_g.apply([x1, x2], out_basis="fourier", gate_params=gp)
        mix.apply([mid, mid])

    def sh_region():
        y = engine._gate_sh(gp, tp.apply([x1, x2]))
        mix.apply([y, y])

    s2f_grid, f2s_grid = _count(grid_region)
    s2f_sh, f2s_sh = _count(sh_region)
    # grid: 2 operand entries + 1 region exit; the gate adds NOTHING
    assert (s2f_grid, f2s_grid) == (2, 1)
    # SH gate: TP pays (2, 1), then the gated product re-enters the selfmix
    # (one shared-operand conversion) — a full extra exit/entry pair
    assert (s2f_sh, f2s_sh) == (3, 2)
    pairs_eliminated = min(s2f_sh - s2f_grid, f2s_sh - f2s_grid)
    assert pairs_eliminated >= 1
    # and the two regions compute the same thing
    mid = tp_g.apply([x1, x2], out_basis="fourier", gate_params=gp)
    got = mix.apply([mid, mid])
    y = engine._gate_sh(gp, tp.apply([x1, x2]))
    want = mix.apply([y, y])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_selfmix_gate_params_matches_gate_apply():
    """manybody_selfmix(gate_params=...) == the models' gate applied to the
    ungated self-product — the fused stage is exact, not approximate."""
    from repro.models.equivariant import gate_apply

    L, nu, B, C = 2, 3, 4, 3
    x = _rand((B, C, num_coeffs(L)), 510)
    gp = _gate_params(C, 511)
    want = gate_apply(gp, manybody_selfmix(x, L, nu, Lout=L), L)
    got = manybody_selfmix(x, L, nu, Lout=L, gate_params=gp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the gate is a chain-route feature: an explicit backend pins the
    # per-plan route, which rejects it
    with pytest.raises(ValueError, match="chain route"):
        manybody_gaunt_product([x, x], (L, L), Lout=L, backend="fft",
                               gate_params=gp)


def test_mace_grid_gate_one_conversion_pair_per_layer():
    """Acceptance: a MaceGaunt layer with grid_gate='on' executes with
    exactly ONE entry + ONE exit conversion (the gate lives inside the
    selfmix chain's resident region).  With identity mb_mix the reordered
    parameterization coincides with the legacy one, so the outputs match."""
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import MaceGaunt

    cfg = EquivariantConfig(name="t", kind="mace", L=1, L_edge=1, channels=5,
                            n_layers=1, nu=3, conv_impl="escn")
    n = 4
    rng = np.random.default_rng(520)
    species = jnp.asarray(rng.integers(0, cfg.n_species, size=(n,)))
    pos = jnp.asarray(rng.normal(size=(n, 3)) * 1.5, jnp.float32)
    model_on = MaceGaunt(dataclasses.replace(cfg, grid_gate="on"))
    params = model_on.init(jax.random.PRNGKey(3))
    # fresh counting retraces the selfmix chain on every call, and the eSCN
    # conv runs no conversion (its aligned Gaunt coupling is a real matmul),
    # so each call counts the gated many-body region's conversions alone
    first = _count(lambda: model_on.features(params, species, pos))
    second = _count(lambda: model_on.features(params, species, pos))
    assert first == second == (1, 1)
    # and the fused gate adds nothing anywhere else: steady state matches
    # the ungated model's steady state exactly
    model_plain = MaceGaunt(cfg)
    model_plain.features(params, species, pos)  # warm its chain trace
    assert second == _count(lambda: model_plain.features(params, species, pos))
    # identity channel mix makes gate-before-mix == gate-after-mix exactly
    for lp in params["layers"]:
        lp["mb_mix"] = jnp.broadcast_to(
            jnp.eye(cfg.channels), (cfg.L + 1, cfg.channels, cfg.channels))
    out_on = model_on.features(params, species, pos)
    out_off = MaceGaunt(cfg).features(params, species, pos)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               rtol=1e-5, atol=1e-5)


def test_segnn_grid_gate_quad_path_matches_off():
    """SEGNN's post-mix gate has no adjacent chain to fuse into: grid_gate
    ='on' routes it through the S^2 quadrature Rep (exact — the gate is
    affine), ticking one sh_to_quad/quad_to_sh pair per layer."""
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import SegnnNBody

    cfg = EquivariantConfig(name="t", kind="segnn", L=1, L_edge=1, channels=4,
                            n_layers=2)
    n = 5
    rng = np.random.default_rng(530)
    charge = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    vel = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    model_off = SegnnNBody(cfg)
    params = model_off.init(jax.random.PRNGKey(4))
    model_on = SegnnNBody(dataclasses.replace(cfg, grid_gate="on"))
    with rep.conversion_stats(fresh=True) as c:
        out_on = model_on.forward(params, charge, pos, vel)
    assert c["sh_to_quad"] == cfg.n_layers
    assert c["quad_to_sh"] == cfg.n_layers
    out_off = model_off.forward(params, charge, pos, vel)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               rtol=1e-5, atol=1e-5)


def test_resolve_grid_gate_policy():
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import _resolve_grid_gate

    cfg = EquivariantConfig(name="t", kind="mace", L=1, channels=4)
    Ls = (1, 1, 1)
    assert _resolve_grid_gate(cfg, Ls, 1) is False
    assert _resolve_grid_gate(
        dataclasses.replace(cfg, grid_gate="on"), Ls, 1) is True
    # 'auto' without measured tuning stays off (no silent timing runs)
    assert _resolve_grid_gate(
        dataclasses.replace(cfg, grid_gate="auto"), Ls, 1) is False
    with pytest.raises(ValueError, match="grid_gate"):
        _resolve_grid_gate(
            dataclasses.replace(cfg, grid_gate="bogus"), Ls, 1)


# --------------------------------------------------------------------------
# Rep semantics
# --------------------------------------------------------------------------


def test_rep_pytree_through_jit_and_resize():
    L = 2
    x = _rand((3, num_coeffs(L)), 80)
    r = Rep.from_sh(x, L).to_fourier("dense")

    @jax.jit
    def f(r):
        return r.resize(L + 2).resize(L).to_sh().data

    np.testing.assert_allclose(np.asarray(f(r)), np.asarray(x), atol=2e-5)


def test_rep_add_and_errors():
    L = 1
    a = Rep.from_sh(_rand((2, 4), 90), L).to_fourier("dense")
    b = Rep.from_sh(_rand((2, 4), 91), L).to_fourier("half")
    s = (a + b).to_sh()
    assert s.L == L
    with pytest.raises(ValueError):
        Rep.from_sh(_rand((2, 4), 92), L).resize(2)
    with pytest.raises(ValueError):
        engine.plan(1, 1, 1, backend="fft",
                    options={"boundary": ("sh", "sh", "fourier")})
    with pytest.raises(ValueError):
        engine.plan(1, 1, 2, backend="dense_einsum",
                    options={"boundary": ("sh", "fourier", "sh")})


def test_chain_rejects_weighted_resident_operand():
    L = 1
    x = _rand((2, 4), 95)
    r = Rep.from_sh(x, L).to_fourier("dense")
    cp = engine.plan_chain((L, L), Lout=L)
    with pytest.raises(ValueError):
        cp.apply([r, x], weights=[_rand((2, 2), 96), None])
