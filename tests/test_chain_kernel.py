"""The n-way fused chain collocation kernel (DESIGN.md §6.4) and the chain
autotune fold.

The tentpole claims, each pinned here:
  * a whole >= 3-operand ChainPlan on the kernel backend is ONE pallas_call
    (proven by the kernel dispatch counter AND by walking the jaxpr);
  * the kernel matches the tree-conv ChainPlan numerically — to f64 machine
    precision under x64 (subprocess), bounded f32 otherwise — across
    2/3/4-operand chains, with per-operand and output weights, under grad
    and vmap, and through `fourier_boundary` entry (resident operands enter
    as grids) and exit (the product stays resident);
  * rotation equivariance holds (testing/ oracle);
  * chains fold into the engine's measured autotuner keyed like plans;
  * sharded chains pad/slice ragged row counts (2-virtual-device
    subprocess).

Everything runs on CPU via interpret=True.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.irreps import num_coeffs
from repro.core.rep import Rep
from repro.kernels.gaunt_fused import (gaunt_chain_fused_pallas,
                                       gaunt_chain_fused_xla, kernel_stats,
                                       reset_kernel_stats)
from repro.testing import (assert_close, random_angles, random_irreps,
                           rotate_irreps)


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


DTYPES = ["float32", "bfloat16"]


CHAINS = [
    ((1, 1), 2),          # pairwise, full degree
    ((2, 2), 2),          # pairwise, truncated exit
    ((2, 1, 2), 3),       # 3-operand, mixed degrees
    ((2, 2, 2), 2),       # 3-operand, truncated
    ((1, 2, 1, 2), 4),    # 4-operand
]


# --------------------------------------------------------------------------
# numerical identity vs the tree-conv ChainPlan
# --------------------------------------------------------------------------


@pytest.mark.parametrize("Ls,Lout", CHAINS)
@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", DTYPES)
def test_chain_kernel_matches_tree(Ls, Lout, backend, weighted, dtype):
    """Kernel-vs-tree identity at both storage precisions: inputs quantized
    to ``dtype``, reference = f32 tree on the same values, tolerance from
    the shared per-precision tiers (repro.testing.tol_for)."""
    B = 9
    xs = [_rand((B, num_coeffs(L)), 3 * i, dtype) for i, L in enumerate(Ls)]
    ws = wo = None
    if weighted:
        ws = [_rand((B, L + 1), 50 + i) for i, L in enumerate(Ls)]
        wo = _rand((B, Lout + 1), 99)
    tree = engine.plan_chain(Ls, Lout, backend="tree")  # f32 reference
    cp = engine.plan_chain(Ls, Lout, backend=backend, dtype=dtype)
    assert cp.backend == backend
    want = np.asarray(tree.apply([x.astype(jnp.float32) for x in xs],
                                 weights=ws, w_out=wo))
    got = cp.apply(xs, weights=ws, w_out=wo)
    assert got.dtype == jnp.dtype(dtype)
    assert_close(np.asarray(got).astype(np.float64), want, dtype=dtype,
                 tier="identity", tol=3e-5 if dtype == "float32" else None)


def test_chain_kernel_f64_exact_vs_tree():
    """Under x64 the collocation kernel and the tree-conv chain agree to
    f64 machine precision (both are exact realizations of the same alias-free
    product) — subprocess so the x64 flag cannot leak into this process."""
    code = r"""
import os
os.environ["JAX_ENABLE_X64"] = "1"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine
from repro.core.irreps import num_coeffs

rng = np.random.default_rng(0)
for Ls, Lout in [((2, 2), 2), ((2, 1, 2), 3), ((1, 2, 1, 2), 4)]:
    xs = [jnp.asarray(rng.normal(size=(5, num_coeffs(L))), jnp.float64)
          for L in Ls]
    ws = [jnp.asarray(rng.normal(size=(5, L + 1)), jnp.float64) for L in Ls]
    tree = engine.plan_chain(Ls, Lout, backend="tree", dtype="float64")
    want = np.asarray(tree.apply(xs, weights=ws))
    for backend in ("fused_xla", "fused_pallas"):
        cp = engine.plan_chain(Ls, Lout, backend=backend, dtype="float64")
        got = np.asarray(cp.apply(xs, weights=ws))
        assert got.dtype == np.float64
        err = np.abs(got - want).max() / (np.abs(want).max() + 1.0)
        assert err < 1e-12, (Ls, Lout, backend, err)
print("F64_OK")
"""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert "F64_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-2000:])


# --------------------------------------------------------------------------
# grad / vmap conformance
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
def test_chain_kernel_grad_matches_tree(backend):
    Ls, Lout, B = (2, 1, 2), 3, 6
    xs = [_rand((B, num_coeffs(L)), 10 + i) for i, L in enumerate(Ls)]
    ws = [_rand((B, L + 1), 20 + i) for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Lout, backend=backend)
    tree = engine.plan_chain(Ls, Lout, backend="tree")

    def loss(plan):
        return lambda a: jnp.sum(plan.apply([a, xs[1], xs[2]], weights=ws) ** 2)

    g = jax.grad(loss(cp))(xs[0])
    g0 = jax.grad(loss(tree))(xs[0])
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("gated", [False, True])
def test_chain_kernel_grad_of_grad_matches_xla(gated):
    """Force-matched training differentiates forces, themselves a gradient:
    the Pallas chain kernel must take a second derivative (through its
    custom JVP) and agree with the plain-XLA collocation."""
    Ls, Lout, B, C = (2, 2, 2), 2, 4, 3
    x = _rand((B, C, num_coeffs(2)), 390)
    w = _rand((3,), 391)
    gp = _gate_params(C, 392) if gated else None
    plans = [engine.plan_chain(Ls, Lout, backend=b, gate=gated)
             for b in ("fused_pallas", "fused_xla")]

    def outer(plan):
        kw = {"gate_params": gp} if gated else {}

        def energy(a, w):
            return jnp.sum(plan.apply([a * w[i] for i in range(3)], **kw) ** 2)

        return lambda w: jnp.sum(jax.grad(energy)(x, w) ** 2)

    g_p, g_x = (jax.grad(outer(p))(w) for p in plans)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
def test_chain_kernel_vmap(backend):
    Ls, Lout = (2, 2, 2), 2
    xs = [_rand((4, 3, num_coeffs(L)), 30 + i) for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Lout, backend=backend)
    direct = cp.apply(xs)
    mapped = jax.vmap(lambda *a: cp.apply(list(a)))(*xs)
    np.testing.assert_allclose(np.asarray(mapped), np.asarray(direct),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# fourier_boundary: resident operands enter as grids; resident exit
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
def test_chain_kernel_resident_entry(backend):
    """A Fourier-resident operand enters the kernel AS A GRID (via the
    grid-evaluation sampling matrix) — no sh_to_fourier runs, and the result
    matches the all-SH kernel chain."""
    from repro.core import rep as _rep

    Ls, Lout, B = (2, 2, 1), 5, 7
    xs = [_rand((B, num_coeffs(L)), 40 + i) for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Lout, backend=backend)
    want = np.asarray(cp.apply(xs))
    resident = Rep.from_sh(xs[1], Ls[1]).to_fourier("half")
    with _rep.conversion_stats(fresh=True) as c:
        got = np.asarray(cp.apply([xs[0], resident, xs[2]]))
    assert c["sh_to_fourier"] == 0 and c["fourier_to_sh"] == 0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # dense-form residents coerce losslessly too
    got_d = np.asarray(cp.apply(
        [xs[0], Rep.from_sh(xs[1], Ls[1]).to_fourier("dense"), xs[2]]))
    np.testing.assert_allclose(got_d, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
def test_chain_kernel_resident_exit(backend):
    """out_basis='fourier' returns the resident half product grid — equal to
    the tree chain's resident exit, and projecting it recovers the SH out."""
    Ls, B = (1, 2, 1), 5
    Ltot = sum(Ls)
    xs = [_rand((B, num_coeffs(L)), 60 + i) for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Ltot, backend=backend)
    tree = engine.plan_chain(Ls, Ltot, backend="tree")
    got = cp.apply(xs, out_basis="fourier")
    want = tree.apply(xs, out_basis="fourier")
    assert got.is_fourier and got.L == Ltot and got.form == "half"
    np.testing.assert_allclose(np.asarray(got.data),
                               np.asarray(want.with_form("half").data),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got.to_sh().data),
                               np.asarray(tree.apply(xs)), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# rotation equivariance (testing/ oracle)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["fused_xla", "fused_pallas"])
def test_chain_kernel_rotation_equivariance(backend):
    Ls, Lout = (2, 1, 2), 2
    ang = random_angles(seed=5)
    xs = [np.asarray(random_irreps(L, (6,), seed=70 + i))
          for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Lout, backend=backend)
    out = np.asarray(cp.apply([jnp.asarray(x) for x in xs]))
    out_rot = np.asarray(cp.apply(
        [jnp.asarray(rotate_irreps(x, L, ang)) for x, L in zip(xs, Ls)]))
    np.testing.assert_allclose(out_rot, rotate_irreps(out, Lout, ang),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# ONE pallas_call: counter- and trace-proven
# --------------------------------------------------------------------------


def _count_pallas_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_pallas_eqns(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
    return n


def test_chain_kernel_single_pallas_call():
    """A 3-operand chain on the fused_pallas backend is ONE pallas_call:
    the kernel dispatch counter ticks once per apply, and the traced jaxpr
    contains exactly one pallas_call primitive (n+2 ops collapsed to 1)."""
    Ls, Lout, B = (2, 2, 2), 2, 8
    xs = [_rand((B, num_coeffs(L)), 80 + i) for i, L in enumerate(Ls)]
    cp = engine.plan_chain(Ls, Lout, backend="fused_pallas")
    reset_kernel_stats()
    jax.block_until_ready(cp.apply(xs))
    assert kernel_stats()["chain_pallas_calls"] == 1
    jaxpr = jax.make_jaxpr(lambda *a: cp.apply(list(a)))(*xs)
    assert _count_pallas_eqns(jaxpr.jaxpr) == 1
    # weights/resident entries don't change the dispatch count
    ws = [_rand((B, L + 1), 90 + i) for i, L in enumerate(Ls)]
    rep = Rep.from_sh(xs[1], Ls[1]).to_fourier("half")
    reset_kernel_stats()
    jax.block_until_ready(cp.apply([xs[0], rep, xs[2]], weights=[ws[0], None, ws[2]]))
    assert kernel_stats()["chain_pallas_calls"] == 1


@pytest.mark.parametrize("dtype", DTYPES)
def test_chain_kernel_grid_blocking_accumulates(dtype):
    """Large product grids run blocked over the sample axis (accumulating in
    the output block) and still match the unblocked kernel exactly — at
    both storage precisions (blocking must not change where bf16 rounds:
    accumulation stays f32 within and across grid blocks)."""
    Ls, Lout, B = (3, 3, 2), 4, 5
    xs = [_rand((B, num_coeffs(L)), 100 + i, dtype) for i, L in enumerate(Ls)]
    full = gaunt_chain_fused_pallas(xs, Ls, Lout, block_g=4096, interpret=True)
    blocked = gaunt_chain_fused_pallas(xs, Ls, Lout, block_g=128, interpret=True)
    np.testing.assert_allclose(np.asarray(blocked).astype(np.float64),
                               np.asarray(full).astype(np.float64),
                               rtol=1e-5, atol=1e-5)
    xla = gaunt_chain_fused_xla(xs, Ls, Lout)
    np.testing.assert_allclose(np.asarray(blocked).astype(np.float64),
                               np.asarray(xla).astype(np.float64),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# grid-resident gate fused into the chain (DESIGN.md §6.5)
# --------------------------------------------------------------------------


def _gate_params(C, seed):
    rng = np.random.default_rng(seed)
    return {"w1": jnp.asarray(rng.normal(size=(C, 16)) * 0.3, jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(16, C)) * 0.3, jnp.float32)}


@pytest.mark.parametrize("backend", engine.CHAIN_BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gated_chain_matches_sh_gate(backend, dtype, Ls=(2, 1, 2), Lout=3):
    """plan_chain(gate=True) == gate applied to the ungated product on SH
    coefficients — on EVERY chain backend (tree/looped gate at the exit; the
    collocation backends fuse the gate as a kernel pointwise stage)."""
    B, C = 5, 3
    xs = [_rand((B, C, num_coeffs(L)), 300 + i, dtype) for i, L in enumerate(Ls)]
    gp = _gate_params(C, 310)
    tree = engine.plan_chain(Ls, Lout, backend="tree")
    want = np.asarray(engine._gate_sh(
        gp, tree.apply([x.astype(jnp.float32) for x in xs])))
    cp = engine.plan_chain(Ls, Lout, backend=backend, dtype=dtype, gate=True)
    assert cp.gate and "+gate" in cp.describe()
    got = cp.apply(xs, gate_params=gp)
    assert got.dtype == jnp.dtype(dtype)
    assert_close(np.asarray(got).astype(np.float64), want, dtype=dtype,
                 tier="identity", tol=3e-5 if dtype == "float32" else None)


@pytest.mark.parametrize("backend", ["tree", "fused_xla", "fused_pallas"])
def test_gated_chain_resident_exit(backend):
    """A gated plan's out_basis='fourier' exit gates the product grid
    in-basis (no extra conversions) and projects back to the gated SH out."""
    Ls, B, C = (1, 2, 1), 4, 3
    Ltot = sum(Ls)
    xs = [_rand((B, C, num_coeffs(L)), 320 + i) for i, L in enumerate(Ls)]
    gp = _gate_params(C, 330)
    cp = engine.plan_chain(Ls, Ltot, backend=backend, gate=True)
    want = np.asarray(cp.apply(xs, gate_params=gp))
    rep = cp.apply(xs, out_basis="fourier", gate_params=gp)
    assert rep.is_fourier and rep.L == Ltot
    np.testing.assert_allclose(np.asarray(rep.to_sh().data), want,
                               rtol=1e-4, atol=1e-4)


def test_gated_looped_has_no_resident_exit():
    cp = engine.plan_chain((1, 1), 2, backend="looped", gate=True)
    xs = [_rand((4, 2, num_coeffs(1)), 340 + i) for i in range(2)]
    with pytest.raises(ValueError, match="no resident exit"):
        cp.apply(xs, out_basis="fourier", gate_params=_gate_params(2, 341))


def test_gated_chain_single_pallas_call():
    """The acceptance proof: the gate-fused chain is still ONE pallas_call —
    dispatch counter ticks once, and the traced jaxpr holds exactly one
    pallas_call primitive (the gate rides the kernel's pointwise stage, it
    does not add a dispatch)."""
    Ls, Lout, B, C = (2, 2, 2), 2, 8, 3
    xs = [_rand((B, C, num_coeffs(L)), 350 + i) for i, L in enumerate(Ls)]
    gp = _gate_params(C, 360)
    cp = engine.plan_chain(Ls, Lout, backend="fused_pallas", gate=True)
    reset_kernel_stats()
    jax.block_until_ready(cp.apply(xs, gate_params=gp))
    assert kernel_stats()["chain_pallas_calls"] == 1
    jaxpr = jax.make_jaxpr(
        lambda a, b, c, p: cp.apply([a, b, c], gate_params=p))(*xs, gp)
    assert _count_pallas_eqns(jaxpr.jaxpr) == 1


def test_gated_chain_grad_matches_xla():
    """The extended custom JVP: gradients through the fused gate (wrt both
    an operand and the gate MLP weights) match the XLA reference kernel."""
    Ls, Lout, B, C = (2, 1, 2), 3, 4, 3
    xs = [_rand((B, C, num_coeffs(L)), 370 + i) for i, L in enumerate(Ls)]
    gp = _gate_params(C, 380)
    plans = [engine.plan_chain(Ls, Lout, backend=b, gate=True)
             for b in ("fused_pallas", "fused_xla")]

    def loss(plan):
        return lambda a, p: jnp.sum(
            plan.apply([a, xs[1], xs[2]], gate_params=p) ** 2)

    gx_p, gw_p = jax.grad(loss(plans[0]), argnums=(0, 1))(xs[0], gp)
    gx_x, gw_x = jax.grad(loss(plans[1]), argnums=(0, 1))(xs[0], gp)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_x),
                               rtol=2e-3, atol=2e-3)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(np.asarray(gw_p[k]), np.asarray(gw_x[k]),
                                   rtol=2e-3, atol=2e-3)


def test_gated_plan_params_validation():
    cp = engine.plan_chain((1, 1), 2, backend="tree", gate=True)
    cpu = engine.plan_chain((1, 1), 2, backend="tree")
    xs = [_rand((4, 2, num_coeffs(1)), 390 + i) for i in range(2)]
    with pytest.raises(ValueError, match="gate_params"):
        cp.apply_jit(xs)
    with pytest.raises(ValueError, match="ungated"):
        cpu.apply_jit(xs, gate_params=_gate_params(2, 391))


def test_gated_chain_rotation_equivariance():
    """The fused gate is equivariant: its scalars are l=0 functions of the
    operands (rotation-invariant), so gating commutes with rotation."""
    Ls, Lout, C = (2, 1, 2), 2, 3
    ang = random_angles(seed=6)
    xs = [np.asarray(random_irreps(L, (5, C), seed=400 + i))
          for i, L in enumerate(Ls)]
    gp = _gate_params(C, 410)
    cp = engine.plan_chain(Ls, Lout, backend="fused_pallas", gate=True)
    out = np.asarray(cp.apply([jnp.asarray(x) for x in xs], gate_params=gp))
    out_rot = np.asarray(cp.apply(
        [jnp.asarray(rotate_irreps(x, L, ang)) for x, L in zip(xs, Ls)],
        gate_params=gp))
    np.testing.assert_allclose(out_rot, rotate_irreps(out, Lout, ang),
                               rtol=2e-3, atol=2e-3)


def test_gate_autotune_keys_and_policy():
    """Gated plans measure under their own key (("gate", 1) appended — the
    ungated persisted keys stay byte-identical), and select_gate caches a
    ("gate", "policy") entry whose value is 'grid' or 'sh'."""
    eng = engine.GauntEngine()
    Ls, B = (1, 1), 64
    cp = eng.plan_chain(Ls, 2, tune="measure", batch_hint=B, gate=True)
    assert cp.backend in engine.CHAIN_BACKENDS and cp.gate
    key = engine.PlanKey(1, 1, 2, kind="chain", batch_hint=B,
                         dtype="float32",
                         extra=(("Ls", Ls), ("entries", ("sh", "sh")),
                                ("out", "sh"), ("share", (0, 1)),
                                ("gate", 1)))
    assert eng._measured[key] == cp.backend
    # ungated key is untouched by the gated measurement
    ukey = engine.PlanKey(1, 1, 2, kind="chain", batch_hint=B,
                          dtype="float32",
                          extra=(("Ls", Ls), ("entries", ("sh", "sh")),
                                 ("out", "sh"), ("share", (0, 1))))
    assert ukey not in eng._measured
    pol = eng.select_gate(Ls, 2, batch_hint=B)
    assert pol in ("grid", "sh")
    pkey = engine.PlanKey(1, 1, 2, kind="chain", batch_hint=B,
                          dtype="float32",
                          extra=(("Ls", Ls), ("entries", ("sh", "sh")),
                                 ("out", "sh"), ("share", (0, 1)),
                                 ("gate", "policy")))
    assert eng._measured[pkey] == pol
    # cached: a second call re-times nothing
    runs = eng.timing_runs
    assert eng.select_gate(Ls, 2, batch_hint=B) == pol
    assert eng.timing_runs == runs


# --------------------------------------------------------------------------
# mixed-precision: the chain-entry dtype rule (DESIGN.md §3.6)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", engine.CHAIN_BACKENDS)
def test_chain_mixed_dtype_operands_cast_at_entry(backend):
    """THE chain-entry rule: SH operands arriving in a different storage
    dtype are cast ONCE at entry to the plan's storage dtype — uniformly
    across every chain backend, never backend-dependent.  An f32 plan fed
    mixed bf16/f32 operands returns f32 within bf16 input-quantization
    error; a bf16 plan fed f32 operands returns bf16."""
    Ls, Lout, B = (2, 1, 2), 2, 8
    xs32 = [_rand((B, num_coeffs(L)), 200 + i) for i, L in enumerate(Ls)]
    mixed = [xs32[0].astype(jnp.bfloat16), xs32[1],
             xs32[2].astype(jnp.bfloat16)]
    cp = engine.plan_chain(Ls, Lout, backend=backend)
    ref = np.asarray(cp.apply(xs32))
    got = cp.apply(mixed)
    assert got.dtype == jnp.float32, backend
    assert_close(np.asarray(got).astype(np.float64), ref,
                 dtype="bfloat16", tier="identity")
    cpb = engine.plan_chain(Ls, Lout, backend=backend, dtype="bfloat16")
    gotb = cpb.apply(xs32)
    assert gotb.dtype == jnp.bfloat16, backend
    assert_close(np.asarray(gotb).astype(np.float64), ref,
                 dtype="bfloat16", tier="identity")


# --------------------------------------------------------------------------
# chain autotune: measured, keyed like plans, cached
# --------------------------------------------------------------------------


def test_chain_autotune_measures_and_caches():
    eng = engine.GauntEngine()
    cp = eng.plan_chain((1, 1, 1), 1, tune="measure", batch_hint=64)
    assert cp.backend in engine.CHAIN_BACKENDS
    # keyed like plans: the measured selection is cached on the engine
    key = engine.PlanKey(1, 1, 1, kind="chain", batch_hint=64,
                         dtype="float32",
                         extra=(("Ls", (1, 1, 1)),
                                ("entries", ("sh", "sh", "sh")),
                                ("out", "sh"), ("share", (0, 1, 2))))
    assert eng._measured[key] == cp.backend
    assert eng.plan_chain((1, 1, 1), 1, tune="measure", batch_hint=64) is cp
    # heuristic default stays the resident tree (the counter-test contract)
    assert eng.plan_chain((1, 1, 1), 1).backend == "tree"
    # an explicit conversion pins the spectral pipeline
    assert eng.plan_chain((1, 1, 1), 1, conversion="dense",
                          tune="measure").backend == "tree"


def test_chain_autotune_entry_hint_keys_and_measures_resident():
    """Resident call sites measure on resident operands: the entry_hint is
    part of the autotune key, and the selected backend reproduces the tree
    result when fed the hinted operand kinds."""
    eng = engine.GauntEngine()
    Ls, Lout, B = (2, 2), 2, 16
    cp = eng.plan_chain(Ls, Lout, tune="measure", batch_hint=B,
                        entry_hint=("sh", "fourier"))
    assert cp.backend in engine.CHAIN_BACKENDS
    key = engine.PlanKey(2, 2, Lout, kind="chain", batch_hint=B,
                         dtype="float32",
                         extra=(("Ls", Ls), ("entries", ("sh", "fourier")),
                                ("out", "sh"), ("share", (0, 1))))
    assert eng._measured[key] == cp.backend
    x = _rand((B, num_coeffs(2)), 150)
    f = _rand((B, num_coeffs(2)), 151)
    rep = Rep.from_sh(f, 2).to_fourier("half")
    want = eng.plan_chain(Ls, Lout, backend="tree").apply([x, rep])
    got = cp.apply([x, rep])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        eng.plan_chain(Ls, Lout, tune="measure", entry_hint=("sh", "bogus"))


def test_chain_autotune_share_hint_measures_duplicates():
    """Selfmix-style [A]*nu chains measure with ONE repeated synthetic
    buffer (tree's shared single conversion engages in the timing), keyed
    separately from the all-distinct chain."""
    eng = engine.GauntEngine()
    Ls, B = (2, 2, 2), 32
    cp = eng.plan_chain(Ls, 2, tune="measure", batch_hint=B,
                        share_hint=(0, 0, 0))
    assert cp.backend in engine.CHAIN_BACKENDS
    key = engine.PlanKey(2, 2, 2, kind="chain", batch_hint=B,
                         dtype="float32",
                         extra=(("Ls", Ls), ("entries", ("sh",) * 3),
                                ("out", "sh"), ("share", (0, 0, 0))))
    assert eng._measured[key] == cp.backend
    x = _rand((B, num_coeffs(2)), 160)
    ws = [_rand((B, 3), 170 + i) for i in range(3)]
    want = eng.plan_chain(Ls, 2, backend="tree").apply_jit(
        [x, x, x], weights=ws)
    got = cp.apply_jit([x, x, x], weights=ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        eng.plan_chain(Ls, 2, tune="measure", share_hint=(0, 0))


def test_chain_autotune_result_matches_tree():
    eng = engine.GauntEngine()
    Ls, Lout, B = (2, 2), 2, 32
    xs = [_rand((B, num_coeffs(L)), 110 + i) for i, L in enumerate(Ls)]
    cp = eng.plan_chain(Ls, Lout, tune="measure", batch_hint=B)
    tree = eng.plan_chain(Ls, Lout, backend="tree")
    np.testing.assert_allclose(np.asarray(cp.apply_jit(xs)),
                               np.asarray(tree.apply_jit(xs)),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# sharded chains: ragged rows pad/slice over the device count
# --------------------------------------------------------------------------


def test_sharded_chain_ragged_rows_two_devices():
    """Chain shard_map granularity (ROADMAP): a 2-virtual-device shard_map
    chain with a row count that does NOT divide the device count pads, runs
    per-shard, slices — matching the unsharded chain exactly (the old code
    silently fell back to the constrained combine)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine
from repro.core.irreps import num_coeffs

assert jax.device_count() == 2
mesh = jax.make_mesh((2,), ("data",))
L = 2
for rows in (5, 7):  # ragged: neither divides 2
    xs = [jnp.asarray(np.random.default_rng(10 + i).normal(
        size=(rows, num_coeffs(L))), jnp.float32) for i in range(3)]
    ref = engine.plan_chain((L,) * 3, L).apply_jit(list(xs))
    sp = engine.ShardSpec(mesh=mesh, axes=("data",), mode="shard_map")
    cp = engine.plan_chain((L,) * 3, L, shard_spec=sp)
    got = cp.apply_jit(list(xs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # the per-shard combine really ran (not the constrained fallback): the
    # jaxpr of the sharded apply contains a shard_map primitive
    jaxpr = jax.make_jaxpr(lambda a, b, c: cp.apply([a, b, c]))(*xs)
    names = set()
    def walk(jx):
        for e in jx.eqns:
            names.add(e.primitive.name)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
    walk(jaxpr.jaxpr)
    assert any("shard_map" in n for n in names), sorted(names)
print("RAGGED_OK")
"""
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert "RAGGED_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-2000:])


# --------------------------------------------------------------------------
# consumers inherit the dispatch
# --------------------------------------------------------------------------


def test_manybody_tune_measure_matches_default():
    from repro.core.manybody import manybody_gaunt_product

    Ls, B = (2, 2, 2), 16
    xs = [_rand((B, num_coeffs(L)), 120 + i) for i, L in enumerate(Ls)]
    ref = manybody_gaunt_product(xs, Ls, Lout=2)
    got = manybody_gaunt_product(xs, Ls, Lout=2, tune="measure")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # a measured plan must still serve a resident exit: out_basis='fourier'
    # excludes the exit-less 'looped' candidate via the out hint
    rep = manybody_gaunt_product(xs, Ls, tune="measure", out_basis="fourier")
    ref_rep = manybody_gaunt_product(xs, Ls, out_basis="fourier")
    assert rep.is_fourier and rep.L == sum(Ls)
    np.testing.assert_allclose(np.asarray(rep.with_form("half").data),
                               np.asarray(ref_rep.with_form("half").data),
                               rtol=1e-4, atol=1e-4)


def test_selfmix_layer_tune_measure_matches_default():
    from repro.models.equivariant import SelfmixLayer

    L, C = 2, 3
    x = _rand((5, C, num_coeffs(L)), 130)
    layer = SelfmixLayer(L=L, channels=C, tp_impl="gaunt")
    params = layer.init(jax.random.PRNGKey(0))
    layer_m = SelfmixLayer(L=L, channels=C, tp_impl="gaunt", tune="measure")
    np.testing.assert_allclose(np.asarray(layer_m(params, x)),
                               np.asarray(layer(params, x)),
                               rtol=1e-4, atol=1e-4)


def test_segnn_chain_tune_measure_matches_default():
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import SegnnNBody

    import dataclasses

    cfg = EquivariantConfig(name="t", kind="segnn", L=1, L_edge=1, channels=4,
                            n_layers=2)
    n = 5
    rng = np.random.default_rng(140)
    charge = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    vel = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    m = SegnnNBody(cfg)
    params = m.init(jax.random.PRNGKey(1))
    ref = m.forward(params, charge, pos, vel)
    m_meas = SegnnNBody(dataclasses.replace(cfg, chain_tune="measure"))
    got = m_meas.forward(params, charge, pos, vel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
