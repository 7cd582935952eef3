"""The unified Gaunt engine: cross-backend equivalence against the complex128
numpy oracle, plan/constant caching, capability filtering, and autotune."""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import constants, engine
from repro.core.cg import gaunt_einsum_reference
from repro.core.gaunt import gaunt_product_numpy
from repro.core.irreps import num_coeffs
from repro.core.so3 import real_sph_harm_jax
from repro.testing import random_array

PAIRWISE = engine.available_backends("pairwise", requires_grad=False)
CONV = engine.available_backends("conv_filter", requires_grad=False)
MANYBODY = engine.available_backends("manybody", requires_grad=False)
CHANNEL_MIX = engine.available_backends("channel_mix", requires_grad=False)

# the full grid the acceptance criteria name: degrees up to L=6
GRID = [(1, 1, 2), (2, 3, 5), (4, 2, 3), (3, 3, 2), (6, 6, 12), (6, 4, 6)]


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(random_array(shape, seed), dtype=dtype)


def test_registry_is_complete():
    assert set(PAIRWISE) == {"dense_einsum", "fft", "direct", "packed", "rfft",
                             "fused_xla", "fused_pallas"}
    assert set(CONV) == set(PAIRWISE) | {"escn_aligned"}
    assert set(MANYBODY) == {"dense_einsum", "fft", "direct", "packed", "rfft"}
    assert set(CHANNEL_MIX) == {"dense_einsum", "fused_xla"}


@pytest.mark.parametrize("backend", PAIRWISE)
@pytest.mark.parametrize("L1,L2,Lout", GRID)
def test_pairwise_backends_vs_numpy_oracle(backend, L1, L2, Lout):
    x1 = np.random.default_rng(1).normal(size=(4, num_coeffs(L1))).astype(np.float32)
    x2 = np.random.default_rng(2).normal(size=(4, num_coeffs(L2))).astype(np.float32)
    ref = gaunt_product_numpy(x1, x2, L1, L2, Lout)
    p = engine.plan(L1, L2, Lout, backend=backend, requires_grad=False)
    got = np.asarray(p.apply(jnp.asarray(x1), jnp.asarray(x2)))
    scale = max(1.0, np.abs(ref).max())
    np.testing.assert_allclose(got, ref, atol=1e-4 * scale)


@pytest.mark.parametrize("batch", [(), (5,), (2, 3)])
@pytest.mark.parametrize("backend", PAIRWISE)
def test_pairwise_backends_batch_shapes(backend, batch):
    L1, L2, Lout = 2, 2, 3
    x1 = np.random.default_rng(3).normal(size=batch + (num_coeffs(L1),)).astype(np.float32)
    x2 = np.random.default_rng(4).normal(size=batch + (num_coeffs(L2),)).astype(np.float32)
    ref = gaunt_product_numpy(x1, x2, L1, L2, Lout)
    p = engine.plan(L1, L2, Lout, backend=backend, requires_grad=False)
    got = np.asarray(p.apply(jnp.asarray(x1), jnp.asarray(x2)))
    assert got.shape == batch + (num_coeffs(Lout),)
    np.testing.assert_allclose(got, ref, atol=1e-4)


@pytest.mark.parametrize("backend",
                         engine.available_backends("pairwise", dtype="bfloat16",
                                                   requires_grad=False))
def test_pairwise_backends_bfloat16(backend):
    """bf16 storage vs the f32 oracle on quantized inputs — bounds from the
    shared per-precision tiers (repro.testing.tol_for)."""
    from repro.testing import assert_close

    L1, L2, Lout = 2, 2, 4
    x1 = _rand((8, num_coeffs(L1)), 5, jnp.bfloat16)
    x2 = _rand((8, num_coeffs(L2)), 6, jnp.bfloat16)
    ref = gaunt_product_numpy(np.asarray(x1, np.float32), np.asarray(x2, np.float32),
                              L1, L2, Lout)
    p = engine.plan(L1, L2, Lout, dtype="bfloat16", backend=backend,
                    requires_grad=False)
    got = np.asarray(p.apply(x1, x2), dtype=np.float32)
    assert_close(got, ref, dtype="bfloat16", tier="identity")


@pytest.mark.parametrize("backend", PAIRWISE)
def test_pairwise_backends_weight_hooks(backend):
    L1, L2, Lout = 2, 3, 4
    x1 = _rand((3, num_coeffs(L1)), 7)
    x2 = _rand((3, num_coeffs(L2)), 8)
    w1 = _rand((3, L1 + 1), 9)
    w2 = _rand((3, L2 + 1), 10)
    w3 = _rand((3, Lout + 1), 11)
    from repro.core.gaunt import expand_degree_weights

    ref = gaunt_einsum_reference(
        x1 * expand_degree_weights(w1, L1), x2 * expand_degree_weights(w2, L2),
        L1, L2, Lout) * expand_degree_weights(w3, Lout)
    p = engine.plan(L1, L2, Lout, backend=backend, requires_grad=False)
    got = p.apply(x1, x2, w1, w2, w3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("backend", CONV)
def test_conv_filter_backends_vs_oracle(backend):
    L1, L2, Lout = 2, 2, 3
    x = _rand((10, num_coeffs(L1)), 12)
    v = np.random.default_rng(13).normal(size=(10, 3))
    r = jnp.asarray(v / np.linalg.norm(v, axis=-1, keepdims=True), jnp.float32)
    filt = real_sph_harm_jax(L2, r).astype(jnp.float32)
    ref = gaunt_einsum_reference(x, filt, L1, L2, Lout)
    p = engine.plan(L1, L2, Lout, kind="conv_filter", backend=backend,
                    requires_grad=False)
    got = p.apply(x, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-4)


@pytest.mark.parametrize("backend", MANYBODY)
def test_manybody_backends_vs_fold(backend):
    L, nu = 2, 3
    xs = [_rand((4, num_coeffs(L)), 20 + i) for i in range(nu)]
    acc = gaunt_einsum_reference(xs[0], xs[1], L, L)
    acc = gaunt_einsum_reference(acc, xs[2], 2 * L, L)
    p = engine.plan(kind="manybody", Ls=(L,) * nu, backend=backend)
    got = p.apply(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc), atol=1e-3)


@pytest.mark.parametrize("backend", CHANNEL_MIX)
def test_channel_mix_backends_vs_loop(backend):
    L1, L2, Lout = 2, 1, 2
    C1, C2, E = 3, 2, 4
    x1 = _rand((2, C1, num_coeffs(L1)), 30)
    x2 = _rand((2, C2, num_coeffs(L2)), 31)
    w = _rand((C1, C2, E), 32)
    ref = jnp.einsum(
        "cde,...cdk->...ek", w,
        jnp.stack([jnp.stack([gaunt_einsum_reference(x1[:, c], x2[:, d], L1, L2, Lout)
                              for d in range(C2)], axis=1)
                   for c in range(C1)], axis=1))
    p = engine.plan(L1, L2, Lout, kind="channel_mix", backend=backend)
    got = p.apply(x1, x2, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_plan_cache_hit_and_constants_built_once():
    """Planning the same op twice returns the same object and rebuilds no
    constants; applying it twice rebuilds no constants either."""
    eng = engine.get_engine()
    # unusual degrees so earlier tests have not warmed these cache entries
    p1 = eng.plan(5, 1, 4, backend="fft")
    stats_after_first = constants.cache_stats()
    p2 = eng.plan(5, 1, 4, backend="fft")
    assert p1 is p2
    x1 = _rand((2, num_coeffs(5)), 40)
    x2 = _rand((2, num_coeffs(1)), 41)
    jax.block_until_ready(p2.apply(x1, x2))
    jax.block_until_ready(p2.apply(x1, x2))
    stats_after_use = constants.cache_stats()
    misses_first = {k: v[1] for k, v in stats_after_first.items()}
    misses_use = {k: v[1] for k, v in stats_after_use.items()}
    assert misses_use == misses_first, "apply() rebuilt constants the plan owns"


def test_heuristic_selection_scales_with_batch():
    """Auto selection runs and returns an eligible backend at every size."""
    for B in (1, 64, 4096):
        p = engine.plan(4, 4, 4, batch_hint=B)
        assert p.backend in engine.available_backends("pairwise", requires_grad=True)


def test_grad_capability_filtering():
    # fused_pallas has no VJP: requires_grad must exclude it...
    with pytest.raises(ValueError):
        engine.plan(2, 2, 4, backend="fused_pallas", requires_grad=True)
    # ...and auto selection under grad must still differentiate fine
    p = engine.plan(2, 2, 4, batch_hint=16)
    x1 = _rand((16, num_coeffs(2)), 50)
    x2 = _rand((16, num_coeffs(2)), 51)
    g = jax.grad(lambda a, b: jnp.sum(p.apply(a, b) ** 2))(x1, x2)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_measured_autotune_caches_choice():
    eng = engine.GauntEngine()
    key_kwargs = dict(batch_hint=32, tune="measure", requires_grad=False)
    p1 = eng.plan(1, 1, 2, **key_kwargs)
    assert p1.backend in PAIRWISE
    assert len(eng._measured) == 1
    p2 = eng.plan(1, 1, 2, **key_kwargs)
    assert p2 is p1
    assert len(eng._measured) == 1  # second plan reused the measurement


def test_selection_rule_rejected():
    with pytest.raises(ValueError):
        engine.plan(2, 2, 5)  # Lout > L1+L2


def test_float64_requests_normalized_consistently():
    """Regression (dtype-mismatch path): with x64 disabled, float64 requests
    must collapse onto the float32 plans — same PlanKey hash, same capability
    set, same cached plan — instead of building complex128 constants that
    every apply silently downcasts."""
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: float64 is a real dtype here")
    assert engine._dtype_str("float64") == "float32"
    assert engine._dtype_str(jnp.complex128) == "float32"
    # available_backends must agree with plan() on the effective dtype:
    # fused backends only support f32/bf16, so a phantom-f64 query would
    # wrongly exclude them
    assert (engine.available_backends("pairwise", dtype="float64")
            == engine.available_backends("pairwise", dtype="float32"))
    p64 = engine.plan(2, 2, 4, dtype="float64", backend="fft")
    p32 = engine.plan(2, 2, 4, dtype="float32", backend="fft")
    assert p64 is p32  # one cache entry, consistent PlanKey hashing
    assert p64.key.dtype == "float32"
    # the fused backend is reachable under a float64 request
    engine.plan(2, 2, 4, dtype="float64", backend="fused_xla")
    x1 = _rand((3, num_coeffs(2)), 70)
    out = p64.apply(x1, x1)
    assert out.dtype == jnp.float32
    with pytest.raises(ValueError):
        engine._dtype_str(jnp.int32)  # non-float requests are rejected


def test_jit_containing_plan_and_apply():
    """Plans can be created and applied inside a jit trace (wrappers do)."""

    @jax.jit
    def f(a, b):
        p = engine.plan(2, 2, 4, backend="fused_xla")
        return p.apply(a, b)

    x1 = _rand((4, num_coeffs(2)), 60)
    x2 = _rand((4, num_coeffs(2)), 61)
    ref = gaunt_einsum_reference(x1, x2, 2, 2)
    np.testing.assert_allclose(np.asarray(f(x1, x2)), np.asarray(ref), atol=2e-4)


# ---------------------------------------------------------------------------
# mixed precision: storage/accumulation split, dtype='auto'
# ---------------------------------------------------------------------------


def test_plankey_storage_accumulation_split():
    """PlanKey.dtype is the STORAGE dtype; accumulation derives from it and
    never drops below f32 (DESIGN.md §3.6)."""
    k = engine.PlanKey(2, 2, 4, dtype="bfloat16")
    assert k.acc_dtype == "float32"
    assert engine.PlanKey(2, 2, 4, dtype="float32").acc_dtype == "float32"
    assert engine.PlanKey(2, 2, 4, dtype="float64").acc_dtype == "float64"
    assert k.with_dtype("float32") == engine.PlanKey(2, 2, 4, dtype="float32")


def test_dtype_auto_measures_both_precisions_and_caches():
    """dtype='auto' + tune='measure' times the f32 and bf16 siblings under
    one key family, picks bf16 only when it measured faster, and caches the
    family winner (second request returns the same plan object)."""
    eng = engine.GauntEngine()
    p = eng.plan(2, 2, 4, dtype="auto", tune="measure", batch_hint=64,
                 requires_grad=False)
    assert p.key.dtype in ("float32", "bfloat16")
    # winner cached under the 'auto' family key
    fam = engine.PlanKey(2, 2, 4, kind="pairwise", batch_hint=64, dtype="auto")
    assert eng._measured[fam] == p.key.dtype
    assert eng.plan(2, 2, 4, dtype="auto", tune="measure", batch_hint=64,
                    requires_grad=False) is p
    # the pick is justified: if bf16 won, its measured time beat f32's
    kb = fam.with_dtype("bfloat16")
    kf = fam.with_dtype("float32")
    if p.key.dtype == "bfloat16":
        assert eng._measured_t[kb] < eng._measured_t[kf]
    # heuristic mode never gambles: 'auto' resolves to float32
    assert eng.plan(2, 2, 4, dtype="auto", requires_grad=False).key.dtype == "float32"


def test_chain_dtype_auto_measures_and_caches():
    eng = engine.GauntEngine()
    cp = eng.plan_chain((2, 2), 2, dtype="auto", tune="measure", batch_hint=32)
    assert cp.dtype in ("float32", "bfloat16")
    assert eng.plan_chain((2, 2), 2, dtype="auto", tune="measure",
                          batch_hint=32) is cp
    # heuristic 'auto' resolves to float32
    assert eng.plan_chain((2, 2), 2, dtype="auto").dtype == "float32"
    x = _rand((32, num_coeffs(2)), 300)
    ref = eng.plan_chain((2, 2), 2, backend="tree").apply([x, x])
    from repro.testing import assert_close

    assert_close(np.asarray(cp.apply([x, x])).astype(np.float64),
                 np.asarray(ref), dtype=cp.dtype, tier="identity")


def test_plan_batch_buckets_key_on_storage_dtype():
    """plan_batch keys its buckets on storage dtype: the same workload at
    f32 and bf16 builds distinct bucket plans with the right output dtypes."""
    items = [(2, 2, 4, 4)]
    bp32 = engine.plan_batch([(2, 2, 4)], kind="pairwise", dtype="float32")
    bpb = engine.plan_batch([(2, 2, 4)], kind="pairwise", dtype="bfloat16")
    a = _rand((4, num_coeffs(2)), 310)
    b = _rand((4, num_coeffs(2)), 311)
    out32 = bp32.apply([(a, b)])[0]
    outb = bpb.apply([(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))])[0]
    assert out32.dtype == jnp.float32 and outb.dtype == jnp.bfloat16
    from repro.testing import assert_close

    assert_close(np.asarray(outb).astype(np.float64), np.asarray(out32),
                 dtype="bfloat16", tier="identity")


# ---------------------------------------------------------------------------
# measured-autotune key corners the persistent cache keys on (DESIGN.md §4.5)
# ---------------------------------------------------------------------------


def test_chain_measure_key_batch_hint_quantization_edges():
    """batch_hint quantizes to the power-of-two ladder [8, 16384]: hints <= 8
    share the bottom rung, hints above the cap collapse to ONE key — the
    invariant that keeps the measured (and persisted) table bounded."""
    def q(b):
        return engine.GauntEngine._chain_measure_key(
            (2, 2), 2, "float32", b, None, "sh", None).batch_hint

    assert q(None) is None  # no hint: one unquantized key
    for b in (1, 2, 7, 8):
        assert q(b) == 8  # the ladder starts at 8
    assert q(9) == 16 and q(12) == 16
    assert q(16384) == 16384
    for b in (16385, 100_000, 10**9):
        assert q(b) == 16384  # everything above the cap is one key
    # quantized hints literally share a measurement key
    mk = engine.GauntEngine._chain_measure_key
    assert mk((2, 2), 2, "float32", 3, None, "sh", None) == \
        mk((2, 2), 2, "float32", 8, None, "sh", None)
    assert mk((2, 2), 2, "float32", 20_000, None, "sh", None) == \
        mk((2, 2), 2, "float32", 10**8, None, "sh", None)
    # ...but a distinct out/share hint still splits the family
    assert mk((2, 2), 2, "float32", 3, None, "fourier", None) != \
        mk((2, 2), 2, "float32", 3, None, "sh", None)


def test_auto_key_family_across_clear():
    """The dtype='auto' family key and its siblings live and die together:
    clear() empties every measurement store (and the timing counter), and a
    fresh measurement afterwards repopulates the family from scratch."""
    eng = engine.GauntEngine()
    p = eng.plan(1, 1, 2, dtype="auto", tune="measure", batch_hint=16,
                 requires_grad=False)
    fam = engine.PlanKey(1, 1, 2, kind="pairwise", batch_hint=16, dtype="auto")
    winner = eng._measured[fam]
    assert winner == p.key.dtype and winner in ("float32", "bfloat16")
    assert fam.with_dtype(winner) in eng._measured_t
    assert eng.timing_runs > 0
    eng.clear()
    assert eng._measured == {} and eng._measured_t == {}
    assert eng.timing_runs == 0
    p2 = eng.plan(1, 1, 2, dtype="auto", tune="measure", batch_hint=16,
                  requires_grad=False)
    assert eng._measured[fam] == p2.key.dtype


# --------------------------------------------------------------------------
# measurement guards: no timing inside a trace, no silent candidate failure
# --------------------------------------------------------------------------


def test_trace_clean_is_false_inside_jit():
    seen = []

    def f(x):
        seen.append(engine._trace_clean())
        return x

    jax.jit(f)(1.0)
    jax.grad(f)(1.0)
    assert engine._trace_clean()
    assert seen == [False, False]


def test_measure_inside_jit_makes_no_timing_runs():
    """A measure-mode plan requested inside a jit trace keeps the safe
    default without timing; the same request made eagerly then measures."""
    eng = engine.GauntEngine()
    picks = []

    def f(x):
        picks.append(eng.plan_chain((1, 1), 2, tune="measure",
                                    batch_hint=16).backend)
        picks.append(eng.plan(1, 1, 2, tune="measure", batch_hint=16).backend)
        return x

    jax.jit(f)(1.0)
    assert eng.timing_runs == 0
    assert picks[0] == "tree"
    eng.plan_chain((1, 1), 2, tune="measure", batch_hint=16)
    assert eng.timing_runs == 1


@pytest.mark.parametrize("site", ["chain", "plan"])
def test_failing_autotune_candidate_is_recorded(monkeypatch, site):
    def refuse(*a, **k):
        raise RuntimeError("refused by the compiler")

    eng = engine.GauntEngine()
    if site == "chain":
        monkeypatch.setattr(engine, "_build_chain_fused", refuse)
        picked = eng.plan_chain((1, 1), 2, tune="measure",
                                batch_hint=16).backend
    else:
        spec = engine._REGISTRY["fused_xla"]
        monkeypatch.setitem(engine._REGISTRY, "fused_xla",
                            dataclasses.replace(spec, build=refuse))
        picked = eng.plan(1, 1, 2, tune="measure", batch_hint=16,
                          requires_grad=False).backend
    assert picked != "fused_xla"
    assert len(eng.autotune_failures) == 1
    rec = eng.autotune_failures[0]
    assert rec["site"] == site and rec["candidate"] == "fused_xla"
    assert "refused by the compiler" in rec["error"]
    eng.clear()
    assert eng.autotune_failures == []


# --------------------------------------------------------------------------
# expand_degree_weights: per-degree broadcasts, no gather and no scatter
# --------------------------------------------------------------------------


@pytest.mark.parametrize("lead", [(), (3,), (2, 3, 5)])
@pytest.mark.parametrize("L", range(7))
def test_expand_degree_weights_equals_gather(L, lead):
    from repro.core.irreps import l_array

    w = _rand(lead + (L + 1,), 20 + L)
    got = engine.expand_degree_weights(w, L)
    ref = w[..., jnp.asarray(l_array(L).astype(np.int32))]
    assert got.shape == lead + (num_coeffs(L),)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("L", range(7))
def test_expand_degree_weights_vjp_sums_each_degree(L):
    w = _rand((4, L + 1), 30 + L)
    ct = _rand((4, num_coeffs(L)), 40 + L)
    _, vjp = jax.vjp(lambda v: engine.expand_degree_weights(v, L), w)
    (got,) = vjp(ct)
    ref = np.stack([np.asarray(ct)[:, l * l:(l + 1) ** 2].sum(-1)
                    for l in range(L + 1)], axis=-1)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("L", [1, 2])
def test_mace_force_backward_has_no_scatter(L):
    """Forces take the transpose of the per-edge radial weights' expansion;
    a gather there would leave a scatter-add (a serial loop on the TPU)."""
    from repro.configs.gaunt_ff import EquivariantConfig
    from repro.models.equivariant import MaceGaunt

    cfg = EquivariantConfig(name="t", kind="mace", L=L, L_edge=3, channels=8,
                            n_layers=2, nu=3, n_species=4, hidden=16,
                            conv_impl="escn")
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = 12
    species = jnp.arange(n) % cfg.n_species
    pos = _rand((n, 3), 50) * 2.0
    hlo = (jax.jit(jax.grad(model.energy_masked, argnums=2))
           .lower(params, species, pos, jnp.ones(n)).compile().as_text())
    assert " scatter(" not in hlo


# --------------------------------------------------------------------------
# the plans each benchmarked configuration serves with, pinned
# --------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What the engine picks for every plan key one served step (vmapped energy +
# forces over slots) builds at each configuration of BENCHMARK.json: the
# backend the step's plans were built with, the cost model's own pick for
# that key when no backend is forced, and each chain's flavour.  Under
# heuristic tuning no key carries a batch hint, so the slot shape does not
# enter them.  A change to the cost model or the backend registry that moves
# a pick shows here before it reaches the chip.
SERVED_PICKS = {
    "mace-3bpa": {
        "conv_filter (2, 3)->2": ("escn_aligned", "dense_einsum"),
        "conv_filter (2, 3)->2 geometry=wigner": ("escn_aligned",
                                                  "escn_aligned"),
        "chain (2, 2, 2)->2": "tree conversion=half conv=rfft",
    },
    "mace-off23-medium": {
        "conv_filter (1, 3)->1": ("escn_aligned", "dense_einsum"),
        "conv_filter (1, 3)->1 geometry=wigner": ("escn_aligned",
                                                  "escn_aligned"),
        "chain (1, 1, 1)->1": "tree conversion=half conv=rfft",
    },
    "eqv2-l6m2-selfmix": {
        "chain (6, 6)->6": "tree conversion=half conv=rfft",
    },
}


def _served_picks(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        path = {c["name"]: c["file"] for c in json.load(f)["configs"]}[name]
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    family = config["family"]
    spec = importlib.util.spec_from_file_location(
        f"{family}_program_t", os.path.join(ROOT, "bench", "programs",
                                            f"{family}.py"))
    program = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program)

    eng = engine.get_engine()
    eng.clear()
    model = program.build(config)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    k = config["model"].get("max_neighbors")
    energy = model.energy_masked if k is None else model.energy_graph

    def step(params, *inputs):
        def one(sp, p, m, *g):
            return jax.value_and_grad(
                lambda q: energy(params, sp, q, m, *g))(p)
        return jax.vmap(one)(*inputs)

    slots, atoms = 2, 8
    inputs = [jax.ShapeDtypeStruct((slots, atoms), jnp.int32),
              jax.ShapeDtypeStruct((slots, atoms, 3), jnp.float32),
              jax.ShapeDtypeStruct((slots, atoms), jnp.float32)]
    if k is not None:
        inputs += [jax.ShapeDtypeStruct((slots, atoms, k), jnp.int32),
                   jax.ShapeDtypeStruct((slots, atoms, k), jnp.float32)]
    with jax.default_matmul_precision(config["precision"]["matmul"]):
        jax.eval_shape(step, params, *inputs)

    picks = {}
    for p in eng.plans():
        key = p.key
        assert key.batch_hint is None and key.dtype == "float32"
        opts = "".join(f" {o}={v}" for o, v in key.extra)
        picks[f"{key.kind} ({key.L1}, {key.L2})->{key.Lout}{opts}"] = (
            p.backend, eng.select(key))
    for c in eng._chains.values():
        assert c.dtype == "float32" and not c.gate
        picks[f"chain {tuple(c.Ls)}->{c.Lout}"] = (
            f"{c.backend} conversion={c.conversion} conv={c.conv}")
    eng.clear()
    return picks


@pytest.mark.parametrize("name", sorted(SERVED_PICKS))
def test_served_plans_are_pinned(name):
    assert _served_picks(name) == SERVED_PICKS[name]
