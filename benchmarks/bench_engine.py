"""Engine autotune sweep: what does the unified Gaunt engine pick, and how
fast is the pick, per (kind, L, batch)?

With ``backend='auto'`` the engine's measured autotuner chooses among all
eligible backends (the heuristic cost-model pick is reported alongside, so
divergence between model and measurement is visible in the record stream);
any other value pins that backend for the whole sweep.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.irreps import num_coeffs

from .common import record, time_fn


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def _bytes_moved(Ls, Lout, B, dtype: str = "float32") -> int:
    """Estimated bytes moved by one collocation-style product at ``dtype``
    storage (DESIGN.md §3.6): operand/output SH rows + sampling/projection
    constants at storage width, the per-operand sample grids and the product
    grid at accumulation width (always >= f32).  An analytic traffic model —
    not a hardware counter — so mixed-precision records report bandwidth
    *utilization* (bytes/us) on a common scale, not just relative speedup."""
    sb = {"bfloat16": 2, "float64": 8}.get(dtype, 4)
    ab = 8 if dtype == "float64" else 4
    nin = sum(num_coeffs(L) for L in Ls)
    G = (2 * sum(Ls) + 2) ** 2  # alias-free collocation grid (pre lane-pad)
    io = B * (nin + num_coeffs(Lout)) * sb          # operand + output rows
    consts = (nin + num_coeffs(Lout)) * G * sb      # T_i and P matrices
    grids = B * G * (len(Ls) + 1) * ab              # sampled + product grids
    return io + consts + grids


def _time_many(fns_args, iters: int = 10, warmup: int = 3) -> float:
    """Median microseconds for one sweep over [(fn, args), ...] — the looped
    dispatch pattern plan_batch replaces."""
    import time

    def sweep():
        outs = [fn(*args) for fn, args in fns_args]
        jax.block_until_ready(outs)

    for _ in range(warmup):
        sweep()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sweep()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def run_batched(backend: str = "auto", csv=True):
    """Batched-vs-looped: one plan_batch invocation vs per-plan dispatch
    loops, for (a) many same-degree items and (b) a ragged mixed-degree set."""
    from .common import record

    records = []
    eng = engine.get_engine()
    be = None if backend == "auto" else backend
    # (name, items, pinned backend or None=CLI choice): tiny items are
    # dispatch-bound (batching amortizes call overhead); the spectral
    # 'direct' pipeline is many-small-ops per call (batching fuses them);
    # the ragged set exercises multi-bucket slicing
    workloads = [
        ("tiny_x32_B4", [(2, 2, 4, 4)] * 32, be),
        ("direct_x16_B64", [(2, 2, 4, 64)] * 16, be or "direct"),
        ("mixedL_ragged", [(1, 1, 2, 64), (2, 2, 4, 64), (3, 3, 6, 64),
                           (2, 2, 4, 32)] * 4, be),
    ]
    for name, items, be in workloads:
        ins = [(_rand((n, num_coeffs(L1)), 2 * i),
                _rand((n, num_coeffs(L2)), 2 * i + 1))
               for i, (L1, L2, Lout, n) in enumerate(items)]
        # looped: one jitted dispatch per item (the pre-batching consumer)
        fns_args = []
        for (L1, L2, Lout, n), args in zip(items, ins):
            p = eng.plan(L1, L2, Lout, batch_hint=n, backend=be,
                         requires_grad=False)
            fns_args.append((jax.jit(lambda a, b, p=p: p.apply(a, b)), args))
        t_loop = _time_many(fns_args)
        # batched: one fused invocation per degree bucket
        bp = eng.plan_batch(items, backend=be, requires_grad=False)
        t_batch = _time_many([(lambda: jax.block_until_ready(bp.apply(ins)), ())])
        record(records, f"engine_batched_{name}", t_batch, echo=csv,
               looped_us=round(t_loop, 1),
               speedup_vs_looped=round(t_loop / t_batch, 2),
               buckets=len(bp.buckets),
               backends=",".join(sorted({b.plan.backend for b in bp.buckets})))
    return records


def run(L_list=(1, 2, 3, 4, 6), B_list=(64, 1024), backend: str = "auto", csv=True):
    records = []
    eng = engine.get_engine()
    # install the host-measured fused cost factor BEFORE recording heuristic
    # picks: the regret guard bounds the *calibrated* cost model (the one
    # heuristic-mode plans actually use after calibrate_fused), not the
    # shipped default factor
    eng.calibrate_fused()
    for L in L_list:
        for B in B_list:
            x1 = _rand((B, num_coeffs(L)), 0)
            x2 = _rand((B, num_coeffs(L)), 1)
            kw = dict(batch_hint=B, requires_grad=False)
            if backend == "auto":
                p = eng.plan(L, L, L, tune="measure", **kw)
            else:
                p = eng.plan(L, L, L, backend=backend, **kw)
            heuristic = eng.select(p.key)
            t = time_fn(jax.jit(lambda a, b: p.apply(a, b)), x1, x2)
            extra = {}
            if heuristic != p.backend:
                # cost-model/measured disagreement: time the heuristic pick so
                # the record (and the CI guard) can bound the regret
                ph = eng.plan(L, L, L, backend=heuristic, **kw)
                th = time_fn(jax.jit(lambda a, b: ph.apply(a, b)), x1, x2)
                extra = {"heuristic_us": round(th, 1),
                         "heuristic_ratio": round(th / t, 2)}
            nb = _bytes_moved((L, L), L, B)
            record(records, f"engine_pairwise_L{L}_B{B}", t, echo=csv,
                   backend=p.backend, heuristic=heuristic,
                   bytes_moved=nb, gbps=round(nb / t / 1e3, 2), **extra)
        # conv_filter: the message-passing hot path
        B = B_list[-1]
        x = _rand((B, num_coeffs(L)), 2)
        v = np.random.default_rng(3).normal(size=(B, 3))
        r = jnp.asarray(v / np.linalg.norm(v, axis=-1, keepdims=True), jnp.float32)
        kw = dict(kind="conv_filter", batch_hint=B, requires_grad=False)
        if backend == "auto":
            p = eng.plan(L, L, L, tune="measure", **kw)
        else:
            be = backend if backend in engine.available_backends("conv_filter", requires_grad=False) else "escn_aligned"
            p = eng.plan(L, L, L, backend=be, **kw)
        heuristic = eng.select(p.key)
        t = time_fn(jax.jit(lambda a, b: p.apply(a, b)), x, r)
        record(records, f"engine_conv_L{L}_B{B}", t, echo=csv,
               backend=p.backend, heuristic=heuristic)
    return records


def run_chain(csv=True):
    """Fourier-resident chain plans vs the looped per-product Fourier path.

    Each workload is a *chained* product (many-body trees, shared-operand
    selfmix, a conv layer stack with fixed edge geometry).  The looped
    baseline pays the full SH->Fourier->SH round trip per product (the 'fft'
    backend); the resident path plans the whole chain, converting each
    operand once and projecting once.  Records per-workload eliminated
    conversion counts (measured by the `repro.core.rep` counters, not
    inferred) and end-to-end speedup.
    """
    import numpy as _np

    from repro.core import rep
    from repro.core.engine import expand_degree_weights
    from repro.core.irreps import num_coeffs as _nc
    from repro.core.rep import Rep
    from repro.core.so3 import real_sph_harm_jax

    records = []
    eng = engine.get_engine()

    def _counts(fn):
        rep.reset_conversion_stats()
        jax.block_until_ready(fn())
        c = rep.conversion_stats()
        return c["sh_to_fourier"], c["fourier_to_sh"]

    # ---- chained products: many-body trees + shared-operand selfmix ------
    workloads = [
        # MACE's actual many-body shape: B_nu = A (x) A (x) A, per-operand
        # weights — the shared operand converts ONCE (degree-resolved).
        # Measured at L=3: the regime where the Fourier path is competitive
        # at all (at L<=2 CG wins regardless of conversion strategy)
        ("mace_mb_L3_nu3_B128", (3, 3, 3), 3, 128, True),
        ("manybody_L3_nu3_B128", (3, 3, 3), 3, 128, False),
        ("manybody_L2_nu4_B256", (2, 2, 2, 2), 2, 256, False),
        ("manybody_L4_nu3_B64", (4, 4, 4), 4, 64, False),
        ("selfmix_L4_B256", (4, 4), 4, 256, True),
        ("selfmix_L6_B64", (6, 6), 6, 64, True),
    ]
    for name, Ls, Lout, B, shared in workloads:
        if shared:
            x = _rand((B, _nc(Ls[0])), 1)
            xs = [x] * len(Ls)
            ws = [_rand((B, L + 1), 10 + i) for i, L in enumerate(Ls)]
        else:
            xs = [_rand((B, _nc(L)), i) for i, L in enumerate(Ls)]
            ws = None
        plans = []
        La = Ls[0]
        for i, L in enumerate(Ls[1:], start=1):
            Lt = Lout if i == len(Ls) - 1 else La + L
            # the historical per-product default: direct for small L, else fft
            be = engine.spectral_default(La, L)
            plans.append(eng.plan(La, L, Lt, backend=be, requires_grad=False))
            La += L

        def looped(*xf, _plans=plans, _ws=ws, _Ls=Ls):
            acc = xf[0]
            if _ws is not None:
                acc = acc * expand_degree_weights(_ws[0], _Ls[0]).astype(acc.dtype)
            for i, p in enumerate(_plans, start=1):
                acc = p.apply(acc, xf[i], None, _ws[i] if _ws else None)
            return acc

        cp = eng.plan_chain(Ls, Lout)  # auto: half grids, direct/rfft by shape

        s2f_l, f2s_l = _counts(lambda: looped(*xs))
        s2f_c, f2s_c = _counts(lambda: cp.apply(xs, weights=ws))
        t_loop = time_fn(jax.jit(looped), *xs)
        # time apply_jit, NOT jax.jit(cp.apply): a bare jit boundary hands a
        # shared operand to n distinct tracers, silently un-deduplicating the
        # very conversion this benchmark measures — apply_jit dedups first
        t_chain = time_fn(lambda: cp.apply_jit(xs, weights=ws))
        record(records, f"engine_chain_{name}", t_chain, echo=csv,
               looped_us=round(t_loop, 1),
               speedup_vs_looped=round(t_loop / t_chain, 2),
               conversions=f"{s2f_c}+{f2s_c}",
               looped_conversions=f"{s2f_l}+{f2s_l}",
               pairs_eliminated=min(s2f_l - s2f_c, f2s_l - f2s_c),
               conversions_eliminated=(s2f_l + f2s_l) - (s2f_c + f2s_c))

    # ---- conv layer stack: filter resident across layers -----------------
    # Execution matches the real consumer pattern: one dispatch per layer
    # (each layer's plan is its own jitted call, as in the model stacks), so
    # the looped path genuinely re-materializes and re-converts the filter
    # every layer — a single mega-jit would let XLA CSE hide that cost, which
    # is exactly what eager/streaming serving does NOT get.
    for name, L, n_layers, B in [("convstack_L2_x8_B512", 2, 8, 512),
                                 ("convstack_L3_x8_B256", 3, 8, 256)]:
        x0 = _rand((B, _nc(L)), 3)
        v = _np.random.default_rng(4).normal(size=(B, 3))
        r = jnp.asarray(v / _np.linalg.norm(v, axis=-1, keepdims=True),
                        jnp.float32)
        be = engine.spectral_default(L, L)
        p_loop = eng.plan(L, L, L, kind="conv_filter", backend=be,
                          requires_grad=False)
        # resident stack: half-grid (real-input) boundary plan + a filter
        # converted once for the whole stack; conv follows the chain policy
        p_res = eng.plan(L, L, L, backend="rfft", requires_grad=False,
                         options={"boundary": ("sh", "fourier", "sh"),
                                  "conv": "direct" if L <= 4 else "rfft"})
        f_loop = jax.jit(lambda x, r: p_loop.apply(x, r))
        f_res = jax.jit(lambda x, filt: p_res.apply(x, filt))
        f_filt = jax.jit(
            lambda r: Rep.from_sh(real_sph_harm_jax(L, r), L).to_fourier("half"))

        def looped(x, r):
            for _ in range(n_layers):
                x = f_loop(x, r)
            return x

        def resident(x, r):
            filt = f_filt(r)
            for _ in range(n_layers):
                x = f_res(x, filt)
            return x

        # count the REAL executions (eager per-layer applies — each dispatch
        # runs its conversions), not a one-layer count extrapolated by hand
        def looped_eager():
            for _ in range(n_layers):
                p_loop.apply(x0, r)

        def resident_eager():
            filt = Rep.from_sh(real_sph_harm_jax(L, r), L).to_fourier("half")
            for _ in range(n_layers):
                p_res.apply(x0, filt)

        s2f_l, f2s_l = _counts(looped_eager)
        s2f_c, f2s_c = _counts(resident_eager)
        t_loop = time_fn(lambda: looped(x0, r))
        t_chain = time_fn(lambda: resident(x0, r))
        # each layer still checkpoints to SH (the projection is the layer's
        # degree truncation), so the elision here is the filter's sh->F
        record(records, f"engine_chain_{name}", t_chain, echo=csv,
               looped_us=round(t_loop, 1),
               speedup_vs_looped=round(t_loop / t_chain, 2),
               conversions=f"{s2f_c}+{f2s_c}",
               looped_conversions=f"{s2f_l}+{f2s_l}",
               conversions_eliminated=(s2f_l + f2s_l) - (s2f_c + f2s_c))

    # ---- eSCN geometry residency: Wigner blocks hoisted per geometry -----
    # The rotation-aligned conv used to rebuild align_rotation + the CG
    # Wigner recursion from the SAME layer-constant rhat inside every
    # layer's dispatch; `EquivariantConv.geometry_rep` hoists them once per
    # geometry (ROADMAP "eSCN geometry residency") and the aligned conv
    # consumes the precomputed WignerBlocks through its bucket.
    from repro.core.conv import EquivariantConv

    for name, L, n_layers, B in [("escn_wigner_L2_x8_B512", 2, 8, 512),
                                 ("escn_wigner_L3_x8_B256", 3, 8, 256)]:
        x0 = _rand((B, _nc(L)), 5)
        v = _np.random.default_rng(6).normal(size=(B, 3))
        r = jnp.asarray(v / _np.linalg.norm(v, axis=-1, keepdims=True),
                        jnp.float32)
        conv = EquivariantConv(L, L, L, method="escn")

        def looped(x, r, _conv=conv, _n=n_layers):
            for _ in range(_n):
                x = _conv(x, r)
            return x

        def resident(x, r, _conv=conv, _n=n_layers):
            geom = _conv.geometry_rep(r)
            for _ in range(_n):
                x = _conv(x, geom)
            return x

        t_loop = time_fn(lambda: looped(x0, r))
        t_res = time_fn(lambda: resident(x0, r))
        record(records, f"engine_chain_{name}", t_res, echo=csv,
               looped_us=round(t_loop, 1),
               speedup_vs_looped=round(t_loop / t_res, 2),
               wigner_builds=f"1-vs-{n_layers}")
    return records


def run_chain_kernel(csv=True):
    """Measured chain autotune (DESIGN.md §6.4): per chained workload, which
    ChainPlan backend does the measured autotuner pick, and how does the pick
    compare to the resident tree-conv baseline?

    Also measures and records the fused cost model's skinny-matmul
    calibration constant (`engine_calibration_fused_skinny`) — heuristic-mode
    plans on this host then use the measured factor instead of the CPU-era
    default.  The CI guard fails if the autotuner picks the collocation
    kernel on a workload where it then *loses* to tree-conv, or if the
    kernel wins nowhere at all (the autotune fold would be dead weight).
    """
    from repro.core.irreps import num_coeffs as _nc
    from repro.kernels import gaunt_fused as _gk

    records = []
    eng = engine.get_engine()
    cal = eng.calibrate_fused()
    record(records, "engine_calibration_fused_skinny", cal["fused_xla_us"],
           echo=csv, factor=cal["factor"],
           dense_einsum_us=cal["dense_einsum_us"],
           default_factor=4.0)
    # chained workloads spanning the regimes: short fat chains (collocation's
    # home turf — one dispatch vs many small spectral ops), long thin chains
    # (tree-conv's home turf: grids grow as sum(L) and the collocation grid
    # pays G ~ (2*sum(L)+2)^2 per operand), and a full-degree exit
    workloads = [
        ("L1x3_B512", (1, 1, 1), 1, 512),
        ("L2x2_B64", (2, 2), 2, 64),
        ("L2x3_B128", (2, 2, 2), 2, 128),
        ("L3x3_B64", (3, 3, 3), 3, 64),
        ("L2x4_B256_full", (2, 2, 2, 2), 8, 256),
    ]
    for name, Ls, Lout, B in workloads:
        xs = [_rand((B, _nc(L)), 7 + i) for i, L in enumerate(Ls)]
        cp = eng.plan_chain(Ls, Lout, tune="measure", batch_hint=B)
        tree = eng.plan_chain(Ls, Lout, backend="tree")
        t_pick = time_fn(lambda: cp.apply_jit(xs))
        t_tree = time_fn(lambda: tree.apply_jit(xs))
        # dispatch proof data: the collocation backends tick the kernel-call
        # counter once per trace — the pallas flavor is ONE pallas_call
        extra = {}
        if cp.backend == "fused_pallas":
            _gk.reset_kernel_stats()
            jax.block_until_ready(cp.apply(xs))
            extra["pallas_calls"] = _gk.kernel_stats()["chain_pallas_calls"]
        record(records, f"engine_chain_kernel_{name}", t_pick, echo=csv,
               backend=cp.backend, tree_us=round(t_tree, 1),
               speedup_vs_tree=round(t_tree / t_pick, 2),
               n_operands=len(Ls), **extra)
    return records


def run_grid_gate(csv=True):
    """Grid-resident equivariant gates (DESIGN.md §6.5): the fused pointwise
    gate stage vs the SH-gate baseline, per chained workload.

    Two workload families, both computing the IDENTICAL function on both
    paths (the gate is affine on the sphere once its scalars are known, so
    the grid evaluation is exact — the recorded ``err`` is storage roundoff,
    not aliasing, and the CI guard holds it to ``BENCH_GUARD_GATE_TOL``):

    * ``region_*`` — a TP -> gate -> selfmix layer region.  Resident path:
      the gate fuses into chain 1 (pointwise stage on the product grid) and
      the gated product enters chain 2 still Fourier-resident — one exit
      conversion for the whole region.  SH path: chain 1 exits to SH, the
      gate runs on coefficients, chain 2 re-enters — the exit/re-entry pair
      the fusion elides.
    * ``selfmix_*`` — MACE's gated many-body chain (grid_gate='on' layer
      shape): gate fused into the selfmix kernel vs the ungated chain plus
      the SH affine epilogue.

    Each record carries the measured ``auto`` gate policy for the workload
    (engine.select_gate) so the guard can fail a policy that picks the grid
    gate where the bench shows it losing.
    """
    from repro.core.engine import _gate_sh

    records = []
    eng = engine.get_engine()

    def _gp(B, seed):
        rng = np.random.default_rng(seed)
        return {"w1": jnp.asarray(rng.normal(size=(B, 16)), jnp.float32) * .3,
                "w2": jnp.asarray(rng.normal(size=(16, B)), jnp.float32) * .3}

    def _err(got, ref):
        got = np.asarray(got, np.float64)
        ref = np.asarray(ref, np.float64)
        return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))

    # ---- TP -> gate -> selfmix regions -----------------------------------
    for name, L1, L2, Lout, B in [("region_L2xL2_B256", 2, 2, 2, 256),
                                  ("region_L1xL1_B1024", 1, 1, 1, 1024)]:
        Lt = L1 + L2
        xs = [_rand((B, num_coeffs(L)), 30 + i) for i, L in enumerate((L1, L2))]
        gp = _gp(B, 40)
        kw = dict(tune="measure", batch_hint=B)
        cp1g = eng.plan_chain((L1, L2), Lt, gate=True, out_hint="fourier",
                              **kw)
        cp1 = eng.plan_chain((L1, L2), Lt, **kw)
        cp2f = eng.plan_chain((Lt, Lt), Lout, share_hint=(0, 0),
                              entry_hint=("fourier", "fourier"), **kw)
        cp2s = eng.plan_chain((Lt, Lt), Lout, share_hint=(0, 0), **kw)

        def grid_path(_cp1g=cp1g, _cp2=cp2f, _xs=xs, _gp=gp):
            mid = _cp1g.apply_jit(_xs, out_basis="fourier", gate_params=_gp)
            return _cp2.apply_jit([mid, mid])

        def sh_path(_cp1=cp1, _cp2=cp2s, _xs=xs, _gp=gp):
            mid = _gate_sh(_gp, _cp1.apply_jit(_xs))
            return _cp2.apply_jit([mid, mid])

        err = _err(grid_path(), sh_path())
        t_grid = time_fn(lambda: jax.block_until_ready(grid_path()))
        t_sh = time_fn(lambda: jax.block_until_ready(sh_path()))
        pol = eng.select_gate((L1, L2), Lt, batch_hint=B, out_hint="fourier")
        record(records, f"engine_grid_gate_{name}", t_grid, echo=csv,
               sh_gate_us=round(t_sh, 1),
               speedup_vs_sh_gate=round(t_sh / t_grid, 2),
               err=round(err, 6), auto_policy=pol,
               backends=f"{cp1g.backend}+{cp2f.backend}")

    # ---- MACE-shaped gated selfmix chains --------------------------------
    for name, L, nu, B in [("selfmix_L2_nu3_B256", 2, 3, 256),
                           ("selfmix_L3_nu3_B64", 3, 3, 64)]:
        x = _rand((B, num_coeffs(L)), 50)
        xs = [x] * nu
        gp = _gp(B, 51)
        kw = dict(tune="measure", batch_hint=B, share_hint=(0,) * nu)
        cpg = eng.plan_chain((L,) * nu, L, gate=True, **kw)
        cps = eng.plan_chain((L,) * nu, L, **kw)
        err = _err(cpg.apply_jit(xs, gate_params=gp),
                   _gate_sh(gp, cps.apply_jit(xs)))
        t_grid = time_fn(
            lambda: jax.block_until_ready(cpg.apply_jit(xs, gate_params=gp)))
        t_sh = time_fn(
            lambda: jax.block_until_ready(_gate_sh(gp, cps.apply_jit(xs))))
        pol = eng.select_gate((L,) * nu, L, batch_hint=B,
                              share_hint=(0,) * nu)
        record(records, f"engine_grid_gate_{name}", t_grid, echo=csv,
               sh_gate_us=round(t_sh, 1),
               speedup_vs_sh_gate=round(t_sh / t_grid, 2),
               err=round(err, 6), auto_policy=pol, backend=cpg.backend)
    return records


def run_mixed_precision(csv=True):
    """bf16 storage vs its f32 sibling, per workload (DESIGN.md §3.6).

    For pairwise and chained workloads this times the SAME op planned at
    float32 and bfloat16 storage, measures the numerical gap on identical
    (bf16-quantized) inputs, and reports what ``dtype='auto'`` under the
    measured autotuner picked for that key family.  The CI guard holds every
    record to the documented bf16 error budget AND forbids the autotuner
    from keeping a bf16 plan that *loses* to its f32 sibling — it does NOT
    require bf16 to win (on hosts emulating bf16, declining is correct).
    Bytes-moved estimates accompany wall time so the record shows bandwidth
    utilization, not just speedup.
    """
    records = []
    eng = engine.get_engine()

    def _err(got, ref):
        got = np.asarray(got, np.float64)
        ref = np.asarray(ref, np.float64)
        return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))

    def _time_pair(ff, fb, rounds=3):
        # the guard consumes the f32/bf16 RATIO, so the two sides must be
        # timed interleaved: back-to-back rounds with a per-side min discard
        # slow host drift (throttling late in a CI run) that would skew a
        # one-shot sequential measurement by 30%+
        tfs, tbs = [], []
        for _ in range(rounds):
            tfs.append(time_fn(ff))
            tbs.append(time_fn(fb))
        return min(tfs), min(tbs)

    # ---- pairwise ---------------------------------------------------------
    for L, B in [(2, 1024), (4, 256), (6, 64)]:
        x1 = _rand((B, num_coeffs(L)), 0).astype(jnp.bfloat16)
        x2 = _rand((B, num_coeffs(L)), 1).astype(jnp.bfloat16)
        x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
        kw = dict(batch_hint=B, requires_grad=False, tune="measure")
        pf = eng.plan(L, L, L, dtype="float32", **kw)
        pb = eng.plan(L, L, L, dtype="bfloat16", **kw)
        pa = eng.plan(L, L, L, dtype="auto", **kw)
        jf = jax.jit(lambda a, b: pf.apply(a, b))
        jb = jax.jit(lambda a, b: pb.apply(a, b))
        tf, tb = _time_pair(lambda: jf(x1f, x2f), lambda: jb(x1, x2))
        err = _err(pb.apply(x1, x2), pf.apply(x1f, x2f))
        nb = _bytes_moved((L, L), L, B, "bfloat16")
        record(records, f"engine_mixed_precision_pairwise_L{L}_B{B}", tb,
               echo=csv, f32_us=round(tf, 1),
               speedup_vs_f32=round(tf / tb, 2), err=round(err, 4),
               auto_dtype=pa.key.dtype, backend=pb.backend,
               f32_backend=pf.backend,
               bytes_moved=nb, bytes_moved_f32=_bytes_moved((L, L), L, B),
               gbps=round(nb / tb / 1e3, 2))

    # ---- chains (fused_xla + fused_pallas interpret are exercised by the
    # measured pool; the record keeps whatever each precision's winner was) -
    for Ls, Lout, B in [((2, 2, 2), 2, 256), ((3, 3), 3, 128)]:
        xs = [_rand((B, num_coeffs(L)), 10 + i).astype(jnp.bfloat16)
              for i, L in enumerate(Ls)]
        xsf = [x.astype(jnp.float32) for x in xs]
        kw = dict(tune="measure", batch_hint=B)
        cf = eng.plan_chain(Ls, Lout, dtype="float32", **kw)
        cb = eng.plan_chain(Ls, Lout, dtype="bfloat16", **kw)
        ca = eng.plan_chain(Ls, Lout, dtype="auto", **kw)
        tf, tb = _time_pair(lambda: cf.apply_jit(xsf), lambda: cb.apply_jit(xs))
        err = _err(cb.apply_jit(xs), cf.apply_jit(xsf))
        nb = _bytes_moved(Ls, Lout, B, "bfloat16")
        name = f"engine_mixed_precision_chain_L{Ls[0]}x{len(Ls)}_B{B}"
        record(records, name, tb, echo=csv, f32_us=round(tf, 1),
               speedup_vs_f32=round(tf / tb, 2), err=round(err, 4),
               auto_dtype=ca.dtype, backend=cb.backend,
               f32_backend=cf.backend,
               bytes_moved=nb, bytes_moved_f32=_bytes_moved(Ls, Lout, B),
               gbps=round(nb / tb / 1e3, 2))
    return records


def _cold_warm_pass(cache_path: str) -> dict:
    """One boot of a fresh engine against ``cache_path``: the measure-mode
    workload whose picks the persisted table must reproduce."""
    t0 = time.perf_counter()
    eng = engine.GauntEngine(cache_path=cache_path)
    eng.load_autotune_cache()
    picks = {}
    p = eng.plan(2, 2, 2, batch_hint=256, tune="measure", requires_grad=False)
    picks["pairwise"] = p.backend
    c = eng.plan_chain((2, 2, 2), 2, tune="measure", batch_hint=512)
    picks["chain"] = c.backend
    a = eng.plan(2, 2, 2, batch_hint=256, dtype="auto", tune="measure",
                 requires_grad=False)
    picks["auto_dtype"] = a.key.dtype
    eng.flush_autotune_cache()
    us = (time.perf_counter() - t0) * 1e6
    return {"us": us, "timing_runs": eng.timing_runs, "picks": picks}


def run_autotune_cache(csv=True):
    """Cold-vs-warm autotune startup (DESIGN.md §4.5).

    Two fresh engines, one after the other in this process, run the same
    measure-mode workload against one shared cache file: the first boots
    cold, measures, and flushes; the second must answer every selection from
    the file.  Both run in one process because a process that holds the
    accelerator cannot hand it to a child; the warm engine therefore reuses
    the cold one's compiled programs, so ``us`` understates a cold process's
    start-up, and the contract is the counters.  The record carries both
    latencies, both timing-run counters, and whether the warm engine picked
    identically — the CI guard holds warm timing runs to ZERO and picks to
    equality, the persisted-cache correctness contract.
    """
    import os
    import tempfile

    records = []
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "autotune.json")
        cold = _cold_warm_pass(path)
        warm = _cold_warm_pass(path)
    record(records, "engine_autotune_cache_warm_start", warm["us"], echo=csv,
           cold_us=round(cold["us"], 1),
           speedup_vs_cold=round(cold["us"] / warm["us"], 2),
           cold_timing_runs=cold["timing_runs"],
           warm_timing_runs=warm["timing_runs"],
           picks_match=cold["picks"] == warm["picks"],
           backend=warm["picks"]["chain"])
    return records


if __name__ == "__main__":
    run()
    run_chain()
    run_chain_kernel()
    run_grid_gate()
    run_mixed_precision()
    run_autotune_cache()
