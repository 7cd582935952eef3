"""Serve scale-out integration (DESIGN.md §10): bucketed pools serve mixed
workloads with direct-evaluation numerics, async pipelining stages overlap
work while steps are in flight, deadlines hold under the real engine, and —
the ISSUE acceptance proof — per-bucket warmup performs ZERO timing runs on
a warm autotune cache (subprocess-counter-proven)."""
import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gaunt_ff import gaunt_mace_ff
from repro.models.equivariant import MaceGaunt
from repro.serve.engine import EquivariantRequest, EquivariantServeEngine
from repro.serve.scheduler import REASON_DEADLINE, Scheduler


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(gaunt_mace_ff, channels=8, n_layers=1, L=1,
                              L_edge=1, n_species=4)
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _mol(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 4, n),
            (rng.normal(size=(n, 3)) * 1.5).astype(np.float32))


def _direct_energy(model, params, r):
    return float(model.energy(params, jnp.asarray(r.species),
                              jnp.asarray(np.asarray(r.pos, np.float32))))


def test_bucketed_mixed_workload_matches_direct(small_model):
    """A mixed small/large workload routed across two buckets completes
    with per-request energies equal to unpadded direct evaluation — bucket
    padding is inert in every bucket, not just the largest."""
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 2), (10, 2)])
    sizes = [2, 3, 4, 5, 7, 10, 3, 8]
    reqs = [EquivariantRequest(*_mol(n, seed=i), rid=i)
            for i, n in enumerate(sizes)]
    out = eng.run(reqs)
    assert all(r.done and not r.rejected for r in out)
    for r in out:
        e = _direct_energy(model, params, r)
        assert abs(r.energy - e) < 1e-4 * max(1.0, abs(e)), r.rid
    # both buckets actually served
    assert all(p.steps_run > 0 for p in eng.pools)
    s = eng.metrics.summary()
    assert s["completed"] == len(reqs)
    assert 0.0 < s["padding_efficiency"] <= 1.0
    assert s["latency_p50_ms"] <= s["latency_p99_ms"]


def test_bucketed_equals_single_bucket_results(small_model):
    """The bucket ladder changes padding and scheduling, never numbers:
    identical request streams through a bucketed and a single-max_atoms
    engine produce identical energies/forces (same ghost-atom contract)."""
    model, params = small_model

    def serve(buckets):
        reqs = [EquivariantRequest(*_mol(n, seed=i), rid=i)
                for i, n in enumerate([2, 5, 9, 3, 7])]
        EquivariantServeEngine(model, params, n_slots=2, max_atoms=9,
                               buckets=buckets).run(reqs)
        return reqs

    single = serve(None)
    bucketed = serve([(3, 2), (6, 2), (9, 2)])
    for a, b in zip(single, bucketed):
        np.testing.assert_allclose(a.energy, b.energy, rtol=1e-5)
        np.testing.assert_allclose(a.forces, b.forces, rtol=1e-4, atol=1e-6)


def test_relaxation_across_buckets(small_model):
    """Multi-step relaxation holds inside a bucket (staged tensors are
    re-uploaded after each relaxation write, not stale-reused)."""
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 1), (8, 1)])
    sp, pos0 = _mol(4, 7)
    s = 1e5
    req = EquivariantRequest(species=sp, pos=pos0.copy(), steps=2,
                             step_size=s)
    out = eng.run([req])[0]
    assert out.done
    e0, f0 = model.energy_forces(params, jnp.asarray(sp), jnp.asarray(pos0))
    pos1 = pos0 + s * np.asarray(f0)
    e1, f1 = model.energy_forces(params, jnp.asarray(sp), jnp.asarray(pos1))
    np.testing.assert_allclose(out.pos, pos1, rtol=1e-5, atol=1e-6)
    assert abs(out.energy - float(e1)) < 1e-4 * max(1.0, abs(float(e1)))


def test_repeated_eval_staged_reuse_is_not_stale(small_model):
    """steps>1 with step_size=0 re-evaluates the SAME geometry: the staging
    cache may reuse the uploaded tensors, but every step must produce the
    direct-evaluation energy (reuse is an upload economy, not a result
    cache)."""
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(6, 1)])
    sp, pos = _mol(4, 13)
    req = EquivariantRequest(species=sp, pos=pos.copy(), steps=3,
                             step_size=0.0)
    out = eng.run([req])[0]
    assert out.done
    e = _direct_energy(model, params, out)
    assert abs(out.energy - e) < 1e-4 * max(1.0, abs(e))
    assert eng.pools.pools[0].steps_run == 3


def test_overlap_admission_stages_early(small_model):
    """Async pipelining: a request arriving while another bucket's step is
    in flight is admitted AND device-staged inside the overlap window
    (metrics count the early staging), and completes with correct
    numerics."""
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 1), (8, 1)])
    sched = Scheduler(eng)
    big = EquivariantRequest(*_mol(8, seed=1), steps=2, rid=0)
    small = EquivariantRequest(*_mol(3, seed=2), rid=1)
    sched.submit(big)
    calls = {"n": 0}

    def poll():
        # fires once per overlap pass; inject the small arrival only inside
        # a step's overlap window (the scheduler has already admitted `big`)
        calls["n"] += 1
        if calls["n"] == 2:
            sched.submit(small)

    while sched.pump(poll=poll):
        pass
    assert big.done and small.done
    assert eng.metrics.counters["staged_early"] >= 1
    e = _direct_energy(model, params, small)
    assert abs(small.energy - e) < 1e-4 * max(1.0, abs(e))


def test_open_loop_smoke_completes_every_request(small_model):
    """A mixed-size stream of 12 requests arriving at 15 req/s on the wall
    clock, admitted mid-flight across a three-bucket ladder: every request
    is answered, none is rejected, and a warm engine times nothing while
    serving."""
    from repro.core.engine import get_engine

    model, params = small_model
    eng = EquivariantServeEngine(model, params,
                                 buckets=[(6, 2), (12, 2), (24, 2)])
    eng.warmup()
    rng = np.random.default_rng(0)
    sizes = [2, 5, 9, 3, 14, 6, 11, 4, 20, 7, 2, 12]
    reqs = [EquivariantRequest(species=rng.integers(0, 4, n),
                               pos=(rng.normal(size=(n, 3)) * 1.5)
                               .astype(np.float32), rid=i)
            for i, n in enumerate(sizes)]
    sched = Scheduler(eng)
    due = [i / 15.0 for i in range(len(reqs))]
    runs0 = get_engine().timing_runs
    t0 = time.monotonic()
    i = 0

    def feed():
        nonlocal i
        while i < len(reqs) and due[i] <= time.monotonic() - t0:
            sched.submit(reqs[i])
            i += 1

    while True:
        feed()
        if not sched.pump(poll=feed) and i >= len(reqs):
            break
        if not eng.has_active() and not len(sched.queue) and i < len(reqs):
            time.sleep(0.002)
    m = eng.metrics.summary()
    assert m["completed"] == 12 and m["rejected"] == 0, m
    assert all(r.done and not r.rejected for r in reqs)
    assert get_engine().timing_runs == runs0


def test_deadline_holds_in_real_engine(small_model):
    """A request whose deadline lapsed while queued is rejected with the
    structured reason and never evaluated; co-queued live requests serve
    normally."""
    clock_t = {"t": 0.0}
    clock = lambda: clock_t["t"]  # noqa: E731
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(6, 1)],
                                 clock=clock)
    sched = Scheduler(eng, clock=clock)
    live = EquivariantRequest(*_mol(3, seed=3), rid=0)
    stale = EquivariantRequest(*_mol(3, seed=4), rid=1, deadline=0.5)
    sched.submit(live)
    sched.submit(stale)
    clock_t["t"] = 1.0               # stale expires while queued
    sched.drain()
    assert live.done and not live.rejected and live.energy is not None
    assert stale.rejected and stale.energy is None
    assert stale.reject_reason.startswith(REASON_DEADLINE)


def test_cfg_serve_buckets_knob(small_model):
    """EquivariantConfig.serve_buckets configures the ladder when the
    engine gets no explicit buckets argument (and the explicit argument
    wins over the config)."""
    model, params = small_model
    cfg = dataclasses.replace(model.cfg, serve_buckets=((4, 1), (8, 2)))
    model2 = MaceGaunt(cfg)
    eng = EquivariantServeEngine(model2, params)
    assert [p.spec.max_atoms for p in eng.pools] == [4, 8]
    assert eng.n_slots == 3
    eng2 = EquivariantServeEngine(model2, params, buckets=[(16, 1)])
    assert [p.spec.max_atoms for p in eng2.pools] == [16]


# ---------------------------------------------------------------------------
# the acceptance proof: per-bucket warmup on a warm cache = zero timing runs
# ---------------------------------------------------------------------------

_BUCKETED_CHILD = r"""
import dataclasses, json, os
import numpy as np
import jax
from repro.configs.gaunt_ff import gaunt_mace_ff
from repro.models.equivariant import MaceGaunt
from repro.serve.engine import EquivariantRequest, EquivariantServeEngine
from repro.core import engine as ce

cfg = dataclasses.replace(gaunt_mace_ff, channels=4, n_layers=1, L=1,
                          L_edge=1, n_species=4, chain_tune="measure",
                          autotune_cache=os.environ["CACHE_PATH"])
model = MaceGaunt(cfg)
params = model.init(jax.random.PRNGKey(0))
# two buckets whose quantized chain batch_hints differ (4*4=16 vs 12*4=48
# rows), so per-bucket warmup seeds two DISTINCT measured chain keys
eng = EquivariantServeEngine(model, params, buckets=[(4, 1), (12, 1)],
                             warmup=True)
rng = np.random.default_rng(0)
reqs = [EquivariantRequest(species=rng.integers(0, 4, n),
                           pos=(rng.normal(size=(n, 3)) * 1.5)
                           .astype(np.float32), rid=i)
        for i, n in enumerate([3, 10])]          # one per bucket
out = eng.run(reqs)
assert all(r.done and not r.rejected for r in out)
assert all(p.steps_run > 0 for p in eng.pools)
g = ce.get_engine()
g.flush_autotune_cache()
print("RUNS=" + str(g.timing_runs))
print("PICKS=" + json.dumps(sorted((repr(k), repr(v))
                                   for k, v in g._measured.items())))
print("NKEYS=" + str(len(g._measured)))
print("SERVE_OK")
"""


def _subprocess_env() -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_per_bucket_warmup_zero_timing_runs_on_warm_cache(tmp_path):
    """ISSUE acceptance: a second process pointed at the populated autotune
    cache performs ZERO timing runs through the BUCKETED warmup (every
    bucket's chain keys answered from disk) + both buckets' first steps,
    selecting identically to the cold process."""
    env = _subprocess_env()
    env["CACHE_PATH"] = str(tmp_path / "bucketed_cache.json")
    out = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _BUCKETED_CHILD],
                           capture_output=True, text=True, env=env,
                           timeout=900)
        assert "SERVE_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
        vals = dict(ln.split("=", 1) for ln in r.stdout.splitlines()
                    if "=" in ln)
        out.append((int(vals["RUNS"]), vals["PICKS"], int(vals["NKEYS"])))
    (cold_runs, cold_picks, cold_n), (warm_runs, warm_picks, _) = out
    assert cold_runs > 0, "cold process should have measured something"
    assert cold_n >= 2, "per-bucket warmup should seed multiple keys"
    assert warm_runs == 0, \
        f"warm process ran {warm_runs} timing passes (cache not consulted)"
    assert warm_picks == cold_picks, "warm selections diverged from cold"
