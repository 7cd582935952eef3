"""Persistent per-host autotune cache (core/autotune_cache.py, DESIGN.md
§4.5): roundtrip + zero-timing warm start, fingerprint/corruption fallback,
stale-entry invalidation, merge-on-save, and the measured-selection failure
paths the persistence layer depends on (never cache a selection that was
never successfully run)."""
import json
import os
import subprocess
import sys

import pytest

from repro.core import autotune_cache as ac
from repro.core import engine


def _measure_some(eng):
    """One plan key + one chain key + one auto family, all measured."""
    p = eng.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    cp = eng.plan_chain((1, 1), 1, tune="measure", batch_hint=32)
    pa = eng.plan(1, 1, 2, dtype="auto", batch_hint=32, tune="measure",
                  requires_grad=False)
    return p, cp, pa


# ---------------------------------------------------------------------------
# roundtrip + warm start
# ---------------------------------------------------------------------------


def test_roundtrip_warm_engine_zero_timing_runs(tmp_path):
    """A second engine pointed at the flushed cache answers every selection
    from the file: zero timing runs, identical picks."""
    path = str(tmp_path / "cache.json")
    cold = engine.GauntEngine(cache_path=path)
    p, cp, pa = _measure_some(cold)
    assert cold.timing_runs > 0
    # every measurement autoflushed; the file is already complete
    assert os.path.exists(path)

    warm = engine.GauntEngine(cache_path=path)
    p2, cp2, pa2 = _measure_some(warm)
    assert warm.timing_runs == 0
    assert (p2.backend, cp2.backend, pa2.key.dtype) == \
        (p.backend, cp.backend, pa.key.dtype)
    assert warm._measured == cold._measured


def test_load_is_lazy_and_in_process_wins(tmp_path):
    """The cache loads on the first measure-mode miss (not at construction),
    and an in-process measurement is never overwritten by the file's."""
    path = str(tmp_path / "cache.json")
    cold = engine.GauntEngine(cache_path=path)
    _measure_some(cold)

    warm = engine.GauntEngine(cache_path=path)
    assert not warm._cache_loaded and warm._measured == {}
    # pre-seed one in-process entry with a DIFFERENT (but real, eligible)
    # backend than the file's, then trigger the lazy load via a miss
    key = engine.PlanKey(1, 1, 2, kind="pairwise", batch_hint=32)
    assert key in cold._measured
    local_pick = "fft" if cold._measured[key] != "fft" else "direct"
    warm._measured[key] = local_pick
    p = warm.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    assert warm._cache_loaded
    assert warm._measured[key] == local_pick  # file did not overwrite it
    assert p.backend == local_pick


def test_file_with_calibration_member_still_loads(tmp_path):
    """Older revisions also wrote a ``calibration`` member; a file carrying
    one loads with the member ignored and warms the engine all the same."""
    path = str(tmp_path / "cache.json")
    cold = engine.GauntEngine(cache_path=path)
    _measure_some(cold)
    raw = json.load(open(path))
    raw["calibration"] = {"fused_skinny": 9.5, "fused_skinny_measured": True}
    json.dump(raw, open(path, "w"))

    sel, tim = ac.load(path)
    assert sel == cold._measured and tim == cold._measured_t
    warm = engine.GauntEngine(cache_path=path)
    _measure_some(warm)
    assert warm.timing_runs == 0


def test_cli_round_trip_verify_warm(tmp_path, monkeypatch, capsys):
    """The operator's warm-up: a first ``main`` measures and persists the
    sweep, a second with ``--verify-warm`` answers it from the file with
    zero timing runs.  The sweep is stubbed to one key (the real ``--fast``
    grid takes most of a minute on a CPU)."""
    def one_key(eng, fast, serve_rows=(1024,)):
        n0 = len(eng._measured)
        eng.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
        return len(eng._measured) - n0

    monkeypatch.setattr(ac, "_sweep", one_key)
    path = str(tmp_path / "cli_cache.json")
    eng = engine.get_engine()
    try:
        eng.clear()
        assert ac.main(["--fast", "--cache", path]) == 0
        assert eng.timing_runs > 0 and ac.load(path)[0]
        eng.clear()   # a fresh process: nothing measured in memory
        assert ac.main(["--fast", "--cache", path, "--verify-warm"]) == 0
        assert eng.timing_runs == 0
        assert "verify-warm OK: zero timing runs" in capsys.readouterr().out
    finally:
        eng.set_autotune_cache(None)
        eng.clear()


# ---------------------------------------------------------------------------
# fallback paths: the cache must never break planning
# ---------------------------------------------------------------------------


def test_fingerprint_mismatch_falls_back_to_measurement(tmp_path):
    path = str(tmp_path / "cache.json")
    cold = engine.GauntEngine(cache_path=path)
    _measure_some(cold)
    raw = json.load(open(path))
    raw["fingerprint"]["jax_version"] = "0.0.0-other-host"
    json.dump(raw, open(path, "w"))

    assert ac.load(path) is None
    warm = engine.GauntEngine(cache_path=path)
    assert warm.load_autotune_cache() == 0
    p = warm.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    assert warm.timing_runs > 0  # fell back to real measurement
    assert p.backend in engine.available_backends("pairwise",
                                                  requires_grad=False)


@pytest.mark.parametrize("content", ["{truncated", "", "[1, 2, 3]", "null"])
def test_corrupt_cache_falls_back_without_error(tmp_path, content):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write(content)
    assert ac.load(path) is None
    eng = engine.GauntEngine(cache_path=path)
    eng.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    assert eng.timing_runs > 0
    # and the broken file is repaired by the autoflush
    assert ac.load(path) is not None


def test_missing_and_disabled_paths_are_noops(tmp_path):
    assert ac.load(str(tmp_path / "nope.json")) is None
    assert ac.load(None) is None
    eng = engine.GauntEngine()  # no path, no env: persistence off
    assert eng.load_autotune_cache() == 0
    assert eng.flush_autotune_cache() is None


def test_stale_entries_dropped_individually(tmp_path):
    """Entries naming unregistered backends / unknown kinds / non-storage
    dtype winners are dropped on load; valid neighbors survive."""
    path = str(tmp_path / "cache.json")
    cold = engine.GauntEngine(cache_path=path)
    _measure_some(cold)
    n_valid = len(cold._measured)
    raw = json.load(open(path))

    def fake(kind="pairwise", dtype="float32", backend="dense_einsum"):
        return {"key": {"L1": 1, "L2": 1, "Lout": 2, "kind": kind,
                        "batch_hint": 8, "dtype": dtype, "extra": []},
                "backend": backend, "t": 1.0}

    raw["selections"] += [
        fake(backend="warp_drive"),              # unregistered backend
        fake(kind="chain", backend="packed"),    # not a chain flavor
        fake(kind="sixbody"),                    # unknown kind
        fake(dtype="float16"),                   # unknown storage dtype
        fake(dtype="auto", backend="float16"),   # auto winner not a storage dtype
        {"backend": "fft", "t": 1.0},            # missing key entirely
    ]
    json.dump(raw, open(path, "w"))
    loaded = ac.load(path)
    assert loaded is not None
    assert len(loaded[0]) == n_valid  # every injected stale entry dropped


def test_save_merges_concurrent_same_fingerprint_entries(tmp_path):
    """Two processes flushing different keys to one file converge: save()
    folds in what the other wrote (local wins on collision)."""
    path = str(tmp_path / "cache.json")
    ka = engine.PlanKey(1, 1, 2, kind="pairwise", batch_hint=8)
    kb = engine.PlanKey(2, 2, 4, kind="pairwise", batch_hint=8)
    ac.save(path, {ka: "fft"}, {ka: 1.0})
    ac.save(path, {kb: "direct"}, {kb: 2.0})  # a "concurrent" process
    sel, tim = ac.load(path)
    assert sel == {ka: "fft", kb: "direct"}
    assert tim == {ka: 1.0, kb: 2.0}
    # collision: the flushing process's own entry wins
    ac.save(path, {ka: "dense_einsum"}, {ka: 0.5})
    sel, tim = ac.load(path)
    assert sel[ka] == "dense_einsum" and tim[ka] == 0.5


def test_unwritable_cache_degrades_to_in_process(tmp_path, monkeypatch):
    eng = engine.GauntEngine(cache_path=str(tmp_path / "cache.json"))

    def boom(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(ac, "save", boom)
    p = eng.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    assert p.backend and len(eng._measured) >= 1  # planned + cached in-process
    with pytest.raises(OSError):
        eng.flush_autotune_cache()  # only the explicit flush surfaces it


def test_env_var_activates_persistence(tmp_path, monkeypatch):
    path = str(tmp_path / "env_cache.json")
    monkeypatch.setenv(ac.ENV_VAR, path)
    eng = engine.GauntEngine()  # no explicit path
    eng.plan(1, 1, 2, batch_hint=32, tune="measure", requires_grad=False)
    assert os.path.exists(path)
    assert ac.load(path) is not None


# ---------------------------------------------------------------------------
# measured-selection failure paths (the bugs that would poison a persisted
# cache): never cache — in-process or on disk — a selection that never ran
# ---------------------------------------------------------------------------


def test_select_chain_all_candidates_failed_is_not_cached(monkeypatch):
    """Satellite: when every chain candidate raises during timing, the safe
    'tree' default is returned but NOT pinned — a later healthy call
    re-measures and caches a real winner."""
    eng = engine.GauntEngine()

    def boom(self, xs, weights=None, w_out=None, out_basis="sh"):
        raise RuntimeError("synthetic all-candidate failure")

    monkeypatch.setattr(engine.ChainPlan, "apply_jit", boom)
    assert eng._select_chain((1, 1), 1, "float32", 32, sharded=False) == "tree"
    assert eng._measured == {} and eng._measured_t == {}

    monkeypatch.undo()
    eng._chains.clear()  # drop plans built during the failed pass
    name = eng._select_chain((1, 1), 1, "float32", 32, sharded=False)
    assert name in engine.CHAIN_BACKENDS
    key = engine.GauntEngine._chain_measure_key((1, 1), 1, "float32", 32,
                                                None, "sh", None)
    assert eng._measured[key] == name
    assert eng._measured_t[key] < float("inf")


def test_measure_fallback_is_not_cached(monkeypatch):
    """Satellite: when _measure falls back to the cost model (every backend
    failed), select() must not pin the never-run pick."""
    eng = engine.GauntEngine()
    key = engine.PlanKey(1, 1, 2, kind="pairwise", batch_hint=16)
    eligible = [b for b in engine._REGISTRY.values()
                if b.eligible(key, False)]

    monkeypatch.setattr(engine.GauntEngine, "_measure",
                        lambda self, k, e: ("dense_einsum", None))
    name = eng.select(key, tune="measure", requires_grad=False)
    assert name == "dense_einsum"
    assert eng._measured == {} and eng._measured_t == {}

    monkeypatch.undo()
    name2 = eng.select(key, tune="measure", requires_grad=False)
    assert name2 in [b.name for b in eligible]
    assert key in eng._measured and key in eng._measured_t


def test_auto_dtype_not_cached_without_timings(monkeypatch):
    """Satellite: a measurement pass that produced no timings must not pin
    'float32' under the auto key for the process lifetime (or the file)."""
    eng = engine.GauntEngine()
    monkeypatch.setattr(engine.GauntEngine, "_measure",
                        lambda self, k, e: ("dense_einsum", None))
    p = eng.plan(1, 1, 2, dtype="auto", batch_hint=16, tune="measure",
                 requires_grad=False)
    assert p.key.dtype == "float32"  # safe resolution...
    auto_key = engine.PlanKey(1, 1, 2, kind="pairwise", batch_hint=16,
                              dtype="auto")
    assert auto_key not in eng._measured  # ...but never a cached decision

    # chain flavor of the same rule
    def boom(self, xs, weights=None, w_out=None, out_basis="sh"):
        raise RuntimeError("synthetic failure")

    monkeypatch.setattr(engine.ChainPlan, "apply_jit", boom)
    assert eng._select_chain_dtype((1, 1), 1, 16, sharded=False,
                                   entry_hint=None, out_hint="sh",
                                   share_hint=None, tune="measure") == "float32"
    chain_auto = engine.GauntEngine._chain_measure_key(
        (1, 1), 1, "auto", 16, None, "sh", None)
    assert chain_auto not in eng._measured

    # the healthy path still caches the winner
    monkeypatch.undo()
    eng.clear()
    eng._select_chain_dtype((1, 1), 1, 16, sharded=False, entry_hint=None,
                            out_hint="sh", share_hint=None, tune="measure")
    assert eng._measured[chain_auto] in ("float32", "bfloat16")


# ---------------------------------------------------------------------------
# the acceptance proof: counter-proven warm serve start across processes
# ---------------------------------------------------------------------------

_SERVE_CHILD = r"""
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
eng = EquivariantServeEngine(model, params, n_slots=1, max_atoms=4,
                             warmup=True)
rng = np.random.default_rng(0)
req = EquivariantRequest(species=rng.integers(0, 4, 3),
                         pos=(rng.normal(size=(3, 3)) * 1.5).astype(np.float32))
out = eng.run([req])[0]
assert out.done
g = ce.get_engine()
g.flush_autotune_cache()
print("RUNS=" + str(g.timing_runs))
print("PICKS=" + json.dumps(sorted((repr(k), v)
                                   for k, v in g._measured.items())))
print("SERVE_OK")
"""


def _subprocess_env() -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_warm_serve_process_performs_zero_timing_runs(tmp_path):
    """ISSUE acceptance: a second process pointed at the populated cache
    file performs ZERO timing runs through serve warmup() + the first step,
    while selecting identically to the cold process."""
    env = _subprocess_env()
    env["CACHE_PATH"] = str(tmp_path / "serve_cache.json")
    out = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _SERVE_CHILD],
                           capture_output=True, text=True, env=env,
                           timeout=900)
        assert "SERVE_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
        vals = dict(ln.split("=", 1) for ln in r.stdout.splitlines()
                    if "=" in ln)
        out.append((int(vals["RUNS"]), vals["PICKS"]))
    (cold_runs, cold_picks), (warm_runs, warm_picks) = out
    assert cold_runs > 0, "cold process should have measured something"
    assert warm_runs == 0, \
        f"warm process ran {warm_runs} timing passes (cache not consulted)"
    assert warm_picks == cold_picks, "warm selections diverged from cold"
