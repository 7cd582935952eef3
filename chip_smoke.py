#!/usr/bin/env python3
"""Smoke run of the Gaunt-MACE force field on a TPU, through its user entry
points, at the full width of the repo's ``gaunt_mace_ff`` preset (64
channels, L=2, L_edge=3, 2 layers, nu=3, 8 species; random weights from
``--seed``).

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the row-sharded serving path, 4 chips

One chip runs, in one process:

1. serve: `EquivariantServeEngine` with one 64-atom bucket of 4 slots,
   ``warmup()``, then 12 seeded requests of 16-64 atoms through the
   scheduler (two are 5-step relaxations, one is a rotated copy of another);
2. serve-measured: the same with ``chain_tune='measure'``, so the engine
   times the chain candidates (``fused_pallas``, the Pallas collocation
   kernel, among them) and serves with the winner;
3. reference: every served energy and force against the model's own
   ``energy_forces`` on the host CPU under highest matmul precision, and
   the rotated copy against the original;
4. train: 3 ``train_loop`` steps of the energy + force-matched loss on 4
   seeded 32-atom Lennard-Jones structures;
5. kernel: the Pallas chain kernel against its plain-XLA twin at the
   selfmix key, forward and the second-order gradient training takes.

``--chips 4`` runs only the sharded serving forward/forces (``shard_data``
over a 4-device data mesh) and the one-device result it is compared with.

Exits non-zero, without the ``"ok": true`` line, when JAX finds no TPU,
when any request is rejected or any recovery counter moved, when an
autotune candidate raised, when a Pallas kernel ran in interpret mode, or
when a result is outside its tolerance.  The last line of standard output
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

BUCKET_ATOMS = 64
N_SLOTS = 4
# (atoms, relaxation steps) per request; request 1 is request 0 rotated.
# Four atom counts keep the reference to four CPU compiles.
REQUESTS = ((16, 1), (16, 1), (32, 1), (32, 1), (48, 1), (48, 1),
            (64, 1), (64, 1), (16, 1), (32, 1), (48, 5), (64, 5))
RELAX_STEP = 1e-3
TRAIN_ATOMS, TRAIN_BATCH, TRAIN_STEPS = 32, 4, 3
# Served results run at the chip's default f32 matmul precision, which
# rounds matmul inputs to bfloat16 (unit roundoff 2^-9, about 2e-3); two
# layers and a cubic many-body product grow that to about 1e-2 of the
# result's scale.  Errors are judged against that scale: max |E| over the
# requests for energies, max |F| for forces.
TOL = 3e-2
# the rotated copy is served next to its original, so only rounding differs
ROT_TOL = 3e-2
# Pallas kernel vs its plain-XLA twin at the same precision
KERNEL_TOL = 1e-2
# the sharded step's per-device scratch must be well under the unsharded
# step's: rows split over 4 devices leave about a quarter of it
SHARD_TEMP_RATIO = 0.5
RECOVERY_COUNTERS = ("rejected", "step_failures", "retries", "quarantined",
                     "nonfinite_bisects", "failovers", "warmup_retries",
                     "autotune_cache_load_failed")


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _scale_err(got, ref) -> tuple[float, float]:
    """(max abs error, max abs reference value) over paired arrays."""
    err = max(float(np.max(np.abs(np.asarray(g) - np.asarray(r))))
              for g, r in zip(got, ref))
    scale = max(float(np.max(np.abs(np.asarray(r)))) for r in ref)
    return err, scale


def make_requests(seed: int, n_species: int):
    from repro.core.so3 import rotation_matrix_zyz
    from repro.data import lj_dataset
    from repro.serve import EquivariantRequest

    reqs = []
    for i, (n, steps) in enumerate(REQUESTS):
        d = lj_dataset(1, n_atoms=n, n_species=n_species, seed=seed + i)
        sp, pos = d["species"][0], d["pos"][0]
        if i == 1:
            R = rotation_matrix_zyz(0.5, 1.0, -0.3).astype(np.float32)
            sp, pos = reqs[0].species, reqs[0].pos @ R.T
        reqs.append(EquivariantRequest(
            species=np.asarray(sp, np.int32), pos=np.asarray(pos, np.float32),
            steps=steps, step_size=RELAX_STEP if steps > 1 else 0.0, rid=i))
    return reqs


def serve(model, params, reqs, label: str) -> dict:
    """Warm up and drain ``reqs``; fail on any rejection, non-finite result
    or recovery event."""
    import jax.numpy as jnp

    from repro.core import engine as _engine
    from repro.kernels.gaunt_fused import kernel_stats, reset_kernel_stats
    from repro.serve import EquivariantServeEngine

    reset_kernel_stats()
    eng = EquivariantServeEngine(model, params,
                                 buckets=((BUCKET_ATOMS, N_SLOTS),))
    t0 = time.perf_counter()
    eng.warmup()
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = eng.run(reqs)
    t_run = time.perf_counter() - t0
    summ = eng.metrics.summary()
    counters = {k: int(eng.metrics.counters[k]) for k in RECOVERY_COUNTERS}
    stats = kernel_stats()
    log(f"[{label}] warmup {t_warm:.1f}s, run {t_run:.2f}s, "
        f"completed {summ['completed']}/{len(reqs)}, steps {summ['steps']}, "
        f"step p50 {summ['step_p50_ms']:.1f}ms p99 {summ['step_p99_ms']:.1f}ms")
    log(f"[{label}] recovery counters {counters}")
    log(f"[{label}] kernel_stats {stats}")
    check(len(done) == len(reqs), f"{label}: {len(done)} of {len(reqs)} back")
    for r in done:
        check(r.done and not r.rejected,
              f"{label}: request {r.rid} rejected ({r.reject_reason})")
        check(r.energy is not None and np.isfinite(r.energy)
              and r.forces is not None and np.all(np.isfinite(r.forces)),
              f"{label}: request {r.rid} has non-finite results")
    check(summ["completed"] == len(reqs),
          f"{label}: completed {summ['completed']} of {len(reqs)}")
    check(not any(counters.values()),
          f"{label}: recovery events {counters}")
    check(stats["interpret_calls"] == 0,
          f"{label}: {stats['interpret_calls']} kernel calls in interpret mode")
    fails = _engine.get_engine().autotune_failures
    check(not fails, f"{label}: autotune candidates raised: {fails}")
    pool = eng.pools.pools[0]
    hlo = pool._step_fn.lower(params, jnp.asarray(pool.species),
                              jnp.asarray(pool.pos),
                              jnp.asarray(pool.mask)).as_text()
    return {"reqs": sorted(done, key=lambda r: r.rid), "stats": stats,
            "step_has_kernel": "tpu_custom_call" in hlo,
            "warmup_s": t_warm, "run_s": t_run}


def selfmix_pick(cfg, rows: int) -> str:
    """The chain backend the measured autotuner holds for the model's
    many-body selfmix key at ``rows`` rows (a cache hit once seeded)."""
    from repro.core import engine as _engine

    return _engine.plan_chain((cfg.L,) * cfg.nu, cfg.L, tune="measure",
                              batch_hint=rows, share_hint=(0,) * cfg.nu,
                              dtype=cfg.compute_dtype).backend


def reference(model, params, req_lists, device) -> list:
    """The model's own ``energy_forces`` per request geometry, on ``device``
    under highest matmul precision (one compile per atom count)."""
    import jax

    p = jax.device_put(params, device)
    ef = jax.jit(model.energy_forces)
    out = []
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        for reqs in req_lists:
            out.append([])
            for r in reqs:
                e, f = ef(p, jax.device_put(r.species, device),
                          jax.device_put(np.asarray(r.pos, np.float32),
                                         device))
                out[-1].append((float(e), np.asarray(f)))
    return out


def compare(label: str, reqs, ref) -> dict:
    e_err, e_scale = _scale_err([np.float32(r.energy) for r in reqs],
                                [np.float32(e) for e, _ in ref])
    f_err, f_scale = _scale_err([r.forces for r in reqs], [f for _, f in ref])
    log(f"[{label}] vs reference: max|dE| {e_err:.3e} (scale {e_scale:.3e}), "
        f"max|dF| {f_err:.3e} (scale {f_scale:.3e}), tol {TOL} of scale")
    check(e_err <= TOL * e_scale, f"{label}: energy error {e_err} > "
          f"{TOL} x {e_scale}")
    check(f_err <= TOL * f_scale, f"{label}: force error {f_err} > "
          f"{TOL} x {f_scale}")
    return {"e_err": e_err, "f_err": f_err, "e_scale": e_scale,
            "f_scale": f_scale}


def check_rotation(label: str, reqs) -> dict:
    from repro.core.so3 import rotation_matrix_zyz

    R = rotation_matrix_zyz(0.5, 1.0, -0.3)
    a, b = reqs[0], reqs[1]
    de = abs(a.energy - b.energy)
    df = float(np.max(np.abs(a.forces @ R.T - b.forces)))
    fs = float(np.max(np.abs(a.forces)))
    log(f"[{label}] rotation: |E - E_rot| {de:.3e} (|E| {abs(a.energy):.3e}),"
        f" max|F R^T - F_rot| {df:.3e} (max|F| {fs:.3e})")
    check(de <= ROT_TOL * max(1.0, abs(a.energy)),
          f"{label}: energy not rotation-invariant ({de})")
    check(df <= ROT_TOL * max(1.0, fs),
          f"{label}: forces not rotation-equivariant ({df})")
    return {"de": de, "df": df}


def train(cfg, params, seed: int) -> dict:
    from repro.config import TrainConfig
    from repro.data import lj_dataset
    from repro.kernels.gaunt_fused import kernel_stats, reset_kernel_stats
    from repro.models.equivariant import MaceGaunt
    from repro.train import train_loop

    model = MaceGaunt(cfg)
    rows = TRAIN_ATOMS * cfg.channels
    # seed the measured selfmix pick at the rows the traced loss presents
    # (measurement cannot run inside the train step's trace)
    pick = selfmix_pick(cfg, rows)
    reset_kernel_stats()
    data = lj_dataset(TRAIN_BATCH, n_atoms=TRAIN_ATOMS,
                      n_species=cfg.n_species, seed=seed + 100)

    def loss_fn(p, batch):
        loss = model.loss(p, batch)
        return loss, {"mse": loss}

    tcfg = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=TRAIN_STEPS,
                       log_every=1, grad_clip=10.0)
    t0 = time.perf_counter()
    # every step sees the same seeded batch
    _, hist = train_loop(loss_fn, params, itertools.repeat(data), tcfg,
                         hooks={"preemption": False})
    dt = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    stats = kernel_stats()
    log(f"[train] selfmix pick at {rows} rows: {pick}; {len(losses)} steps "
        f"in {dt:.1f}s (compile included); losses {losses}; kernel_stats "
        f"{stats}")
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          f"train: losses {losses}")
    check(stats["interpret_calls"] == 0, "train: kernel ran in interpret mode")
    return {"pick": pick, "losses": losses, "s": dt,
            "chain_pallas_calls": stats["chain_pallas_calls"]}


def kernel_check(cfg, seed: int) -> dict:
    """fused_pallas against fused_xla at the selfmix key: forward, and the
    grad of a force-like gradient (what force-matched training takes)."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine as _engine
    from repro.core.irreps import num_coeffs

    Ls, rows = (cfg.L,) * cfg.nu, BUCKET_ATOMS * cfg.channels
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, num_coeffs(cfg.L))), jnp.float32)
    w = jnp.asarray(rng.normal(size=(cfg.nu,)), jnp.float32)

    def second_order(cp):
        def energy(x, w):
            return jnp.sum(cp.apply([x * w[i] for i in range(cfg.nu)]) ** 2)

        def loss(w):
            return jnp.sum(jax.grad(energy)(x, w) ** 2)

        return jax.jit(lambda w: (cp.apply([x] * cfg.nu), jax.grad(loss)(w)))

    out = {}
    for name in ("fused_pallas", "fused_xla"):
        cp = _engine.plan_chain(Ls, cfg.L, backend=name)
        y, g = second_order(cp)(w)
        out[name] = (np.asarray(y), np.asarray(g))
    y_err, y_scale = _scale_err([out["fused_pallas"][0]], [out["fused_xla"][0]])
    g_err, g_scale = _scale_err([out["fused_pallas"][1]], [out["fused_xla"][1]])
    log(f"[kernel] fused_pallas vs fused_xla at {rows} rows: forward "
        f"max err {y_err:.3e} (scale {y_scale:.3e}), second-order grad max "
        f"err {g_err:.3e} (scale {g_scale:.3e}), tol {KERNEL_TOL} of scale")
    check(y_err <= KERNEL_TOL * y_scale, f"kernel: forward error {y_err}")
    check(g_err <= KERNEL_TOL * g_scale, f"kernel: grad error {g_err}")
    return {"y_err": y_err, "g_err": g_err}


def run_one_chip(cfg, seed: int, ref_device) -> dict:
    import jax

    from repro.models.equivariant import MaceGaunt

    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    res = {}
    base = serve(model, params, make_requests(seed, cfg.n_species), "serve")
    cfg_m = dataclasses.replace(cfg, chain_tune="measure")
    meas = serve(MaceGaunt(cfg_m), params, make_requests(seed, cfg.n_species),
                 "serve-measured")
    pick = selfmix_pick(cfg_m, BUCKET_ATOMS * cfg.channels)
    log(f"[serve-measured] selfmix chain pick at "
        f"{BUCKET_ATOMS * cfg.channels} rows: {pick}; served step holds the "
        f"Pallas kernel: {meas['step_has_kernel']}")
    check(meas["step_has_kernel"] == (pick == "fused_pallas"),
          "serve-measured: the compiled step does not match the pick")
    res["serve"] = {"warmup_s": base["warmup_s"], "run_s": base["run_s"]}
    res["serve_measured"] = {"pick": pick, "warmup_s": meas["warmup_s"],
                             "run_s": meas["run_s"],
                             "kernel_stats": meas["stats"]}
    t0 = time.perf_counter()
    ref, ref_m = reference(model, params, [base["reqs"], meas["reqs"]],
                           ref_device)
    log(f"[reference] {len(ref) + len(ref_m)} evaluations on "
        f"{ref_device.platform} in {time.perf_counter() - t0:.1f}s")
    res["err"] = compare("serve", base["reqs"], ref)
    res["err_measured"] = compare("serve-measured", meas["reqs"], ref_m)
    res["rotation"] = check_rotation("serve", base["reqs"])
    res["train"] = train(cfg_m, params, seed)
    res["kernel"] = kernel_check(cfg, seed)
    return res


def run_sharded(cfg, seed: int) -> dict:
    """``shard_data`` serving on a 4-device data mesh against one device:
    same requests, same weights; the rows must really split."""
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import set_activation_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models.equivariant import MaceGaunt
    from repro.serve import EquivariantServeEngine

    params = MaceGaunt(cfg).init(jax.random.PRNGKey(seed))
    out, temp = {}, {}
    for label, sharded in (("one-device", False), ("sharded", True)):
        set_activation_mesh(make_host_mesh(data=4) if sharded else None)
        try:
            m = MaceGaunt(dataclasses.replace(cfg, shard_data=sharded))
            eng = EquivariantServeEngine(m, params,
                                         buckets=((BUCKET_ATOMS, N_SLOTS),))
            t0 = time.perf_counter()
            eng.warmup()
            reqs = [r for r in make_requests(seed, cfg.n_species)
                    if r.steps == 1][:N_SLOTS]
            done = sorted(eng.run(reqs), key=lambda r: r.rid)
            dt = time.perf_counter() - t0
            pool = eng.pools.pools[0]
            mem = pool._step_fn.lower(
                params, jnp.asarray(pool.species), jnp.asarray(pool.pos),
                jnp.asarray(pool.mask)).compile().memory_analysis()
        finally:
            set_activation_mesh(None)
        for r in done:
            check(r.done and not r.rejected and np.isfinite(r.energy),
                  f"{label}: request {r.rid} failed ({r.reject_reason})")
        out[label], temp[label] = done, mem.temp_size_in_bytes
        log(f"[{label}] {len(done)} requests in {dt:.1f}s (compile "
            f"included); step temp bytes per device {mem.temp_size_in_bytes},"
            f" argument bytes {mem.argument_size_in_bytes}")
    e_err, e_scale = _scale_err([np.float32(r.energy) for r in out["sharded"]],
                                [np.float32(r.energy)
                                 for r in out["one-device"]])
    f_err, f_scale = _scale_err([r.forces for r in out["sharded"]],
                                [r.forces for r in out["one-device"]])
    ratio = temp["sharded"] / max(1, temp["one-device"])
    log(f"[sharded] vs one-device: max|dE| {e_err:.3e} (scale {e_scale:.3e})"
        f", max|dF| {f_err:.3e} (scale {f_scale:.3e}), tol {TOL} of scale; "
        f"per-device temp ratio {ratio:.3f} (limit {SHARD_TEMP_RATIO})")
    check(e_err <= TOL * e_scale, f"sharded: energy error {e_err}")
    check(f_err <= TOL * f_scale, f"sharded: force error {f_err}")
    check(ratio <= SHARD_TEMP_RATIO,
          f"sharded: per-device temp ratio {ratio} — rows not split")
    return {"e_err": e_err, "f_err": f_err, "temp_ratio": ratio}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    import jax

    from repro.configs.gaunt_ff import gaunt_mace_ff

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] {dev}, jax {jax.__version__}")
    if dev["platform"] != "tpu":
        log(f"FAIL: no TPU found (platform {dev['platform']!r})")
        return 1
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            check(dev["count"] == 4, f"--chips 4 needs 4 chips, found "
                  f"{dev['count']}")
            res = run_sharded(gaunt_mace_ff, args.seed)
        else:
            res = run_one_chip(gaunt_mace_ff, args.seed, jax.devices("cpu")[0])
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    log(f"[done] {time.perf_counter() - t0:.1f}s; "
        + json.dumps(res, default=float))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
