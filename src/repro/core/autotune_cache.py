"""Persistent per-host autotune cache (DESIGN.md §4.5).

The measured autotuner (engine.py, ``tune='measure'``) is the arbiter of
every hot-path choice — backend per plan key, chain flavor per chain key,
storage dtype per 'auto' key family — but its selection table lives
in-process, so every serve process re-times every key at startup.  This
module persists the two measurement stores to ONE versioned JSON file per
host so selections are measured once and reused:

    selections   engine._measured    {PlanKey -> backend | chain backend |
                                       dtype winner ('auto' keys)}
    timings      engine._measured_t  {PlanKey -> best wall seconds}

File format (schema-versioned, human-inspectable):

    {"fingerprint": {schema, backend, device_kind, device_count,
                     jax_version, x64},
     "selections": [{"key": {...PlanKey fields...}, "backend": "...",
                     "t": seconds | null}, ...]}

Members the loader does not know (a ``calibration`` member written by older
revisions) are ignored.

Trust rules — a persisted entry is only as good as the measurement that
produced it:

* The whole file is keyed by a hardware/software **fingerprint** (device
  kind, device count, jax version, x64 mode, cache schema version).  Any
  mismatch invalidates the file wholesale: timings from another device kind
  (or another jax) are not this host's timings.  A corrupted or unreadable
  file behaves identically — ``load`` returns None and the engine falls
  back to in-process measurement, never an error.
* Per-entry **stale invalidation** on load: entries naming a backend that is
  no longer registered (or a chain flavor no longer in CHAIN_BACKENDS, or a
  dtype winner that is not a storage dtype), or keyed by an unknown
  kind/dtype, are silently dropped — a cache written by a newer/older code
  revision degrades to partial warmth instead of poisoning selection.
* Only selections that were actually *run* are persisted (the engine never
  caches a failed measurement — see ``GauntEngine._select_chain`` /
  ``_measure``), so a loaded entry always has a real timing behind it
  ('auto' dtype winners carry ``t: null`` but are only ever cached when at
  least one sibling produced a timing).
* Writes are **atomic** (tempfile in the target directory + ``os.replace``)
  and **merging**: flushing re-reads the file and folds in entries a
  concurrent process persisted meanwhile (same fingerprint only) — last
  writer wins per key, no torn files.

The engine engages persistence only when a path is configured: explicitly
(``GauntEngine(cache_path=...)`` / ``set_autotune_cache``), per serve config
(``EquivariantConfig.autotune_cache``), or via the ``REPRO_AUTOTUNE_CACHE``
environment variable.  With no path configured every load/flush is a no-op
and behavior is exactly the historical in-process autotune.

Offline pre-population::

    python -m repro.core.autotune_cache --cache /var/cache/gaunt.json
    python -m repro.core.autotune_cache --cache ... --verify-warm  # 0 runs?

sweeps a known workload grid (pairwise/conv/chain keys at both storage
precisions plus the 'auto' families, and the serve selfmix chain keys —
ungated, gate-fused, and the ``grid_gate='auto'`` policy family) so
processes that tune with ``tune='measure'`` boot with a fully warm
selection table.  It warms only the measured autotuner: the heuristic cost
model reads no file.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile

__all__ = [
    "SCHEMA_VERSION",
    "ENV_VAR",
    "fingerprint",
    "default_path",
    "resolve_path",
    "load",
    "save",
    "main",
]

SCHEMA_VERSION = 1
ENV_VAR = "REPRO_AUTOTUNE_CACHE"


def fingerprint() -> dict:
    """The hardware/software identity persisted measurements are valid for.

    device_kind + device_count pin the hardware (a timing on 1 CPU device
    says nothing about 8 TPU cores), jax_version + x64 pin the software that
    produced the compiled executables being timed, and the schema version
    invalidates files written by an incompatible cache layout.
    """
    import jax

    devs = jax.devices()
    return {
        "schema": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64),
    }


def default_path() -> str:
    """The conventional per-user cache location (the CLI's default target)."""
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(base, "repro", "gaunt_autotune.json")


def resolve_path(path: str | None = None) -> str | None:
    """The effective cache path: explicit arg, else the env var, else None
    (None = persistence disabled; the engine stays purely in-process)."""
    if path:
        return path
    return os.environ.get(ENV_VAR) or None


# --------------------------------------------------------------------------
# (de)serialization
# --------------------------------------------------------------------------


def _tuplify(v):
    """JSON round-trips tuples as lists; PlanKey hashing needs tuples back."""
    if isinstance(v, list):
        return tuple(_tuplify(x) for x in v)
    return v


def _encode_key(key) -> dict:
    return dataclasses.asdict(key)


def _decode_key(d: dict):
    from .engine import PlanKey

    return PlanKey(
        L1=d["L1"], L2=d["L2"], Lout=d["Lout"], kind=d["kind"],
        batch_hint=d["batch_hint"], dtype=d["dtype"],
        extra=_tuplify(d["extra"]),
    )


def _entry_valid(key, backend: str) -> bool:
    """Per-entry stale invalidation (see module docstring)."""
    from .engine import _RDTYPE, _REGISTRY, CHAIN_BACKENDS, KINDS

    if not isinstance(backend, str):
        return False
    if key.kind != "chain" and key.kind not in KINDS:
        return False
    if key.dtype == "auto":
        # 'auto' family keys store the winning STORAGE dtype, not a backend
        return backend in ("float32", "bfloat16")
    if key.dtype not in _RDTYPE:
        return False
    if key.kind == "chain":
        if ("gate", "policy") in key.extra:
            # grid_gate='auto' policy keys (engine.select_gate) store the
            # gate placement winner, not a chain backend
            return backend in ("grid", "sh")
        return backend in CHAIN_BACKENDS
    return backend in _REGISTRY


def load(path: str | None):
    """-> (selections, timings) or None.

    None means "no usable cache": missing file, unreadable/corrupt JSON,
    wrong schema, or a fingerprint mismatch — all fall back to in-process
    measurement without error.  Stale entries are dropped individually.
    """
    if not path:
        return None
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(raw, dict) or raw.get("fingerprint") != fingerprint():
        return None
    selections, timings = {}, {}
    for ent in raw.get("selections", ()):
        try:
            key = _decode_key(ent["key"])
            backend = ent["backend"]
        except (KeyError, TypeError):
            continue
        if not _entry_valid(key, backend):
            continue
        selections[key] = backend
        t = ent.get("t")
        if isinstance(t, (int, float)):
            timings[key] = float(t)
    return selections, timings


def save(path: str, selections: dict, timings: dict,
         merge: bool = True) -> None:
    """Atomically persist the measurement stores to ``path``.

    With ``merge`` (the default) a valid same-fingerprint file already at
    ``path`` contributes entries we don't have locally — concurrent
    processes flushing different keys converge instead of clobbering.
    The write itself is tempfile + ``os.replace``: readers never see a
    torn file, and the last concurrent writer wins wholesale.
    """
    selections = dict(selections)
    timings = dict(timings)
    if merge:
        prev = load(path)
        if prev is not None:
            for k, b in prev[0].items():
                selections.setdefault(k, b)
            for k, t in prev[1].items():
                timings.setdefault(k, t)
    payload = {
        "fingerprint": fingerprint(),
        "selections": [
            {"key": _encode_key(k), "backend": b, "t": timings.get(k)}
            for k, b in selections.items()
        ],
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".gaunt_autotune.", suffix=".json", dir=d)
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# --------------------------------------------------------------------------
# offline warm-up CLI
# --------------------------------------------------------------------------


def _sweep(eng, fast: bool, serve_rows: tuple = (1024,)) -> int:
    """Measure the known workload grid into ``eng``'s selection table.

    Pairwise, conv and chain keys plus the serve warmup's selfmix chain
    keys, at both storage precisions and the 'auto' family, so a process
    that loads the resulting file boots with zero timing runs.
    """
    n0 = len(eng._measured)
    dtypes = ("float32", "bfloat16", "auto")
    # pairwise + conv_filter plan keys
    L_list = (1, 2, 3, 6) if fast else (1, 2, 3, 4, 6)
    B_list = (64, 1024)
    for L in L_list:
        for B in B_list:
            for d in dtypes:
                eng.plan(L, L, L, batch_hint=B, dtype=d, tune="measure",
                         requires_grad=False)
        eng.plan(L, L, L, kind="conv_filter", batch_hint=B_list[-1],
                 tune="measure", requires_grad=False)
    # chained workloads
    chains = [
        ((1, 1, 1), 1, 512),
        ((2, 2), 2, 64),
        ((2, 2, 2), 2, 128),
        ((3, 3, 3), 3, 64),
        ((2, 2, 2, 2), 8, 256),
    ]
    if fast:
        chains = chains[:3]
    for Ls, Lout, B in chains:
        for d in dtypes:
            eng.plan_chain(Ls, Lout, tune="measure", batch_hint=B, dtype=d)
    # serve warmup's selfmix chain keys (shared-operand [A]*nu pattern) for
    # the shipped force-field configs, at the requested row hints
    from repro.configs.gaunt_ff import gaunt_mace_ff as _cfg

    for rows in serve_rows:
        for d in dtypes:
            eng.plan_chain((_cfg.L,) * _cfg.nu, _cfg.L, tune="measure",
                           batch_hint=int(rows), share_hint=(0,) * _cfg.nu,
                           dtype=d)
            # gate-fused siblings + the grid_gate='auto' policy family
            # (DESIGN.md §6.5): a serve config with grid_gate != 'off'
            # seeds exactly these keys in warmup()
            eng.plan_chain((_cfg.L,) * _cfg.nu, _cfg.L, tune="measure",
                           batch_hint=int(rows), share_hint=(0,) * _cfg.nu,
                           dtype=d, gate=True)
            eng.select_gate((_cfg.L,) * _cfg.nu, _cfg.L, dtype=d,
                            batch_hint=int(rows),
                            share_hint=(0,) * _cfg.nu)
    return len(eng._measured) - n0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.autotune_cache",
        description="Offline autotune warm-up: sweep the known workload "
                    "grid and persist the measured selection table so "
                    "processes that tune by measurement boot warm.")
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default: ${ENV_VAR} or "
                         f"{default_path()})")
    ap.add_argument("--fast", action="store_true", help="smaller sweep")
    ap.add_argument("--serve-rows", default="1024",
                    help="comma-separated serve chain row hints "
                         "(max_atoms*channels per deployment)")
    ap.add_argument("--verify-warm", action="store_true",
                    help="re-run the sweep and FAIL (exit 2) if any timing "
                         "run happened — proves the cache file fully covers "
                         "the grid")
    args = ap.parse_args(argv)

    from .engine import get_engine

    path = resolve_path(args.cache) or default_path()
    eng = get_engine()
    eng.set_autotune_cache(path)
    loaded = eng.load_autotune_cache()
    rows = tuple(int(r) for r in args.serve_rows.split(",") if r)
    new = _sweep(eng, fast=args.fast, serve_rows=rows)
    eng.flush_autotune_cache()
    print(f"cache: {path}")
    print(f"loaded {loaded} persisted selections; measured {new} new; "
          f"{eng.timing_runs} timing runs this process")
    if args.verify_warm and eng.timing_runs > 0:
        print(f"VERIFY-WARM FAILED: {eng.timing_runs} timing runs — the "
              "cache did not cover the sweep (stale fingerprint? partial "
              "file?)")
        return 2
    if args.verify_warm:
        print("verify-warm OK: zero timing runs")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
