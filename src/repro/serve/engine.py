"""Batched serving engines (DESIGN.md §10).

`ServeEngine` — slot-based continuous batching for LM decoding over a shared
KV (or recurrent-state) cache:

- Fixed B decode slots; requests are admitted into free slots, prefilled
  one-at-a-time (slot-batched prefill), then all active slots step together.
- Greedy or temperature sampling; sampling keys derive from
  ``(engine seed, request rid, token index)`` so a request's sampled tokens
  are reproducible regardless of admission order or batch composition.
- Per-slot stop conditions (EOS / max_len); the ``max_new_tokens`` budget is
  checked at admission too — the prefill-sampled token counts against it.
- Cache layouts come from Model.init_cache and work for every family
  (attention KV, RWKV state, Zamba hybrid).

`EquivariantServeEngine` — the same continuous-batching discipline for
force-field inference (energy/forces/relaxation requests on a Gaunt-MACE
model), scaled out across the serve subsystem:

- **admission** rides `serve/scheduler.py`: a priority queue with
  per-request deadlines and structured rejection (invalid or oversized
  geometry never touches a shared batched step);
- **slots** ride `serve/pools.py`: size-bucketed slot pools, each bucket
  compiling its own step function for its own padded shape, so a small
  molecule no longer pads to the deployment-maximum atom count;
- **stepping** is pipelined: each pool's jitted step is dispatched
  asynchronously and the NEXT step's admissions + host slot writes +
  device staging overlap the in-flight device computation;
- **observability** rides `serve/metrics.py`: queue-wait/step/total
  latency, occupancy and padding-waste gauges, rejection counters, and the
  Gaunt engine's own timing-run/conversion counters.

Inside every step each layer's tensor products route through the engine's
batched Gaunt plans (DESIGN.md §5) and Fourier-resident chain plans
(DESIGN.md §6): per relaxation step each layer's many-body product converts
once and projects once, the edge geometry is built once, and each bucket's
compiled step (plus the plan/constant caches behind it) is carried across
ALL relaxation steps of every request it serves.  Residency holds for
sharded configs too (``shard_data``): resident grids row-shard through the
batched buckets, so the serving step is never forced off the resident
route.  ``warmup()`` seeds every bucket's measured autotune keys and
compiles every bucket's step on ghost-only slots, so the first real request
pays serving cost only.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import faults
from .metrics import ServeMetrics
from .pools import BucketedPools, BucketSpec
from .scheduler import REASON_INVALID, REASON_TOO_LARGE, Scheduler

__all__ = ["ServeEngine", "Request",
           "EquivariantServeEngine", "EquivariantRequest"]


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    rid: int = 0
    # scheduling (serve/scheduler.py): lower priority value = served first;
    # deadline = seconds of allowed queue wait from submission, None = none
    priority: int = 0
    deadline: float | None = None
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False
    reject_reason: str | None = None


class ServeEngine:
    def __init__(self, model, params, n_slots: int = 4, max_len: int = 512, seed: int = 0):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = model.init_cache(n_slots, max_len)
        self.pos = np.full(n_slots, -1, dtype=np.int32)  # last written index
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self._base_key = jax.random.PRNGKey(seed)
        self.metrics = ServeMetrics()
        self._decode = jax.jit(model.decode_step)

        def prefill_one(params, cache, tokens, slot):
            """Prefill a single sequence via repeated decode steps (works for
            every cache family without slot-gather logic)."""
            def body(carry, tok_pos):
                cache, _ = carry
                tok, p = tok_pos
                toks = jnp.zeros((self.n_slots, 1), jnp.int32).at[slot, 0].set(tok)
                # inactive slots write to a scratch position (max_len-1) so
                # they can never clobber live sequences
                pos = jnp.full((self.n_slots,), max_len - 1, jnp.int32).at[slot].set(p)
                logits, cache = model.decode_step(params, cache, toks, pos)
                return (cache, logits[slot, 0]), None

            (cache, last_logits), _ = jax.lax.scan(
                body, (cache, jnp.zeros((model.cfg.vocab,), jnp.float32)),
                (tokens, jnp.arange(tokens.shape[0], dtype=jnp.int32)),
            )
            return cache, last_logits

        self._prefill_one = jax.jit(prefill_one)

    # ------------------------------------------------------------- admission
    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def has_active(self) -> bool:
        return any(r is not None for r in self.slot_req)

    def validate(self, req: Request):
        """Admission-time validation -> None | (reason, detail)."""
        if not req.prompt:
            return (REASON_INVALID, "empty prompt")
        if req.max_new_tokens < 1:
            return (REASON_INVALID,
                    f"max_new_tokens={req.max_new_tokens} < 1")
        if len(req.prompt) + 1 >= self.max_len:
            return (REASON_TOO_LARGE,
                    f"prompt of {len(req.prompt)} tokens leaves no decode "
                    f"room under max_len={self.max_len}")
        return None

    def _reset_slot(self, slot: int):
        """Zero one slot's rows in every cache leaf (batch dim = 1)."""
        self.cache = jax.tree.map(
            lambda a: a.at[:, slot].set(jnp.zeros_like(a[:, slot])), self.cache)

    def add_request(self, req: Request) -> bool:
        free = self._free_slots()
        if not free:
            return False
        slot = free[0]
        pre = self.cache  # pre-admission cache (fast-retire restores it)
        self._reset_slot(slot)  # recurrent families accumulate state otherwise
        toks = jnp.asarray(req.prompt, jnp.int32)
        snapshot = self.cache
        new_cache, last_logits = self._prefill_one(
            self.params, self.cache, toks, slot)
        # keep ONLY this slot's rows from the prefill — recurrent families
        # update every row per step, which would pollute live slots
        self.cache = jax.tree.map(
            lambda old, new: old.at[:, slot].set(new[:, slot]), snapshot, new_cache)
        # first generated token comes from the last prompt logits
        tok = self._sample(last_logits, req)
        req.output.append(int(tok))
        if len(req.output) >= req.max_new_tokens:
            # budget met by the prefill-sampled token: retire at admission,
            # never occupy the slot (a max_new_tokens=1 request used to get
            # a second token before the post-step done check fired) — and
            # put the cache back exactly as found: the slot was never
            # occupied, so its rows must not carry this prefill's state
            self.cache = pre
            req.done = True
            self.metrics.observe_complete(req)
            return True
        self.pos[slot] = len(req.prompt) - 1
        self.slot_req[slot] = req
        return True

    # scheduler protocol: admission (validation runs in the scheduler)
    try_admit = add_request

    def _sample(self, logits, req: Request):
        if req.temperature <= 0:
            return int(jnp.argmax(logits))
        # reproducible per request: (engine seed, rid, token index) — NOT a
        # shared mutating engine key, whose stream depended on admission order
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, req.rid), len(req.output))
        return int(jax.random.categorical(key, logits / req.temperature))

    # ------------------------------------------------------------- stepping
    def step(self, overlap=None):
        """One decode step for all active slots.  ``overlap`` (the
        scheduler's admission pass) runs after the decode dispatch and
        before sampling reads the logits, so prefill/bookkeeping for the
        next step's admissions overlaps the in-flight decode."""
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return
        toks = np.zeros((self.n_slots, 1), np.int32)
        pos_np = np.full(self.n_slots, self.max_len - 1, np.int32)  # scratch
        for i in active:
            toks[i, 0] = self.slot_req[i].output[-1]
            pos_np[i] = self.pos[i] + 1
        pos = jnp.asarray(pos_np)
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(toks), pos)
        if overlap is not None:
            overlap()
        for i in active:
            self.pos[i] += 1
            req = self.slot_req[i]
            tok = self._sample(logits[i, 0], req)
            req.output.append(tok)
            if len(req.output) >= req.max_new_tokens or self.pos[i] + 2 >= self.max_len:
                req.done = True
                self.metrics.observe_complete(req)
                self.slot_req[i] = None
                self.pos[i] = -1

    def run(self, requests: list[Request]) -> list[Request]:
        return Scheduler(self).run(requests)


# --------------------------------------------------------------------------
# equivariant (force-field) serving
# --------------------------------------------------------------------------


@dataclasses.dataclass
class EquivariantRequest:
    """One molecular inference job: `steps` gradient-descent relaxation steps
    (steps=1 => a single energy/forces evaluation)."""

    species: np.ndarray           # [n] int
    pos: np.ndarray               # [n, 3]; updated in place by relaxation —
    #                               on completion it is the geometry that
    #                               produced `energy`/`forces`
    steps: int = 1
    step_size: float = 0.0        # relaxation: pos += step_size * forces
    rid: int = 0
    # fault tolerance (DESIGN.md §11): failed/timed-out/non-finite steps
    # retry this request from its admission snapshot up to max_retries
    # total attempts beyond the first; past it -> reject_reason='step_failed'
    max_retries: int = 2
    # scheduling (serve/scheduler.py): lower priority value = served first;
    # deadline = seconds of allowed queue wait from submission, None = none
    priority: int = 0
    deadline: float | None = None
    # filled by the engine:
    energy: float | None = None
    forces: np.ndarray | None = None
    done: bool = False
    rejected: bool = False
    reject_reason: str | None = None


class EquivariantServeEngine:
    """Continuous batching for a MaceGaunt-style model, or one served on a
    host-built neighbour graph (EquiformerV2, `serve/pools.py`), over
    size-bucketed atom-padded slot pools: every step dispatches one fused
    batched evaluation per active bucket, pipelining the next step's
    admissions against the in-flight device compute."""

    def __init__(self, model, params, n_slots: int = 4, max_atoms: int = 16,
                 warmup: bool = False, buckets=None, clock=time.monotonic,
                 step_timeout_s: float | None = None,
                 retry_backoff_s: float = 5e-4, metrics=None, tag: str = ""):
        self.model = model
        self.params = params
        self.clock = clock
        self.tag = tag                 # replica label (fault scoping)
        self.metrics = metrics if metrics is not None \
            else ServeMetrics(clock=clock)
        specs = self._resolve_buckets(buckets, n_slots, max_atoms)
        self.pools = BucketedPools(model, params, specs,
                                   metrics=self.metrics, clock=clock,
                                   step_timeout_s=step_timeout_s,
                                   retry_backoff_s=retry_backoff_s, tag=tag)
        if warmup:
            self.warmup()

    def _resolve_buckets(self, buckets, n_slots, max_atoms):
        """Bucket resolution: explicit ``buckets`` arg > the config's
        ``serve_buckets`` knob > a single (max_atoms, n_slots) bucket (the
        historical fixed-padding behavior)."""
        if buckets is None:
            cfg = getattr(self.model, "cfg", None)
            buckets = getattr(cfg, "serve_buckets", None) \
                if cfg is not None else None
        if buckets is None:
            return (BucketSpec(max_atoms, n_slots),)
        return tuple(b if isinstance(b, BucketSpec) else BucketSpec(*b)
                     for b in buckets)

    # ------------------------------------------------------- compat surface
    @property
    def max_atoms(self) -> int:
        return self.pools.max_atoms

    @property
    def n_slots(self) -> int:
        return sum(p.spec.n_slots for p in self.pools)

    @property
    def slot_req(self) -> list:
        """Flat view over every pool's slots (smallest bucket first)."""
        return [r for p in self.pools for r in p.slot_req]

    # ------------------------------------------------------------- warmup
    def warmup(self) -> None:
        """Per-bucket compile + autotune seeding, so admission latency for
        the first real request is serving cost only.  Each bucket's step is
        compiled on ghost-only slots, and each bucket's measured chain keys
        are seeded at that bucket's OWN row count — the batch_hint its
        traced step actually presents.

        With ``cfg.chain_tune='measure'`` the model's chained products
        dispatch through the engine's measured chain autotuner (DESIGN.md
        §6.4) — measurement cannot run inside a step's jit trace, so it is
        seeded here, outside jit: per bucket, the many-body selfmix chain
        key (the only chain a served MaceGaunt plans — its layer-constant
        edge geometry rides boundary buckets, not chains) is measured once
        and the traced step then hits the cached selection.  Both storage
        precisions are pre-measured (DESIGN.md §3.6), and a ``grid_gate``
        'auto' policy is resolved per bucket before its step compiles
        (DESIGN.md §6.5).  Skipped for ``shard_data`` configs: sharded
        chains pin the 'tree' backend and never consult the measured cache.

        If a persistent autotune cache is configured (``cfg.autotune_cache``
        or $REPRO_AUTOTUNE_CACHE, DESIGN.md §4.5), it is loaded FIRST: on a
        warm host every per-bucket key hits the persisted table and warmup
        performs zero timing runs — subprocess-proven in
        tests/test_serve_scale.py."""
        cfg = getattr(self.model, "cfg", None)
        from repro.core import engine as _engine

        # each pool's warm-up seconds, as laps of one stopwatch: its seeding
        # and its compile (the first lap also holds the cache load)
        lap = self.metrics.clock()

        def charge(pool):
            nonlocal lap
            now = self.metrics.clock()
            self.metrics.observe_warmup(pool.spec.label(), now - lap)
            lap = now

        eng = _engine.get_engine()
        cache = getattr(cfg, "autotune_cache", None) if cfg is not None else None
        if cache is not None:
            eng.set_autotune_cache(cache)
        if faults._ACTIVE is not None and faults.fire(
                "autotune_cache_load", tag=self.tag) is not None:
            # unreadable persistent cache: degrade to cold measurement —
            # serving still comes up, it just pays warmup timing runs
            self.metrics.counters["autotune_cache_load_failed"] += 1
        else:
            eng._maybe_load_cache()
        if (cfg is not None
                and getattr(cfg, "chain_tune", "heuristic") == "measure"
                and not getattr(cfg, "shard_data", False)):
            for pool in self.pools:
                # mirror each bucket's traced call exactly: per-slot row
                # count (the step vmaps over slots, so the chain sees
                # [bucket max_atoms, channels] leading dims per element)
                # and the selfmix [A]*nu share pattern
                rows = pool.spec.max_atoms * cfg.channels
                dts = getattr(cfg, "compute_dtype", "float32")
                gg = getattr(cfg, "grid_gate", "off")
                if gg == "auto":
                    gg = "on" if eng.select_gate(
                        (cfg.L,) * cfg.nu, cfg.L, dtype=dts, batch_hint=rows,
                        entry_hint=("sh",) * cfg.nu,
                        share_hint=(0,) * cfg.nu) == "grid" else "off"
                gate_opts = (False, True) if gg in ("on", "grid", True) \
                    else (False,)
                for d in dict.fromkeys(["float32", dts] if dts != "auto"
                                       else ["auto"]):
                    for g in gate_opts:
                        _engine.plan_chain((cfg.L,) * cfg.nu, cfg.L,
                                           tune="measure", batch_hint=rows,
                                           share_hint=(0,) * cfg.nu, dtype=d,
                                           gate=g)
                charge(pool)
        for pool in self.pools:
            # transient compile failures (injected or real) retry: a serving
            # host that loses one compile attempt should come up, not die
            for attempt in range(3):
                try:
                    pool.warmup_compile()
                    break
                except Exception:
                    self.metrics.counters["warmup_retries"] += 1
                    if attempt == 2:
                        raise
            charge(pool)

    # ------------------------------------------------------------- admission
    def has_active(self) -> bool:
        return self.pools.has_active()

    def evict_active(self) -> list:
        """Pull every in-flight request out of every pool, restored to its
        admission snapshot (replica failover: `serve/replicas.py` requeues
        them onto surviving replicas)."""
        return [r for p in self.pools for r in p.evict()]

    def validate(self, req: EquivariantRequest):
        """Admission-time validation -> None | (reason, detail).  Bad
        geometry is rejected HERE, structurally — one NaN position evaluated
        in a shared batched step would poison every slot's gradient."""
        species = np.asarray(req.species)
        if species.size == 0:
            return (REASON_INVALID, "empty species")
        if not np.issubdtype(species.dtype, np.integer):
            return (REASON_INVALID,
                    f"species dtype {species.dtype} is not integral")
        if species.min() < 0:
            return (REASON_INVALID,
                    f"negative species value {int(species.min())}")
        n_species = getattr(getattr(self.model, "cfg", None),
                            "n_species", None)
        if n_species is not None and species.max() >= n_species:
            # the jitted step's embedding gather clamps out-of-range
            # indices, which would silently produce a wrong energy
            return (REASON_INVALID,
                    f"species value {int(species.max())} >= "
                    f"n_species={n_species}")
        if getattr(req, "steps", 1) < 1:
            return (REASON_INVALID, f"steps={req.steps} < 1")
        pos = np.asarray(req.pos, np.float32)
        if pos.shape != (species.size, 3):
            return (REASON_INVALID,
                    f"pos shape {pos.shape} != ({species.size}, 3)")
        if not np.all(np.isfinite(pos)):
            return (REASON_INVALID, "non-finite positions")
        if species.size > self.pools.max_atoms:
            return (REASON_TOO_LARGE,
                    f"{species.size} atoms > largest bucket "
                    f"{self.pools.max_atoms}")
        return None

    def try_admit(self, req: EquivariantRequest) -> bool:
        """Admit into the smallest bucket that fits (strictly — a small
        request never spills into a larger bucket, so it can never trigger
        a larger bucket's compile or pay its padding)."""
        pool = self.pools.select(len(req.species))
        if pool is None:  # unreachable through the scheduler (validate)
            return False
        return pool.admit(req)

    def add_request(self, req: EquivariantRequest) -> bool:
        """Direct (scheduler-less) admission, kept for callers that manage
        their own loop: validation failures reject structurally (the request
        is consumed: ``rejected=True, done=True``) and return True; False
        means no free slot right now."""
        err = self.validate(req)
        if err is not None:
            req.rejected, req.done = True, True
            req.reject_reason = f"{err[0]}:{err[1]}" if err[1] else err[0]
            self.metrics.observe_reject(req, err[0])
            return True
        return self.try_admit(req)

    # ------------------------------------------------------------- stepping
    def step(self, overlap=None):
        """One pipelined evaluation round: dispatch every active bucket's
        jitted step (asynchronous), run the overlap callback (the
        scheduler's admission pass — queue pops, validation, host slot
        writes) and pre-stage idle pools' tensors while the device computes,
        then block, retire finished requests, and advance relaxations."""
        inflight = []
        for pool in self.pools:
            h = pool.begin_step()
            if h is not None:
                inflight.append((pool, h))
        if overlap is not None:
            overlap()
        busy = {id(p) for p, _ in inflight}
        for pool in self.pools:
            # stage pools admitted-into during the overlap window (their
            # step dispatches next round); in-flight pools re-stage after
            # finish_step's relaxation writes
            if id(pool) not in busy and pool.n_active():
                pool.stage(early=True)
        for pool, h in inflight:
            pool.finish_step(h)

    def run(self, requests: list[EquivariantRequest]) -> list[EquivariantRequest]:
        return Scheduler(self, clock=self.clock).run(requests)
