"""Size-bucketed slot pools for force-field serving (DESIGN.md §10.2).

The single fixed-``max_atoms`` slot array that `EquivariantServeEngine`
carried since PR 2 padded EVERY molecule to the worst case: a 12-atom
molecule in a 256-atom deployment paid 256-atom pair geometry, convolution,
and many-body products.  A `SlotPool` is that slot array scoped to one
atom-count bucket — its own host arrays, its own ghost-atom parking, and its
OWN jitted step function compiled for its own ``[n_slots, max_atoms]``
shapes — and `BucketedPools` is the small/medium/large ladder: a request is
routed to the smallest bucket it fits (`select`), so padding waste is
bounded by the bucket ladder instead of the deployment maximum.

Per-bucket compilation is lazy (a bucket that never sees traffic never
compiles — counter-proven in tests/test_serve_scheduler.py) and per-bucket
warmup is explicit: `EquivariantServeEngine.warmup()` seeds each bucket's
measured chain/gate autotune keys at that bucket's own row count
(``max_atoms * channels`` — the batch_hint the traced step actually sees)
and compiles each step on ghost-only slots.

Async host↔device pipelining (DESIGN.md §10.3) lives in the
`begin_step`/`finish_step` split: `begin_step` uploads the staged slot
tensors and dispatches the jitted step — JAX dispatch is asynchronous, so
the call returns an in-flight handle while the device computes — and
`finish_step` blocks, retires finished requests, and advances relaxations.
Between the two, the engine runs the scheduler's admission pass and
pre-stages other pools' tensors (`stage`), overlapping `jnp.asarray` +
bookkeeping with device compute.  A pool whose host state did not change
since the last upload reuses its staged device tensors (skipped when the
step donates its inputs — donation consumes them).

Step-level fault tolerance (DESIGN.md §11.2): the host slot arrays are the
source of truth, so recovery from a failed step is cheap — drop the staged
device tensors and re-stage.  A step that raises (dispatch or at the
blocking read), exceeds the per-pool watchdog deadline (``step_timeout_s``
against the injectable clock), or returns non-finite results enters
`_on_step_failure`: every affected request is restarted from its admission
geometry snapshot (retry is idempotent — relaxations restart from step 0)
up to its ``max_retries``, past which it is structurally rejected with
``reject_reason='step_failed:<kind>'``; the pool backs off exponentially
(``retry_backoff_s``, consecutive-failure doubling) before re-dispatching.
Non-finite outputs quarantine ONLY the offending slots — bucket-mates with
finite numbers retire normally in the same step — and a batch that fails
collectively is bisected into per-slot verdicts by re-evaluating masked
sub-batches, so one degenerate geometry cannot poison its mates' results.
Fault-injection points (`serve/faults.py`) thread through both halves of
the step; they are no-ops unless a `FaultPlan` is installed.

A model whose config declares ``max_neighbors`` (EquiformerV2) runs on a
neighbour graph: each slot also stages ``nbr [max_atoms, k]`` and
``nbr_mask``, the k nearest atoms within ``max_radius`` of each real atom,
built on the host in float64 (`neighbour_graph`) when the slot is staged
with geometry its graph was not built from, under a ``serve.graph`` span.
Its step evaluates ``energy_graph``.  Any other model stages species,
positions and mask, and its step evaluates ``energy_masked``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import faults

__all__ = ["BucketSpec", "SlotPool", "BucketedPools", "default_buckets",
           "neighbour_graph"]


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """One size bucket: molecules with ``n <= max_atoms`` atoms may land in
    any of its ``n_slots`` slots."""
    max_atoms: int
    n_slots: int = 4
    name: str = ""

    def label(self) -> str:
        return self.name or f"b{self.max_atoms}"


def default_buckets(max_atoms: int, n_slots: int = 4,
                    ladder=(4, 2, 1)) -> tuple[BucketSpec, ...]:
    """A small/medium/large ladder under a deployment cap: bucket sizes
    ``max_atoms // f`` for each ladder divisor (deduplicated, floor 2).
    ``default_buckets(256)`` -> 64/128/256; tiny caps collapse to fewer
    buckets (``default_buckets(4)`` is a single bucket)."""
    names = {0: "small", 1: "medium", 2: "large"}
    sizes = sorted({max(2, max_atoms // f) for f in ladder})
    n = len(sizes)
    return tuple(
        BucketSpec(sz, n_slots, names.get(i + (3 - n), f"b{sz}"))
        for i, sz in enumerate(sizes))


def neighbour_graph(pos, mask, cutoff: float, k: int):
    """Each real atom's k nearest real atoms closer than ``cutoff``, in
    float64, nearest first, ties broken by index; atoms with ``mask`` 0 get
    no edges and are no one's neighbour.  pos [n, 3], mask [n] -> (nbr
    [n, k] int32, the sources of each atom's edges; nbr_mask [n, k]
    float32, 1 for a real edge).  A missing edge points at atom 0."""
    pos = np.asarray(pos, np.float64)
    real = np.asarray(mask) > 0
    n = len(pos)
    d = np.sqrt(np.sum(np.square(pos[None, :, :] - pos[:, None, :]), -1))
    d[~(real[:, None] & real[None, :]) | np.eye(n, dtype=bool)] = np.inf
    d[d >= cutoff] = np.inf
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    ok = np.isfinite(np.take_along_axis(d, order, axis=1))
    nbr = np.zeros((n, k), np.int32)
    nbr_mask = np.zeros((n, k), np.float32)
    w = order.shape[1]
    nbr[:, :w] = np.where(ok, order, 0)
    nbr_mask[:, :w] = ok
    return nbr, nbr_mask


class _Inflight:
    """Handle for a dispatched-but-unfinished pool step."""
    __slots__ = ("active", "energy", "forces", "t0")

    def __init__(self, active, energy, forces, t0):
        self.active = active
        self.energy = energy
        self.forces = forces
        self.t0 = t0


class SlotPool:
    """Fixed atom-padded slots for ONE size bucket, with the bucket's own
    compiled step function (vmapped masked energy + forces over slots)."""

    def __init__(self, model, params, spec: BucketSpec, metrics=None,
                 clock=time.monotonic, step_timeout_s: float | None = None,
                 retry_backoff_s: float = 5e-4, tag: str = ""):
        self.model = model
        self.params = params
        self.spec = spec
        self.metrics = metrics
        self.clock = clock
        self.step_timeout_s = step_timeout_s
        self.retry_backoff_s = retry_backoff_s
        self.tag = tag                 # fault-scope / replica label
        n_slots, max_atoms = spec.n_slots, spec.max_atoms
        self.slot_req: list[Optional[object]] = [None] * n_slots
        self.species = np.zeros((n_slots, max_atoms), np.int32)
        self.pos = np.asarray(self._parked(), np.float32)[None] \
            .repeat(n_slots, 0)
        self.mask = np.zeros((n_slots, max_atoms), np.float32)
        self.steps_run = 0
        # recovery state (DESIGN.md §11.2)
        self.failures = 0              # total failed steps (replica health)
        self._fail_streak = 0          # consecutive failures -> backoff
        self._cooldown_until = 0.0     # begin_step sits out until then
        self._failed_at = None         # first failure of the current outage

        cfg = getattr(model, "cfg", None)
        self.k = getattr(cfg, "max_neighbors", None)
        if self.k is None:
            self.graph = None
            energy = model.energy_masked
        else:
            self.cutoff = float(cfg.max_radius)
            self.graph = (np.zeros((n_slots, max_atoms, self.k), np.int32),
                          np.zeros((n_slots, max_atoms, self.k), np.float32))
            self._graph_of = [None] * n_slots   # (pos, mask) it was built on
            energy = model.energy_graph

        def batched(params, species, pos, mask, *graph):
            """All slots in one call: vmapped masked energy + forces."""
            def one(sp, p, m, *g):
                e, grad = jax.value_and_grad(
                    lambda pp: energy(params, sp, pp, m, *g))(p)
                return e, -grad
            return jax.vmap(one)(species, pos, mask, *graph)

        # step inputs are fresh device buffers every step on accelerators
        # (donation consumes them, so the staged-tensor reuse below is a
        # CPU-only economy); on CPU nothing is donated and clean staged
        # tensors survive across steps
        self._donate = jax.default_backend() != "cpu"
        n_in = 3 if self.graph is None else 5
        donate = tuple(range(1, 1 + n_in)) if self._donate else ()
        self._step_fn = jax.jit(batched, donate_argnums=donate)
        self._staged = None          # device copies of `_host_inputs()`
        self._dirty = True

    # ------------------------------------------------------------ queries
    def compiled(self) -> bool:
        """Whether this bucket's step function has ever compiled — the
        no-cross-bucket-compile counter-proof hooks in here."""
        return self._step_fn._cache_size() > 0

    def fits(self, n_atoms: int) -> bool:
        return n_atoms <= self.spec.max_atoms

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def n_active(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    # ------------------------------------------------------------ slots
    def _parked(self) -> np.ndarray:
        """Ghost-atom positions: distinct sites far outside any cutoff, so
        padded atoms interact with nothing (incl. each other)."""
        far = 1e4 * (1.0 + np.arange(self.spec.max_atoms, dtype=np.float32))
        return np.stack([far, np.zeros_like(far), np.zeros_like(far)], -1)

    def admit(self, req) -> bool:
        """Place a (validated, fitting) request into a free slot; host-side
        writes only — safe while a step for the CURRENT slot contents is in
        flight (the step read its own device copies at dispatch).  The
        admission geometry is snapshotted on the request: a retried or
        failed-over request restarts from this snapshot, so retry is
        idempotent (relaxations restart from step 0)."""
        free = self.free_slots()
        if not free:
            return False
        n = len(req.species)
        slot = free[0]
        self.species[slot] = 0
        self.species[slot, :n] = np.asarray(req.species, np.int32)
        self.pos[slot] = self._parked()
        self.pos[slot, :n] = np.asarray(req.pos, np.float32)
        self.mask[slot] = 0.0
        self.mask[slot, :n] = 1.0
        self.slot_req[slot] = req
        req._snap_pos = self.pos[slot, :n].copy()
        req._snap_steps = int(getattr(req, "steps", 1))
        self._dirty = True
        return True

    # ------------------------------------------------------------ stepping
    def _host_inputs(self, mask=None) -> tuple:
        """The step's inputs after the weights, as host arrays: species,
        positions, mask (``mask`` in place of the slots' own), and the
        neighbour graph where the model runs on one."""
        arrays = (self.species, self.pos, self.mask if mask is None else mask)
        return arrays if self.graph is None else arrays + self.graph

    def _build_graphs(self) -> None:
        """Rebuild the graph of every slot whose positions or mask changed
        since its graph was built (a freed slot's mask is all zero, so its
        graph has no edges)."""
        stale = [s for s, built in enumerate(self._graph_of)
                 if built is None or not (np.array_equal(built[0], self.pos[s])
                                          and np.array_equal(built[1], self.mask[s]))]
        if not stale:
            return
        label = self.spec.label()
        t0 = self.clock()
        edges = 0
        with TraceAnnotation("serve.graph", pool=label):
            nbr, nbr_mask = self.graph
            for s in stale:
                nbr[s], nbr_mask[s] = neighbour_graph(self.pos[s], self.mask[s],
                                                      self.cutoff, self.k)
                self._graph_of[s] = (self.pos[s].copy(), self.mask[s].copy())
                edges += int(nbr_mask[s].sum())
        if self.metrics is not None:
            self.metrics.observe_graph(self.clock() - t0, edges,
                                       len(stale) * self.spec.max_atoms * self.k)

    def stage(self, early: bool = False) -> None:
        """Upload the slot arrays to the device if they changed since the
        last upload, after rebuilding the graphs that went stale.  Called
        with ``early=True`` from the pipelining overlap window (another
        pool's step in flight) — counted so the overlap is observable, not
        just asserted."""
        if self._staged is not None and not self._dirty:
            return
        if self.graph is not None:
            self._build_graphs()
        with TraceAnnotation("serve.stage", pool=self.spec.label()):
            self._staged = tuple(jnp.asarray(a) for a in self._host_inputs())
        self._dirty = False
        if early and self.metrics is not None:
            self.metrics.observe_staged_early(self.spec.label())

    def warmup_compile(self) -> None:
        """Compile this bucket's step on its current (ghost-only at boot)
        slot contents, blocking until done — the per-bucket half of
        `EquivariantServeEngine.warmup()` (which retries transient compile
        failures — the injected kind raises here, before any device work)."""
        if faults._ACTIVE is not None and faults.fire(
                "compile_fail", tag=self.tag,
                pool=self.spec.label()) is not None:
            raise faults.InjectedFault(
                f"injected compile failure in bucket {self.spec.label()}")
        self.stage()
        staged = self._staged
        if self._donate:
            self._staged = None
        jax.block_until_ready(self._step_fn(self.params, *staged))

    def begin_step(self) -> Optional[_Inflight]:
        """Dispatch one fused evaluation of every active slot; returns an
        in-flight handle (device compute proceeds asynchronously).  Returns
        None while the pool is in retry backoff, and routes dispatch-time
        exceptions (real or injected) into step-failure recovery."""
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return None
        if self._cooldown_until and self.clock() < self._cooldown_until:
            return None                  # retry backoff: sit this round out
        if faults._ACTIVE is not None and faults.fire(
                "step_raise", tag=self.tag, pool=self.spec.label(),
                n_active=len(active)) is not None:
            self._on_step_failure(active, "step_raised")
            return None
        self.stage()
        staged = self._staged
        if self._donate:
            self._staged = None          # donated — never touch again
        t0 = self.clock()
        compiled = self._step_fn._cache_size()
        try:
            with TraceAnnotation("serve.dispatch", pool=self.spec.label()):
                e, f = self._step_fn(self.params, *staged)
        except Exception:
            self._on_step_failure(active, "step_raised")
            return None
        if self.metrics is not None and \
                self._step_fn._cache_size() > compiled:
            # a step compiled here, after warm-up: serving paid for it
            self.metrics.observe_step_compile(self.spec.label())
        return _Inflight(active, e, f, t0)

    def finish_step(self, h: _Inflight) -> list:
        """Block on the in-flight step, retire finished requests, advance
        relaxations.  Returns the requests completed by this step.

        The recovery half of the watchdog lives here: an exception at the
        blocking read, a duration past ``step_timeout_s``, or non-finite
        outputs route into `_on_step_failure` — non-finite outputs
        quarantine ONLY the offending slots (bucket-mates retire normally;
        a collectively failing batch is bisected first)."""
        label = self.spec.label()
        try:
            with TraceAnnotation("serve.block", pool=label):
                e = np.asarray(h.energy)   # blocks until the device finishes
                f = np.asarray(h.forces)
        except Exception:
            self._on_step_failure(h.active, "step_raised")
            return []
        with TraceAnnotation("serve.retire", pool=label):
            return self._retire(h, e, f)

    def _retire(self, h: _Inflight, e, f) -> list:
        """The host half of `finish_step` once the results are read:
        watchdog and finiteness checks, retirement, relaxation writes and
        completion stamps."""
        dur = self.clock() - h.t0
        timed_out = (self.step_timeout_s is not None
                     and dur > self.step_timeout_s)
        if faults._ACTIVE is not None:
            if faults.fire("step_timeout", tag=self.tag,
                           pool=self.spec.label(),
                           n_active=len(h.active)) is not None:
                timed_out = True
            nf = faults.fire("step_nonfinite", tag=self.tag,
                             pool=self.spec.label(), n_active=len(h.active))
            if nf is not None:
                e = e.copy()
                f = f.copy()
                slots = nf.payload.get("slots", [0])
                rel = range(len(h.active)) if slots == "all" \
                    else [int(j) % len(h.active) for j in slots]
                for j in rel:
                    e[h.active[j]] = np.nan
                    f[h.active[j]] = np.nan
        if timed_out:
            self._on_step_failure(h.active, "step_timeout")
            return []
        self.steps_run += 1
        real_atoms = sum(len(self.slot_req[i].species) for i in h.active)
        if self.metrics is not None:
            self.metrics.observe_step(
                self.spec.label(), active=len(h.active),
                n_slots=self.spec.n_slots, real_atoms=real_atoms,
                padded_atoms=len(h.active) * self.spec.max_atoms,
                dur_s=dur)
        finite = {i: self._finite(e, f, i) for i in h.active}
        bad = [i for i in h.active if not finite[i]]
        if bad and len(bad) == len(h.active) and len(h.active) > 1:
            # the whole batch is non-finite: bisect into per-slot verdicts
            # (one poisoned slot must not take its mates down with it)
            truly_bad = self._bisect_nonfinite(list(h.active))
            if truly_bad:
                self._on_step_failure(sorted(truly_bad), "nonfinite",
                                      quarantine=True)
            transient = [i for i in h.active if i not in truly_bad
                         and self.slot_req[i] is not None]
            if transient:
                # individually finite — the corruption was batch-level;
                # plain retry, no quarantine accounting
                self._on_step_failure(transient, "nonfinite_collective")
            return []
        if bad:
            # per-slot quarantine: pull ONLY the offending slots from this
            # step's retirements; finite bucket-mates retire normally below
            self._on_step_failure(bad, "nonfinite", quarantine=True)
        completed = []
        good = [i for i in h.active if finite[i]]
        for i in good:
            req = self.slot_req[i]
            n = len(req.species)
            req.energy = float(e[i])
            req.forces = f[i, :n].copy()
            req.pos = self.pos[i, :n].copy()  # the evaluated geometry
            req.steps -= 1
            if req.steps <= 0:
                req.done = True
                self.slot_req[i] = None
                self.mask[i] = 0.0
                self._dirty = True
                completed.append(req)
                if self.metrics is not None:
                    self.metrics.observe_complete(req, self.clock())
            elif req.step_size != 0.0:
                # relaxation: steepest descent on the masked energy
                self.pos[i, :n] += req.step_size * f[i, :n]
                self._dirty = True
        if good:
            # the pool produced usable results: the outage (if any) is over
            self._fail_streak = 0
            self._cooldown_until = 0.0
            if self._failed_at is not None:
                if self.metrics is not None:
                    self.metrics.observe_recovery(self.clock()
                                                  - self._failed_at)
                self._failed_at = None
        return completed

    # --------------------------------------------------------- recovery
    def _finite(self, e, f, i) -> bool:
        n = len(self.slot_req[i].species)
        return bool(np.isfinite(e[i]) and np.all(np.isfinite(f[i, :n])))

    def _bisect_nonfinite(self, slots: list) -> set:
        """Per-slot finite verdicts for a collectively non-finite batch, by
        re-evaluating masked sub-batches from the host slot arrays: a group
        whose re-evaluation separates finite from non-finite slots is
        trusted; a group that fails collectively again is split in half.
        Returns the set of slots that are INDIVIDUALLY non-finite."""
        evals = 0

        def verdicts(group):
            nonlocal evals
            evals += 1
            mask = np.zeros_like(self.mask)
            for i in group:
                mask[i, :len(self.slot_req[i].species)] = 1.0
            e, f = self._step_fn(self.params, *(
                jnp.asarray(a) for a in self._host_inputs(mask)))
            e, f = np.asarray(e), np.asarray(f)
            return {i: self._finite(e, f, i) for i in group}

        def bisect(group):
            v = verdicts(group)
            bad = [i for i in group if not v[i]]
            if len(group) == 1 or len(bad) < len(group):
                return set(bad)
            mid = len(group) // 2
            return bisect(group[:mid]) | bisect(group[mid:])

        bad = bisect(slots)
        if self.metrics is not None:
            self.metrics.observe_bisect(self.spec.label(), evals)
        return bad

    def _on_step_failure(self, slots: list, kind: str,
                         quarantine: bool = False) -> None:
        """Step-failure recovery for ``slots``: restart each affected
        request from its admission snapshot (or structurally reject it past
        ``max_retries``), rebuild device state from the host slot arrays,
        and back off exponentially before the next dispatch."""
        now = self.clock()
        if self._failed_at is None:
            self._failed_at = now
        self.failures += 1
        self._fail_streak += 1
        self._cooldown_until = now + self.retry_backoff_s * \
            (2.0 ** min(self._fail_streak - 1, 6))
        if self.metrics is not None:
            self.metrics.observe_step_failure(self.spec.label(), kind)
        for i in slots:
            req = self.slot_req[i]
            if req is None:
                continue
            if quarantine and self.metrics is not None:
                self.metrics.observe_quarantine(self.spec.label())
            req._retries = getattr(req, "_retries", 0) + 1
            if req._retries > max(0, int(getattr(req, "max_retries", 2))):
                req.rejected = True
                req.done = True
                req.reject_reason = f"step_failed:{kind}"
                req.energy = None
                req.forces = None
                if self.metrics is not None:
                    self.metrics.observe_reject(req, "step_failed")
                self.slot_req[i] = None
                self.mask[i] = 0.0
            else:
                if self.metrics is not None:
                    self.metrics.observe_retry(self.spec.label(), kind)
                self._restore_slot(i)
        # the staged device tensors may reflect the failed dispatch (or have
        # been donated into it): drop them — the host arrays are the source
        # of truth and the next stage() rebuilds device state from them
        self._staged = None
        self._dirty = True

    def _restore_slot(self, i: int) -> None:
        """Reset slot ``i`` to its request's admission snapshot (idempotent
        retry: relaxation restarts from step 0 on the original geometry)."""
        req = self.slot_req[i]
        n = len(req.species)
        self.pos[i] = self._parked()
        self.pos[i, :n] = req._snap_pos
        req.steps = req._snap_steps
        req.energy = None
        req.forces = None

    def evict(self) -> list:
        """Pull every active request out of the pool (replica failover):
        each is restored to its admission snapshot and its slot freed, so
        the caller can requeue it elsewhere.  Retry counts survive — a
        failover does not launder a degenerate geometry's history."""
        evicted = []
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            n = len(req.species)
            req.pos = req._snap_pos.copy()
            req.steps = req._snap_steps
            req.energy = None
            req.forces = None
            self.slot_req[i] = None
            self.mask[i] = 0.0
            evicted.append(req)
        self._staged = None
        self._dirty = True
        return evicted


class BucketedPools:
    """The bucket ladder: pools sorted by ``max_atoms`` ascending; a request
    routes to the smallest bucket that fits it."""

    def __init__(self, model, params, specs, metrics=None,
                 clock=time.monotonic, step_timeout_s: float | None = None,
                 retry_backoff_s: float = 5e-4, tag: str = ""):
        specs = sorted(specs, key=lambda s: s.max_atoms)
        if len({s.max_atoms for s in specs}) != len(specs):
            raise ValueError(f"duplicate bucket sizes: {specs}")
        self.pools = [SlotPool(model, params, s, metrics=metrics,
                               clock=clock, step_timeout_s=step_timeout_s,
                               retry_backoff_s=retry_backoff_s, tag=tag)
                      for s in specs]

    def __iter__(self):
        return iter(self.pools)

    def __len__(self) -> int:
        return len(self.pools)

    @property
    def max_atoms(self) -> int:
        return self.pools[-1].spec.max_atoms

    def select(self, n_atoms: int) -> Optional[SlotPool]:
        """Smallest bucket with ``max_atoms >= n_atoms``; None if the
        request exceeds even the largest bucket."""
        for p in self.pools:
            if p.fits(n_atoms):
                return p
        return None

    def has_active(self) -> bool:
        return any(p.n_active() for p in self.pools)
