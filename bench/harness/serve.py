"""Drives `EquivariantServeEngine` through its `Scheduler`, open or closed
loop, and records when each request was due (or sent) and when its answer
came back.  The feed/pump loop is the one `benchmarks/bench_serve.py` uses.
`run_serving` is the whole run of a serving cell: set-up, the window, and
the comparison of a seed-drawn sample of the answers with the reference.

Host spans (``jax.profiler.TraceAnnotation``): ``window`` around the
measured window, ``client`` around the generator's submissions, ``pump``
around each `Scheduler.pump`, ``wait`` around idle sleeps.  A traced run's idle gaps are labelled by them.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve import EquivariantRequest, EquivariantServeEngine
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Scheduler

from .cell import Context, device_info, make_params, pairs_within
from .stats import percentile
from .trace import WINDOW_SPAN
from .traffic import rng_for

__all__ = ["Served", "CompletionClock", "make_engine", "open_loop",
           "closed_loop", "run_serving", "serve_gaps", "latency_p95_ms",
           "GRACE_S"]

# how long past the window's close a run waits for answers still due
GRACE_S = 60.0


class CompletionClock(ServeMetrics):
    """`ServeMetrics` that also stamps each request with the time its
    answer was ready."""

    def observe_complete(self, req, now=None):
        now = self.clock() if now is None else now
        req._done_t = now
        super().observe_complete(req, now)


@dataclasses.dataclass
class Served:
    requests: list          # EquivariantRequest, in submission order
    start_t: list           # due (open loop) or sent (closed loop) time
    t0: float               # window open (clock seconds)
    seconds: float
    lateness_max_s: float   # how late the generator submitted, at worst

    def latencies_s(self) -> np.ndarray:
        """Answer time minus due/sent time.  A request that failed or never
        came back counts as missing: as late as the run waited for it."""
        gave_up = self.t0 + self.seconds + GRACE_S
        return np.asarray([
            (r._done_t - s) if self._answered(r) else gave_up - s
            for r, s in zip(self.requests, self.start_t)])

    @staticmethod
    def _answered(r) -> bool:
        return (r.done and not r.rejected
                and getattr(r, "_done_t", None) is not None)

    def completed_in_window(self) -> int:
        end = self.t0 + self.seconds
        return sum(1 for r in self.requests
                   if self._answered(r) and r._done_t <= end)

    def failed(self) -> int:
        return sum(1 for r in self.requests if not self._answered(r))


def make_engine(model, params, buckets, clock=time.perf_counter):
    return EquivariantServeEngine(model, params, buckets=tuple(
        tuple(b) for b in buckets), clock=clock,
        metrics=CompletionClock(clock=clock))


def _drain(sched, eng, pending, deadline, clock):
    while any(not r.done for r in pending) and clock() < deadline:
        with TraceAnnotation("pump"):
            sched.pump()


def open_loop(eng, arrivals, seconds: float, clock=time.perf_counter) -> Served:
    """Submit each arrival at its due time, whether or not earlier ones are
    done; pump the engine between.  After the window, wait (up to
    ``GRACE_S``) for every submitted request."""
    sched = Scheduler(eng, clock=clock)
    reqs, due = [], []
    state = {"i": 0, "late": 0.0}
    t0 = clock()

    def feed():
        now = clock()
        with TraceAnnotation("client"):
            while (state["i"] < len(arrivals)
                   and t0 + arrivals[state["i"]].due_s <= now):
                a = arrivals[state["i"]]
                r = EquivariantRequest(species=a.species, pos=a.pos.copy(),
                                       rid=state["i"])
                sched.submit(r)
                reqs.append(r)
                due.append(t0 + a.due_s)
                state["late"] = max(state["late"], now - due[-1])
                state["i"] += 1

    with TraceAnnotation(WINDOW_SPAN):
        while state["i"] < len(arrivals) or clock() < t0 + seconds:
            feed()
            if eng.has_active() or len(sched.queue):
                with TraceAnnotation("pump"):
                    sched.pump(poll=feed)
            else:
                nxt = (t0 + arrivals[state["i"]].due_s
                       if state["i"] < len(arrivals) else t0 + seconds)
                with TraceAnnotation("wait"):
                    time.sleep(max(0.0, min(0.002, nxt - clock())))
    _drain(sched, eng, reqs, t0 + seconds + GRACE_S, clock)
    return Served(reqs, due, t0, seconds, state["late"])


def closed_loop(eng, starts, next_pos, seconds: float,
                clock=time.perf_counter) -> Served:
    """Each client sends its next request as soon as its previous answer is
    back, until the window closes.  ``next_pos(client, call, pos0)`` gives
    a call's geometry."""
    sched = Scheduler(eng, clock=clock)
    reqs, sent = [], []
    calls = [0] * len(starts)
    inflight = [None] * len(starts)
    t0 = clock()

    def send(c):
        sp, p0 = starts[c]
        r = EquivariantRequest(species=sp, pos=next_pos(c, calls[c], p0),
                               rid=len(reqs))
        calls[c] += 1
        sched.submit(r)
        reqs.append(r)
        sent.append(clock())
        inflight[c] = r

    def feed():
        if clock() >= t0 + seconds:
            return
        with TraceAnnotation("client"):
            for c, r in enumerate(inflight):
                if r is None or r.done:
                    send(c)

    with TraceAnnotation(WINDOW_SPAN):
        while clock() < t0 + seconds:
            feed()
            with TraceAnnotation("pump"):
                sched.pump(poll=feed)
    _drain(sched, eng, reqs, t0 + seconds + GRACE_S, clock)
    return Served(reqs, sent, t0, seconds, 0.0)


def latency_p95_ms(served) -> float:
    return percentile(list(served.latencies_s()), 95) * 1e3


# ------------------------------------------------------------ a serving run

def run_serving(cell, env, drive) -> Context:
    """Set-up (weights, engine, warm-up of every bucket), the window
    (``drive(engine) -> Served``), then the reference over a sample of the
    answers.  -> the run's `Context`."""
    import jax

    model_cfg = cell.config["model"]
    model = cell.program.build(cell.config)
    params = make_params(cell, env.seed)
    eng = make_engine(model, params, cell.mix["buckets"])
    eng.warmup()
    env.open_window()
    setup_s = time.perf_counter() - env.t_start
    served = drive(eng)
    env.close_window()
    device = device_info(env.devices)
    end = served.t0 + served.seconds
    flops = sum(3 * cell.family.forward_flops(
        model_cfg, len(r.species), pairs_within(r.pos, model_cfg["cutoff"]))
        for r in served.requests if Served._answered(r) and r._done_t <= end)
    env.log(f"[serve] {len(served.requests)} requests, "
            f"{served.completed_in_window()} answered in the "
            f"{served.seconds}s window, {served.failed()} failed; generator "
            f"late by at most {served.lateness_max_s * 1e3:.3f} ms; steps "
            f"{eng.metrics.counters['steps']}")
    metrics = eng.metrics
    del eng, model
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_sample(cell, params, served, env.seed)
    env.log(f"[reference] {len(ref)} requests in "
            f"{time.perf_counter() - t0:.1f}s")
    gaps = serve_gaps([(r.energy, e, r.forces, f) for r, e, f in ref])
    return Context(cell, setup_s, served.seconds, flops, len(env.devices),
                   attempted=len(served.requests), failed=served.failed(),
                   gaps=gaps, served=served, serve_metrics=metrics,
                   device=device)


def reference_sample(cell, params, served, seed, dtype="float32"):
    """The reference over a seed-drawn sample of the answered requests,
    the largest among them.  -> list of (request, E_ref, F_ref)."""
    import jax
    import jax.numpy as jnp

    done = [r for r in served.requests if Served._answered(r)]
    if not done:
        return []
    k = min(len(done), int(cell.mix["reference_sample"]))
    rng = rng_for(seed, 5)
    biggest = max(range(len(done)), key=lambda i: len(done[i].species))
    rest = [i for i in range(len(done)) if i != biggest]
    pick = [biggest] + list(rng.choice(rest, size=k - 1, replace=False)) \
        if k > 1 else [biggest]
    sample = [done[i] for i in pick]
    buckets = sorted(b[0] for b in cell.mix["buckets"])
    model = cell.config["model"]
    chunk = int(cell.mix["reference_chunk"])
    ef = jax.jit(jax.vmap(lambda p, s, x, m: cell.family.energy_forces(
        p, s, x, m, model, dtype), in_axes=(None, 0, 0, 0)))
    out = []
    by_size = {}
    for r in sample:
        by_size.setdefault(next(b for b in buckets if b >= len(r.species)),
                           []).append(r)
    for size, reqs in sorted(by_size.items()):
        for c in range(0, len(reqs), chunk):
            part = reqs[c: c + chunk]
            sp = np.zeros((chunk, size), np.int32)
            pos = (1e3 * (1 + np.arange(size)))[None, :, None] * np.ones(
                (chunk, size, 3), np.float32)
            mask = np.zeros((chunk, size), np.float32)
            for j, r in enumerate(part):
                n = len(r.species)
                sp[j, :n], pos[j, :n], mask[j, :n] = r.species, r.pos, 1.0
            e, f = ef(params, jnp.asarray(sp), jnp.asarray(pos, jnp.float32),
                      jnp.asarray(mask))
            e, f = np.asarray(e), np.asarray(f)
            for j, r in enumerate(part):
                out.append((r, float(e[j]), f[j, :len(r.species)]))
    return out


def serve_gaps(pairs) -> dict:
    """Energy and force gaps over the sample, each over the sample's scale:
    max |E - E_ref| / max |E_ref| and max |F - F_ref| / max |F_ref|."""
    if not pairs:
        return {"energy_gap": np.nan, "force_gap": np.nan}
    e_err = max(abs(np.float64(e) - np.float64(er)) for e, er, _, _ in pairs)
    e_scale = max(abs(np.float64(er)) for _, er, _, _ in pairs)
    f_err = max(float(np.max(np.abs(np.asarray(f, np.float64) - fr)))
                for _, _, f, fr in pairs)
    f_scale = max(float(np.max(np.abs(fr))) for _, _, _, fr in pairs)
    return {"energy_gap": e_err / e_scale, "force_gap": f_err / f_scale}
