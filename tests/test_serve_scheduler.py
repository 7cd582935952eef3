"""The serve scheduler and slot pools (DESIGN.md §10): deadline expiry,
priority ordering, FIFO discipline, bucket-selection boundaries, and the
counter-proof that a small-bucket request never triggers a larger bucket's
compile."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.gaunt_ff import gaunt_mace_ff
from repro.models.equivariant import MaceGaunt
from repro.serve.engine import EquivariantRequest, EquivariantServeEngine
from repro.serve.metrics import ServeMetrics, percentile
from repro.serve.pools import BucketedPools, BucketSpec, default_buckets
from repro.serve.scheduler import (AdmissionQueue, REASON_DEADLINE,
                                   REASON_INVALID, Scheduler)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@dataclasses.dataclass
class _Req:
    rid: int = 0
    priority: int = 0
    deadline: float | None = None
    invalid: str | None = None   # stub validation verdict
    done: bool = False
    rejected: bool = False
    reject_reason: str | None = None


class _StubEngine:
    """Capacity-limited engine stub: records admission order, completes
    every active request per step."""

    def __init__(self, capacity: int = 1):
        self.capacity = capacity
        self.active: list[_Req] = []
        self.admitted_order: list[int] = []
        self.metrics = None

    def validate(self, req):
        return (REASON_INVALID, req.invalid) if req.invalid else None

    def try_admit(self, req) -> bool:
        if len(self.active) >= self.capacity:
            return False
        self.active.append(req)
        self.admitted_order.append(req.rid)
        return True

    def has_active(self) -> bool:
        return bool(self.active)

    def step(self, overlap=None):
        stepping, self.active = self.active, []
        if overlap is not None:
            overlap()
        for r in stepping:
            r.done = True


# --------------------------------------------------------------- the queue


def test_queue_priority_order_fifo_within_class():
    clock = FakeClock()
    q = AdmissionQueue(clock)
    for rid, prio in [(0, 1), (1, 0), (2, 1), (3, 0), (4, 2)]:
        q.submit(_Req(rid=rid, priority=prio))
    # priority ascending, submission order within each priority class
    assert [q.pop().rid for _ in range(len(q))] == [1, 3, 0, 2, 4]


def test_queue_expire_removes_only_stale():
    clock = FakeClock()
    q = AdmissionQueue(clock)
    q.submit(_Req(rid=0, deadline=1.0))
    q.submit(_Req(rid=1, deadline=5.0))
    q.submit(_Req(rid=2))                  # no deadline: never expires
    clock.advance(2.0)
    assert [r.rid for r in q.expire()] == [0]
    assert len(q) == 2


def test_queue_requeue_preserves_fifo_standing():
    clock = FakeClock()
    q = AdmissionQueue(clock)
    a, b = _Req(rid=0), _Req(rid=1)
    q.submit(a)
    q.submit(b)
    popped = q.pop()
    assert popped is a
    q.requeue(a)                       # blocked, not consumed
    assert q.pop() is a                # still ahead of b
    assert q.pop() is b


# ----------------------------------------------------------- the scheduler


def test_deadline_expired_rejected_with_structured_reason():
    clock = FakeClock()
    eng = _StubEngine(capacity=1)
    sched = Scheduler(eng, clock=clock, metrics=ServeMetrics(clock=clock))
    fresh, stale = _Req(rid=0), _Req(rid=1, deadline=0.5)
    sched.submit(fresh)
    sched.submit(stale)
    clock.advance(1.0)                 # stale's queue wait exceeds deadline
    sched.drain()
    assert fresh.done and not fresh.rejected
    assert stale.rejected and stale.done
    assert stale.reject_reason.startswith(REASON_DEADLINE)
    assert sched.metrics.counters[f"rejected:{REASON_DEADLINE}"] == 1
    assert eng.admitted_order == [0]   # the expired request never admitted


def test_admission_respects_priority_then_fifo():
    eng = _StubEngine(capacity=1)      # serial: admission order observable
    sched = Scheduler(eng, clock=FakeClock())
    reqs = [_Req(rid=0, priority=1), _Req(rid=1, priority=0),
            _Req(rid=2, priority=1), _Req(rid=3, priority=0)]
    sched.run(list(reqs))
    assert all(r.done for r in reqs)
    assert eng.admitted_order == [1, 3, 0, 2]


def test_blocked_request_requeued_without_losing_position():
    eng = _StubEngine(capacity=1)
    sched = Scheduler(eng, clock=FakeClock())
    a, b, c = _Req(rid=0), _Req(rid=1), _Req(rid=2)
    sched.submit(a)
    sched.submit(b)
    assert sched.admit_ready() == 1    # a admitted, b blocked + requeued
    sched.submit(c)
    eng.step()                         # a completes, capacity frees
    sched.drain()
    assert eng.admitted_order == [0, 1, 2]


def test_invalid_requests_rejected_by_engine_validator():
    eng = _StubEngine(capacity=4)
    sched = Scheduler(eng, clock=FakeClock())
    bad = _Req(rid=0, invalid="broken geometry")
    good = _Req(rid=1)
    sched.run([bad, good])
    assert bad.rejected and bad.reject_reason == \
        f"{REASON_INVALID}:broken geometry"
    assert good.done and not good.rejected
    assert eng.admitted_order == [1]


# ------------------------------------------------------------ the metrics


def test_percentile_interpolates():
    assert percentile([], 99) == 0.0
    assert percentile([5.0], 50) == 5.0
    xs = list(range(1, 101))
    assert percentile(xs, 50) == pytest.approx(50.5)
    assert percentile(xs, 99) == pytest.approx(99.01)
    assert percentile(xs, 100) == 100.0


def test_metrics_padding_and_occupancy_gauges():
    m = ServeMetrics(clock=FakeClock())
    m.observe_step("small", active=2, n_slots=4, real_atoms=6,
                   padded_atoms=12, dur_s=0.01)
    m.observe_step("large", active=1, n_slots=4, real_atoms=20,
                   padded_atoms=64, dur_s=0.02)
    assert m.padding_efficiency() == pytest.approx(26 / 76)
    assert m.occupancy_mean() == pytest.approx(3 / 8)
    s = m.summary()
    assert s["steps"] == 2
    assert s["pool:small:padding_efficiency"] == pytest.approx(0.5)
    assert "engine_timing_runs" in s and "conversions" in s


def test_metrics_latency_pipeline():
    clock = FakeClock()
    m = ServeMetrics(clock=clock)
    r = _Req()
    m.observe_submit(r)
    clock.advance(0.5)
    m.observe_admit(r)
    clock.advance(1.5)
    m.observe_complete(r)
    s = m.summary()
    assert s["queue_wait_p50_ms"] == pytest.approx(500.0)
    assert s["latency_p50_ms"] == pytest.approx(2000.0)
    assert s["completed"] == 1


# ----------------------------------------------------------------- buckets


def test_default_buckets_ladder():
    specs = default_buckets(256, n_slots=4)
    assert [s.max_atoms for s in specs] == [64, 128, 256]
    assert [s.name for s in specs] == ["small", "medium", "large"]
    assert all(s.n_slots == 4 for s in specs)
    assert [s.max_atoms for s in default_buckets(4)] == [2, 4]
    assert [s.max_atoms for s in default_buckets(2)] == [2]


def test_duplicate_bucket_sizes_rejected():
    with pytest.raises(ValueError):
        BucketedPools(None, None, [BucketSpec(8, 1), BucketSpec(8, 2)])


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(gaunt_mace_ff, channels=8, n_layers=1, L=1,
                              L_edge=1, n_species=4)
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_bucket_selection_boundaries(small_model):
    """select() routes to the SMALLEST bucket that fits, with exact
    boundary behavior at every bucket edge."""
    model, params = small_model
    pools = BucketedPools(model, params,
                          [BucketSpec(4, 1), BucketSpec(8, 1),
                           BucketSpec(16, 1)])
    assert pools.select(1).spec.max_atoms == 4
    assert pools.select(4).spec.max_atoms == 4    # boundary: exact fit
    assert pools.select(5).spec.max_atoms == 8    # boundary + 1: next bucket
    assert pools.select(8).spec.max_atoms == 8
    assert pools.select(9).spec.max_atoms == 16
    assert pools.select(16).spec.max_atoms == 16
    assert pools.select(17) is None
    assert pools.max_atoms == 16


def _mol(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 4, n),
            (rng.normal(size=(n, 3)) * 1.5).astype(np.float32))


def test_small_requests_never_compile_the_large_bucket(small_model):
    """Counter-proof: a workload that fits the small bucket leaves the
    large bucket's step function UNCOMPILED (its jit cache stays empty) and
    never steps it — bucketing really isolates compilation, it does not
    just relabel slots."""
    model, params = small_model
    eng = EquivariantServeEngine(model, params,
                                 buckets=[(4, 2), (12, 2)])
    small_pool, large_pool = eng.pools.pools
    assert not small_pool.compiled() and not large_pool.compiled()
    reqs = [EquivariantRequest(*_mol(2 + i % 3, seed=i), rid=i)
            for i in range(5)]                      # all <= 4 atoms
    out = eng.run(reqs)
    assert all(r.done and not r.rejected for r in out)
    assert small_pool.compiled() and small_pool.steps_run > 0
    assert not large_pool.compiled(), \
        "a small-bucket workload compiled the large bucket's step"
    assert large_pool.steps_run == 0
    assert "large" not in {k.split(":")[1]
                           for k in eng.metrics.summary() if ":" in k}
    # and the large bucket still works when a large request does arrive
    big = EquivariantRequest(*_mol(10, seed=99), rid=99)
    eng.run([big])
    assert big.done and large_pool.compiled() and large_pool.steps_run == 1


# ------------------------------------------------ spans and warm-up counters

SERVE_SPANS = {"serve.admit", "serve.stage", "serve.dispatch", "serve.block",
               "serve.retire"}


def test_serving_loop_writes_its_spans_into_the_profilers_trace(
        small_model, tmp_path):
    """Under `jax.profiler` the scheduler and the pools write a host span
    for each part of a round, the pool's spans tagged with its bucket."""
    import glob

    from jax.profiler import ProfileData

    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 2)])
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run([EquivariantRequest(*_mol(3, seed=i), rid=i)
                 for i in range(3)])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = [e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("serve.")]
    assert {e.name for e in events} == SERVE_SPANS
    pooled = [dict(e.stats) for e in events if e.name != "serve.admit"]
    assert pooled and all(s == {"pool": "b4"} for s in pooled)


def test_warmed_pools_compile_nothing_while_serving(small_model):
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 2), (8, 1)])
    eng.warmup()
    m = eng.metrics
    assert set(m.warmup_s) == {"b4", "b8"}
    assert all(s > 0 for s in m.warmup_s.values())
    eng.run([EquivariantRequest(*_mol(n, seed=n), rid=n)
             for n in (2, 3, 4, 6, 7)])
    assert m.counters["steps"] > 0 and m.counters["step_compiles"] == 0
    s = m.summary()
    assert s["step_compiles"] == 0
    assert s["warmup_s"] == pytest.approx(sum(m.warmup_s.values()))
    assert s["pool:b8:warmup_s"] == m.warmup_s["b8"]


def test_pool_left_out_of_warmup_counts_its_compile_once(small_model):
    model, params = small_model
    eng = EquivariantServeEngine(model, params, buckets=[(4, 2), (8, 1)])
    eng.pools.pools[0].warmup_compile()     # warm the 4-atom pool alone
    eng.run([EquivariantRequest(*_mol(3, seed=1), rid=1)])
    assert eng.metrics.counters["step_compiles"] == 0
    for rid in (2, 3):
        eng.run([EquivariantRequest(*_mol(6, seed=rid), rid=rid)])
    s = eng.metrics.summary()
    assert s["step_compiles"] == 1
    assert s["pool:b8:step_compiles"] == 1 and s["pool:b4:step_compiles"] == 0
    assert eng.metrics.warmup_s == {}
