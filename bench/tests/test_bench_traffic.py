"""The traffic generator: seeded, with fixed work per seed, molecule-like
geometry, and open-loop latency timed from the due time."""
import json
import os
import time

import numpy as np
import pytest

from bench.harness import traffic as T
from bench.harness.geometry import molecule, neighbour_stats
from bench.harness.serve import CompletionClock, open_loop
from bench.harness.stats import percentile

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "traffic")
ELEMENTS = ["H", "C", "N", "O"]
SEED = 2 ** 31 + 77


def _mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_stream():
    mix = _mix("screen")
    a = T.open_loop(mix, ELEMENTS, SEED, 5.0)
    b = T.open_loop(mix, ELEMENTS, SEED, 5.0)
    c = T.open_loop(mix, ELEMENTS, SEED + 1, 5.0)
    assert len(a) == len(b) == len(c) == round(mix["rate_per_s"] * 5.0)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s
        np.testing.assert_array_equal(x.species, y.species)
        np.testing.assert_array_equal(x.pos, y.pos)
    assert any(not np.array_equal(x.pos, y.pos) for x, y in zip(a, c)
               if len(x.pos) == len(y.pos))


def test_every_seed_gets_the_same_work():
    """Sizes and gaps are one multiset per seed, in another order."""
    mix = _mix("screen")
    a = T.open_loop(mix, ELEMENTS, 1, 10.0)
    b = T.open_loop(mix, ELEMENTS, 2, 10.0)
    assert sorted(len(x.species) for x in a) == sorted(len(x.species) for x in b)
    gaps = lambda s: sorted(np.diff([x.due_s for x in s] + [10.0]))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)
    assert all(0.0 <= x.due_s < 10.0 for x in a)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)


def test_every_block_gets_the_same_work():
    """With ``block_s``, each block of the window holds the same count of
    each size class and the same gaps, so a seed cannot bunch the large
    molecules together."""
    mix = _mix("screen")
    per = int(round(mix["rate_per_s"] * mix["block_s"]))

    def counts(blk):
        return [sum(lo <= len(x.species) <= hi for x in blk)
                for lo, hi, _ in mix["sizes"]]

    for seed in (1, 2):
        a = T.open_loop(mix, ELEMENTS, seed, 4 * mix["block_s"])
        blocks = [a[k * per:(k + 1) * per] for k in range(4)]
        for k, blk in enumerate(blocks):
            assert counts(blk) == counts(blocks[0])
            start = k * mix["block_s"]
            assert all(start <= x.due_s < start + mix["block_s"] for x in blk)
            gaps = np.diff([x.due_s for x in blk] + [start + mix["block_s"]])
            first = np.diff([x.due_s for x in blocks[0]] + [mix["block_s"]])
            np.testing.assert_allclose(sorted(gaps), sorted(first), atol=1e-9)


def test_size_class_shares():
    mix = _mix("screen")
    n = 400
    sizes = np.asarray([len(x.species) for x in
                        T.open_loop(mix, ELEMENTS, SEED, n / mix["rate_per_s"])])
    assert len(sizes) == n
    for lo, hi, share in mix["sizes"]:
        got = np.mean((sizes >= lo) & (sizes <= hi))
        assert abs(got - share) <= 1.0 / n
    assert sizes.min() == mix["sizes"][0][0]
    assert sizes.max() == mix["sizes"][-1][1]


@pytest.mark.parametrize("n,density", [(16, None), (64, None), (128, 0.1)])
def test_molecule_geometry(n, density):
    rng = T.rng_for(SEED, 9)
    sp, pos = molecule(rng, n, [0.5, 0.5], density=density)
    d = np.linalg.norm(pos[None] - pos[:, None], axis=-1) + np.eye(n) * 99
    assert d.min() >= 0.9 - 1e-5
    # every atom but the first sits 1.0-1.5 A from some atom placed before
    near = d.min(axis=1)
    assert np.all(near <= 1.5 + 1e-5)
    assert sp.shape == (n,) and set(sp) <= {0, 1}
    if density is not None:
        radius = (3 * n / (4 * np.pi * density)) ** (1 / 3)
        assert np.linalg.norm(pos - pos.mean(0), axis=1).max() <= 2 * radius


def test_neighbour_density_statistic():
    """Mean neighbours within the cutoff: hand case, then the cells' own
    geometries (the figures PERF.md records)."""
    line = np.asarray([[0, 0, 0], [1, 0, 0], [2, 0, 0], [10, 0, 0]], float)
    mean, closest, share = neighbour_stats(line, 1.5)
    assert mean == pytest.approx((1 + 2 + 1 + 0) / 4)
    assert closest == pytest.approx(1.0)
    assert share == pytest.approx(4 / 12)
    rng = T.rng_for(SEED, 10)
    dense = [neighbour_stats(molecule(rng, 128, [1.0], density=0.1)[1], 5.0)
             for _ in range(3)]
    # a 128-atom cluster at liquid density: tens of neighbours, and well
    # over half of the n^2 pairs beyond 5 A
    assert 30 < np.mean([m for m, _, _ in dense]) < 100
    assert np.mean([s for _, _, s in dense]) < 0.7


def test_lj_labels_match_finite_differences():
    species = np.asarray([[0, 1, 1]])
    pos = np.asarray([[[0, 0, 0], [1.1, 0, 0], [0.2, 1.2, 0.1]]], float)
    eps, sig = np.asarray([0.1, 0.2]), np.asarray([0.8, 0.9])
    E, F = T.lj_labels(species, pos, eps, sig)
    h = 1e-6
    for i in range(3):
        for k in range(3):
            p = pos.copy()
            p[0, i, k] += h
            num = -(T.lj_labels(species, p, eps, sig)[0][0] - E[0]) / h
            assert F[0, i, k] == pytest.approx(num, rel=1e-4, abs=1e-6)


def test_train_batches_rows_differ():
    mix = dict(_mix("train"), batch=3, atoms=8, library=4)
    a = T.train_batches(mix, ELEMENTS, SEED, 3)
    b = T.train_batches(mix, ELEMENTS, SEED, 3)
    rows = np.concatenate([x["pos"].reshape(3, -1) for x in a])
    assert len({r.tobytes() for r in rows}) == 9
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


class _StubEngine:
    """The scheduler protocol over a fake device: each step answers every
    active request after ``service_s``; one step stalls for ``stall_s``."""

    def __init__(self, service_s, stall_s=0.0, stall_at=None):
        self.metrics = CompletionClock(clock=time.perf_counter)
        self.active, self.service_s = [], service_s
        self.stall_s, self.stall_at, self.steps = stall_s, stall_at, 0

    def validate(self, req):
        return None

    def try_admit(self, req):
        if len(self.active) >= 4:
            return False
        self.active.append(req)
        return True

    def has_active(self):
        return bool(self.active)

    def step(self, overlap=None):
        if overlap is not None:
            overlap()
        self.steps += 1
        time.sleep(self.service_s
                   + (self.stall_s if self.steps == self.stall_at else 0.0))
        for r in self.active:
            r.done = True
            self.metrics.observe_complete(r)
        self.active = []


def test_open_loop_latency_counts_a_stall():
    """A stall that delays later submissions shows in the p95, because
    latency runs from the due time and not from the submission."""
    mix = dict(_mix("screen"), sizes=[[3, 4, 1.0]], library=2)
    arrivals = T.open_loop(mix, ELEMENTS, SEED, 1.0, rate=60.0)
    calm = open_loop(_StubEngine(0.002), arrivals, 1.0)
    eng = _StubEngine(0.002, stall_s=0.4, stall_at=5)
    stalled = open_loop(eng, arrivals, 1.0)
    assert calm.failed() == stalled.failed() == 0
    p_calm = percentile(list(calm.latencies_s()), 95)
    p_stall = percentile(list(stalled.latencies_s()), 95)
    assert p_stall > p_calm + 0.1
    assert stalled.lateness_max_s > 0.1
    # timed from submission (ServeMetrics.total_s), the requests that came
    # due during the stall and were submitted after it look fast
    slow_due = sum(x >= 0.2 for x in stalled.latencies_s())
    slow_submit = sum(x >= 0.2 for x in eng.metrics.total_s)
    assert slow_submit <= 4 < slow_due
