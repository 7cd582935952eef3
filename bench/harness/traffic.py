"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests.  Nothing here depends on the model's outputs, so
the parent and the change see the same geometries.

Every seed gets the same work: the same multiset of molecule sizes and of
gaps between arrivals, in a seed-drawn order (within each block of the
window, where the mix names ``block_s``), and fresh geometries.
Geometries come from a seeded library of grown molecules: a request of
``n`` atoms takes the first ``n`` atoms of a library molecule (itself a
grown molecule), turned by a random rotation, with its species drawn anew.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .geometry import molecule

__all__ = ["Arrival", "rng_for", "composition", "open_loop", "library",
           "closed_loop_start", "jitter", "train_batches", "lj_labels",
           "stratified_sizes", "poisson_gaps"]


@dataclasses.dataclass
class Arrival:
    due_s: float          # seconds after the window opens
    species: np.ndarray   # [n] int32
    pos: np.ndarray       # [n, 3] float32


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def composition(mix: dict, elements: list[str]) -> np.ndarray:
    """The mix's element shares, as probabilities over species indices."""
    comp = mix["composition"]
    unknown = set(comp) - set(elements)
    if unknown:
        raise ValueError(f"composition names {sorted(unknown)}, not among "
                         f"the configuration's elements {elements}")
    p = np.asarray([comp.get(e, 0.0) for e in elements], np.float64)
    return p / p.sum()


def _rotations(rng: np.random.Generator, k: int) -> np.ndarray:
    """k uniform random rotation matrices [k, 3, 3]."""
    q, r = np.linalg.qr(rng.normal(size=(k, 3, 3)))
    q = q * np.sign(np.diagonal(r, axis1=1, axis2=2))[:, None, :]
    det = np.linalg.det(q)
    q[:, :, 0] *= det[:, None]
    return q


def library(rng: np.random.Generator, mix: dict, n_atoms: int,
            size: int) -> list[np.ndarray]:
    """``size`` grown molecules of ``n_atoms`` atoms (positions only)."""
    geo = mix["geometry"]
    return [molecule(rng, n_atoms, [1.0], bond=tuple(geo["bond"]),
                     min_dist=geo["min_dist"])[1] for _ in range(size)]


def stratified_sizes(classes, n: int) -> np.ndarray:
    """Sizes for ``n`` requests from ``[[lo, hi, share], ...]``: class
    counts by largest remainder, sizes spread evenly over each class's
    integers.  Unshuffled; the same for every seed."""
    shares = np.asarray([c[2] for c in classes], np.float64)
    raw = shares / shares.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(counts - raw)[: n - counts.sum()]:
        counts[i] += 1
    out = []
    for (lo, hi, _), c in zip(classes, counts):
        k = np.arange(c)
        out.append(lo + np.floor((k + 0.5) / max(c, 1) * (hi - lo + 1)).astype(int))
    return np.concatenate(out)


def poisson_gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """``n`` exponential gaps at ``rate`` (their quantiles, unshuffled),
    scaled to sum to ``seconds``."""
    q = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    return q * (seconds / q.sum())


def open_loop(mix: dict, elements: list[str], seed: int, seconds: float,
              rate: float | None = None) -> list[Arrival]:
    """Poisson arrivals at the mix's rate: exactly round(rate * seconds)
    requests, all due inside the window.  Where the mix names ``block_s``,
    the window is cut into blocks of about that length; the window's sizes
    are dealt out to the blocks in turn, so that every block holds the same
    count of each size class, and every block the same Poisson gaps, in a
    seed-drawn order within it: no seed draws a burstier window than
    another."""
    rate = float(rate if rate is not None else mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 1)
    blocks = 1
    if mix.get("block_s"):
        blocks = max(1, int(round(seconds / float(mix["block_s"]))))
        if n % blocks:
            raise ValueError(f"{n} requests do not fill {blocks} blocks "
                             "alike; choose seconds to match block_s")
    per, span = n // blocks, seconds / blocks
    dealt = stratified_sizes(mix["sizes"], n)   # by class, ascending
    sizes, due = [], []
    for b in range(blocks):
        sizes.append(rng.permutation(dealt[b::blocks]))
        gaps = rng.permutation(poisson_gaps(rate, per, span))
        due.append(b * span + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
    sizes, due = np.concatenate(sizes), np.concatenate(due)
    big = max(c[1] for c in mix["sizes"])
    lib = library(rng, mix, big, mix["library"])
    pick = rng.integers(0, len(lib), n)
    rot = _rotations(rng, n)
    comp = composition(mix, elements)
    out = []
    for k in range(n):
        p = lib[pick[k]][: sizes[k]]
        p = (p - p.mean(0)) @ rot[k].T
        sp = rng.choice(len(comp), size=sizes[k], p=comp).astype(np.int32)
        out.append(Arrival(float(due[k]), sp, p.astype(np.float32)))
    return out


def closed_loop_start(mix: dict, elements: list[str], seed: int):
    """One starting structure per client: [(species, pos), ...]."""
    rng = rng_for(seed, 2)
    comp = composition(mix, elements)
    starts = []
    geo = mix["geometry"]
    for _ in range(mix["clients"]):
        _, pos = molecule(rng, mix["atoms"], [1.0], bond=tuple(geo["bond"]),
                          min_dist=geo["min_dist"],
                          density=mix.get("density"),
                          fragment=mix.get("fragment"))
        sp = rng.choice(len(comp), size=mix["atoms"], p=comp).astype(np.int32)
        starts.append((sp, pos))
    return starts


def jitter(seed: int, client: int, call: int, pos: np.ndarray,
           scale: float) -> np.ndarray:
    """Call ``call`` of ``client``: the start plus seeded Gaussian jitter
    of ``scale`` Angstrom per coordinate."""
    rng = rng_for(seed, 3, client, call)
    return (pos + rng.normal(scale=scale, size=pos.shape)).astype(np.float32)


# ------------------------------------------------------------ training

def lj_labels(species, pos, eps_table, sig_table):
    """Lennard-Jones energies and forces, vectorised over structures:
    species [S, n], pos [S, n, 3] -> (E [S], F [S, n, 3])."""
    n = pos.shape[1]
    diff = pos[:, None, :, :] - pos[:, :, None, :]        # r_j - r_i
    eye = np.eye(n)
    d = np.sqrt(np.sum(diff ** 2, -1) + eye)
    e = eps_table[species]
    s = sig_table[species]
    eps = e[:, :, None] * e[:, None, :]
    sig = 0.5 * (s[:, :, None] + s[:, None, :])
    x6 = (sig / d) ** 6
    off = 1.0 - eye
    E = 0.5 * np.sum(4 * eps * (x6 ** 2 - x6) * off, axis=(1, 2))
    dEdd = 4 * eps * (-12 * x6 ** 2 + 6 * x6) / d * off
    F = np.sum(dEdd[..., None] * diff / d[..., None], axis=2)
    return E, F


def train_batches(mix: dict, elements: list[str], seed: int, steps: int):
    """``steps`` batches of ``mix['batch']`` distinct structures with
    Lennard-Jones labels (per-species parameters drawn from the seed)."""
    rng = rng_for(seed, 4)
    lj = mix["lj"]
    eps_table = rng.uniform(*lj["eps"], len(elements))
    sig_table = rng.uniform(*lj["sigma"], len(elements))
    b, n = mix["batch"], mix["atoms"]
    lib = library(rng, mix, n, mix["library"])
    comp = composition(mix, elements)
    total = b * steps
    pick = rng.integers(0, len(lib), total)
    rot = _rotations(rng, total)
    pos = np.stack([lib[i] for i in pick]) @ np.transpose(rot, (0, 2, 1))
    pos += rng.normal(scale=mix["jitter"], size=pos.shape)
    species = rng.choice(len(comp), size=(total, n), p=comp).astype(np.int32)
    E, F = lj_labels(species, pos, eps_table, sig_table)
    cut = lambda a: a.reshape(steps, b, *a.shape[1:])  # noqa: E731
    return [{"species": s, "pos": p.astype(np.float32),
             "energy": e.astype(np.float32), "forces": f.astype(np.float32)}
            for s, p, e, f in zip(cut(species), cut(pos), cut(E), cut(F))]
