"""Seeded molecule-like geometries.

A structure grows atom by atom: each new atom bonds to an existing atom
with fewer than four bonds, at a bond length drawn from [bond_min,
bond_max] Angstrom, in a random direction, and is kept only if no other
atom lies closer than ``min_dist``.  With
``density`` set, every atom also has to lie inside the sphere that holds
``n`` atoms at that many atoms per cubic Angstrom (a cluster cut from a
liquid), optionally grown from several seeds at once.  Species are drawn
from the mix's composition.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["molecule", "neighbour_stats"]

MAX_BONDS = 4


def molecule(rng: np.random.Generator, n: int, composition, bond=(1.0, 1.5),
             min_dist: float = 0.9, density: float | None = None,
             fragment: int | None = None, batch: int = 32,
             max_rounds: int = 200):
    """-> (species [n] int32, pos [n, 3] float32), centred on the origin.

    ``composition``: probabilities over species indices.  ``fragment``
    (with ``density``): the cluster grows from n / fragment seed atoms
    spread uniformly over the sphere, as a liquid of molecules of about
    that many atoms.  Candidates for each atom are drawn ``batch`` at a
    time and the first that fits is kept."""
    radius = (np.inf if density is None
              else (3.0 * n / (4.0 * math.pi * density)) ** (1.0 / 3.0))
    pos = np.zeros((n, 3))
    bonds = np.zeros(n, np.int64)
    seeds = 1 if fragment is None else max(1, n // fragment)
    k = 1
    while k < seeds:
        cand = rng.uniform(-radius, radius, size=3)
        if (np.linalg.norm(cand) <= radius and np.min(np.linalg.norm(
                pos[:k] - cand, axis=1)) >= 2 * bond[1]):
            pos[k] = cand
            k += 1
    for k in range(seeds, n):
        for _ in range(max_rounds):
            free = np.flatnonzero(bonds[:k] < MAX_BONDS)
            parent = rng.choice(free, size=batch)
            v = rng.normal(size=(batch, 3))
            v *= rng.uniform(*bond, size=(batch, 1)) / np.linalg.norm(
                v, axis=1, keepdims=True)
            cand = pos[parent] + v
            d = np.linalg.norm(cand[:, None, :] - pos[None, :k], axis=-1)
            ok = (d.min(1) >= min_dist) & (np.linalg.norm(cand, axis=1)
                                          <= radius)
            if ok.any():
                j = int(np.argmax(ok))
                pos[k] = cand[j]
                bonds[parent[j]] += 1
                bonds[k] = 1
                break
        else:
            raise RuntimeError(f"could not place atom {k} of {n}")
    species = rng.choice(len(composition), size=n, p=np.asarray(composition))
    return species.astype(np.int32), (pos - pos.mean(0)).astype(np.float32)


def neighbour_stats(pos: np.ndarray, cutoff: float):
    """(mean neighbours within ``cutoff`` per atom, closest pair distance,
    share of the n*(n-1) ordered pairs that lie within the cutoff)."""
    n = len(pos)
    d = np.linalg.norm(pos[None] - pos[:, None], axis=-1) + np.eye(n) * 1e9
    within = d < cutoff
    return (float(within.sum(1).mean()), float(d.min()),
            float(within.sum() / max(1, n * (n - 1))))
