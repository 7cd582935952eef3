"""The Gaunt engine's central constant cache (see DESIGN.md §2.4).

Every precomputed tensor used by any Gaunt backend lives behind exactly one
lru-cached builder in this module: SH<->Fourier conversion tensors (dense and
packed), packed-layout gather maps, the eSCN m=0 Gaunt coupling, the
Wigner-recursion CG blocks, and the fused collocation matrices
T1/T2/P.  This replaces the per-module ``lru_cache`` constellations that used
to live in ``core/gaunt.py``, ``core/conv.py`` and ``kernels/gaunt_fused.py``.

All values are **numpy** arrays: a jnp constant created inside one jit trace
would leak that trace's tracer into every later trace served from the cache.
Consumers wrap with ``jnp.asarray`` at use time (free — XLA hoists constants).

``cache_stats()`` exposes hit/miss counters so tests can assert that plans
reuse constants instead of rebuilding them.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

from . import fourier as _fx
from .irreps import idx, num_coeffs
from .so3 import real_clebsch_gordan_block, real_gaunt_tensor, real_sph_harm

__all__ = [
    "y_dense",
    "z_dense",
    "y_packed",
    "z_packed",
    "y_half",
    "z_half",
    "pack_index",
    "escn_coupling",
    "cg_11_blocks",
    "fused_matrices",
    "chain_matrices",
    "chain_sample_sh",
    "chain_sample_grid",
    "chain_project_sh",
    "chain_project_grid",
    "chain_l0",
    "quad_sample_sh",
    "quad_project_sh",
    "quad_sample_fourier",
    "quad_project_fourier",
    "gaunt_dense",
    "cache_stats",
    "clear_all",
]


# --------------------------------------------------------------------------
# SH <-> 2D Fourier conversion tensors
# --------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _y_raw(L: int) -> np.ndarray:
    return _fx.sh_to_fourier_dense(L)


@lru_cache(maxsize=None)
def _z_raw(Lf: int, Lout: int) -> np.ndarray:
    return _fx.fourier_to_sh_dense(Lf, Lout)


@lru_cache(maxsize=None)
def y_dense(L: int, cdtype: str = "complex64") -> np.ndarray:
    """sh->Fourier tensor [(L+1)^2, 2L+1 (u), 2L+1 (v)], centered."""
    return _y_raw(L).astype(cdtype)


@lru_cache(maxsize=None)
def z_dense(Lf: int, Lout: int, cdtype: str = "complex64") -> np.ndarray:
    """Fourier->sh tensor [2Lf+1, 2Lf+1, (Lout+1)^2], centered."""
    return _z_raw(Lf, Lout).astype(cdtype)


@lru_cache(maxsize=None)
def y_packed(L: int, cdtype: str = "complex64") -> tuple[np.ndarray, np.ndarray]:
    """Packed (per-|m| block-sparse) sh->Fourier matrices (yp, yn)."""
    yp, yn = _fx.sh_to_fourier_packed(L, y=_y_raw(L))
    return yp.astype(cdtype), yn.astype(cdtype)


@lru_cache(maxsize=None)
def z_packed(Lf: int, Lout: int, cdtype: str = "complex64") -> tuple[np.ndarray, np.ndarray]:
    """Packed Fourier->sh matrices (zp, zn)."""
    zp, zn = _fx.fourier_to_sh_packed(Lf, Lout, z=_z_raw(Lf, Lout))
    return zp.astype(cdtype), zn.astype(cdtype)


@lru_cache(maxsize=None)
def y_half(L: int, cdtype: str = "complex64") -> np.ndarray:
    """Half (Hermitian / real-input) sh->Fourier tensor: v >= 0 columns only."""
    return _fx.sh_to_fourier_half(L, y=_y_raw(L)).astype(cdtype)


@lru_cache(maxsize=None)
def z_half(Lf: int, Lout: int, cdtype: str = "complex64") -> np.ndarray:
    """Half Fourier->sh tensor with the v < 0 columns conjugate-folded in."""
    return _fx.fourier_to_sh_half(Lf, Lout, z=_z_raw(Lf, Lout)).astype(cdtype)


@lru_cache(maxsize=None)
def pack_index(L: int) -> tuple[np.ndarray, np.ndarray]:
    """Gather map packed[plane, mm, l] <- flat idx(l, +-mm); mask for valid."""
    gidx = np.zeros((2, L + 1, L + 1), dtype=np.int32)
    mask = np.zeros((2, L + 1, L + 1), dtype=np.float32)
    for mm in range(L + 1):
        for l in range(mm, L + 1):
            gidx[0, mm, l] = l * l + l + mm
            mask[0, mm, l] = 1.0
            if mm > 0:
                gidx[1, mm, l] = l * l + l - mm
                mask[1, mm, l] = 1.0
    return gidx, mask


# --------------------------------------------------------------------------
# eSCN rotation-aligned path constants
# --------------------------------------------------------------------------


@lru_cache(maxsize=None)
def escn_coupling(L1: int, L2: int, Lout: int, dtype: str = "float32") -> np.ndarray:
    """C [L2+1, (L1+1)^2, (Lout+1)^2]: the Gaunt product with the aligned filter.

    In the frame whose zenith is the edge, Y(e_z) has only m=0 coefficients,
    S_{l,0}(e_z) = sqrt((2l+1)/4pi); slice l is the real Gaunt tensor at the
    filter column idx(l, 0) times that value.  So for per-degree filter
    weights w, x (x)_Gaunt (w . Y(e_z)) = einsum('i,l,lik->k', x, w, C), and
    with no weights it is x @ C.sum(0).  Gaunt selection keeps it
    m-conserving: C[l, i, k] != 0 only where i and k have the same m.
    """
    G = gaunt_dense(L1, L2, Lout, "float64")
    C = np.stack([G[:, idx(l, 0), :] * math.sqrt((2 * l + 1) / (4 * math.pi))
                  for l in range(L2 + 1)], axis=0)
    return C.astype(dtype)


@lru_cache(maxsize=None)
def cg_11_blocks(L: int) -> tuple[np.ndarray, ...]:
    """CG blocks C_{(l-1,1)->l} for the Wigner-from-rotmat recursion
    (float64; the recursion casts them to the rotation's dtype)."""
    return tuple(real_clebsch_gordan_block(l - 1, 1, l) for l in range(2, L + 1))


# --------------------------------------------------------------------------
# fused collocation (sample-multiply-project) matrices — pairwise and n-way
# chain forms share one set of builders (DESIGN.md §3.4 / §6.4)
# --------------------------------------------------------------------------


def _chain_grid_angles(Ltot: int) -> tuple[int, np.ndarray]:
    """(N, angles) of the alias-free product grid for total degree Ltot.

    A product of bandlimited spherical functions with degrees summing to
    Ltot is bandlimited at Ltot on the torus double cover; N = 2*Ltot + 2
    (> 2*Ltot + 1 and even) samples it alias-free.
    """
    N = 2 * Ltot + 2
    return N, 2 * math.pi * np.arange(N) / N


@lru_cache(maxsize=None)
def chain_sample_sh(L: int, Ltot: int) -> np.ndarray:
    """T [(L+1)^2, G]: real SH of degree <= L sampled on the degree-Ltot
    product grid (float64, unpadded) — the per-operand sampling matrix of
    the chain collocation kernel."""
    N, t = _chain_grid_angles(Ltot)
    tt, pp = np.meshgrid(t, t, indexing="ij")
    xyz = np.stack([np.sin(tt) * np.cos(pp), np.sin(tt) * np.sin(pp), np.cos(tt)], -1)
    S = real_sph_harm(L, xyz.reshape(-1, 3))  # [G, (L+1)^2]
    return S.T.copy()


@lru_cache(maxsize=None)
def chain_sample_grid(L: int, Ltot: int) -> np.ndarray:
    """T' [2*(2L+1)*(L+1), G]: Fourier-resident entry sampling matrix.

    A resident operand arrives as its Hermitian *half* coefficient grid
    F [2L+1 (u), L+1 (v >= 0)]; its real spatial samples on the product grid
    are  V[g] = Re( sum_{u, v>=0} c_v F[u,v] e^{i(u t_g + v p_g)} )  with
    c_0 = 1, c_v = 2 (the v < 0 half is the conjugate mirror).  Stacking the
    grid as the real vector [Re F; Im F] makes this one REAL matmul, so
    resident operands enter the chain kernel as grids — no SH data, no
    sh_to_fourier, the sampling matmul just uses this matrix instead of
    `chain_sample_sh`.
    """
    N, t = _chain_grid_angles(Ltot)
    us = np.arange(-L, L + 1)
    vs = np.arange(0, L + 1)
    Et = np.exp(1j * np.outer(us, t))          # [2L+1, N]
    Ep = np.exp(1j * np.outer(vs, t))          # [L+1, N]
    c = np.where(vs == 0, 1.0, 2.0)
    E = np.einsum("ua,vb,v->uvab", Et, Ep, c).reshape((2 * L + 1) * (L + 1), N * N)
    return np.concatenate([E.real, -E.imag], axis=0)


@lru_cache(maxsize=None)
def chain_project_sh(Ltot: int, Lout: int) -> np.ndarray:
    """P [G, (Lout+1)^2]: product-grid samples -> SH degrees <= Lout.

    P[g, k] = Re((1/G) sum_{u,v} e^{-i(u t_g + v p_g)} z^k_{u,v}) — the
    discrete projection equals the convolution-theorem result to machine
    precision because the sampled product is alias-free (float64, unpadded).
    """
    N, t = _chain_grid_angles(Ltot)
    z = _z_raw(Ltot, Lout)  # [2Lt+1, 2Lt+1, dout] complex
    us = np.arange(-Ltot, Ltot + 1)
    Et = np.exp(-1j * np.outer(t, us))  # [N, 2Lt+1]
    P = np.einsum("au,bv,uvk->abk", Et, Et, z).real / (N * N)
    return P.reshape(N * N, -1)


@lru_cache(maxsize=None)
def chain_project_grid(Ltot: int) -> np.ndarray:
    """P' [G, 2*(2Lt+1)*(Lt+1)]: samples -> real-stacked half product grid.

    F[u,v] = (1/G) sum_g V[g] e^{-i(u t_g + v p_g)} for v >= 0; the output
    stacks [Re F; Im F] so a 'fourier' chain exit is one real matmul whose
    result reassembles into the resident half grid outside the kernel.
    """
    N, t = _chain_grid_angles(Ltot)
    us = np.arange(-Ltot, Ltot + 1)
    vs = np.arange(0, Ltot + 1)
    Et = np.exp(-1j * np.outer(t, us))          # [N, 2Lt+1]
    Ep = np.exp(-1j * np.outer(t, vs))          # [N, Lt+1]
    E = np.einsum("au,bv->abuv", Et, Ep).reshape(N * N, -1) / (N * N)
    return np.concatenate([E.real, E.imag], axis=1)


@lru_cache(maxsize=None)
def chain_matrices(Ls: tuple, Lout: int, entries: tuple = None,
                   out_entry: str = "sh", pad_lanes: bool = True,
                   dtype: str = "float32"):
    """Chain collocation matrices ((T_1..T_n), P) for  x1 (x) ... (x) xn.

    entries: per-operand 'sh' (packed SH vector, T from `chain_sample_sh`)
    or 'grid' (Fourier-resident real-stacked half grid, `chain_sample_grid`);
    out_entry: 'sh' projects to degrees <= Lout, 'grid' returns the
    real-stacked half product grid (requires Lout == sum(Ls)).  When
    ``pad_lanes``, G rounds up to a multiple of 128 (zero sample columns /
    zero projection rows — inert, keeps the TPU MXU lane-aligned).

    ``dtype`` is the *storage* dtype of the returned matrices; 'bfloat16'
    works through numpy via the ml_dtypes registration that jax ships (the
    float64 intermediates round once, at the very end).  Mixed-precision
    callers request T at the storage dtype and P at the accumulation dtype
    (two cache entries — see kernels/gaunt_fused.py).
    """
    Ls = tuple(int(L) for L in Ls)
    Ltot = sum(Ls)
    entries = ("sh",) * len(Ls) if entries is None else tuple(entries)
    if len(entries) != len(Ls) or any(e not in ("sh", "grid") for e in entries):
        raise ValueError(f"entries must be {len(Ls)} of 'sh'|'grid', got {entries!r}")
    Ts = [chain_sample_sh(L, Ltot) if e == "sh" else chain_sample_grid(L, Ltot)
          for L, e in zip(Ls, entries)]
    if out_entry == "sh":
        P = chain_project_sh(Ltot, Lout)
    elif out_entry == "grid":
        if Lout != Ltot:
            raise ValueError(f"out_entry='grid' keeps the full product grid "
                             f"(L={Ltot}); got Lout={Lout}")
        P = chain_project_grid(Ltot)
    else:
        raise ValueError(f"unknown out_entry {out_entry!r} (expected 'sh'|'grid')")
    if pad_lanes:
        G = Ts[0].shape[1]
        Gp = ((G + 127) // 128) * 128
        Ts = [np.pad(T, [(0, 0), (0, Gp - G)]) for T in Ts]
        P = np.pad(P, [(0, Gp - G), (0, 0)])
    return tuple(T.astype(dtype) for T in Ts), P.astype(dtype)


@lru_cache(maxsize=None)
def fused_matrices(L1: int, L2: int, Lout: int, pad_lanes: bool = True,
                   dtype: str = "float32"):
    """Pairwise collocation matrices (T1 [d1,G], T2 [d2,G], P [G,dout]) —
    the n=2 special case of `chain_matrices` (see DESIGN.md §3.4), at the
    requested storage dtype (both T and P; mixed-precision callers that
    want f32 P call `chain_matrices` twice instead)."""
    (T1, T2), P = chain_matrices((L1, L2), Lout, ("sh", "sh"), "sh",
                                 pad_lanes=pad_lanes, dtype=dtype)
    return T1, T2, P


@lru_cache(maxsize=None)
def chain_l0(Ls: tuple, entries: tuple = None) -> np.ndarray:
    """C [d_1, ..., d_n] float64: the l = 0 coefficient of an n-way product
    as a multilinear form over the operands,

        s = einsum('...a,...b,...,ab...->...', x_1, ..., x_n, C),

    built by contracting the chain sampling matrices against the l = 0
    projection column of the alias-free product grid — exact.  This is how
    a gate-fused chain obtains its per-row gate scalars *before* dispatch:
    the fused kernels cannot compute the (channel-mixing) gate MLP on the
    blocked product grid, but the scalars only need the product's l = 0
    component, which is this cheap d^n-sized contraction away.  'grid'
    entries index the real-stacked half-grid layout of `chain_sample_grid`.
    """
    Ls = tuple(int(L) for L in Ls)
    Ltot = sum(Ls)
    entries = ("sh",) * len(Ls) if entries is None else tuple(entries)
    Ts = [chain_sample_sh(L, Ltot) if e == "sh" else chain_sample_grid(L, Ltot)
          for L, e in zip(Ls, entries)]
    p0 = chain_project_sh(Ltot, 0)[:, 0]
    letters = "abcdefghij"[: len(Ls)]
    expr = ",".join(c + "z" for c in letters) + ",z->" + letters
    return np.einsum(expr, *Ts, p0, optimize=True)


# --------------------------------------------------------------------------
# S^2 quadrature matrices (Gauss-Legendre x equispaced phi, DESIGN.md §6.5)
# --------------------------------------------------------------------------


@lru_cache(maxsize=None)
def quad_sample_sh(L: int, n_theta: int, n_phi: int) -> np.ndarray:
    """A [(L+1)^2, G]: SH coefficients -> quadrature-grid samples (float64)."""
    return _fx.s2quad_sample_sh(L, n_theta, n_phi)


@lru_cache(maxsize=None)
def quad_project_sh(Lout: int, n_theta: int, n_phi: int) -> np.ndarray:
    """P [G, (Lout+1)^2]: weighted quadrature projection back onto SH."""
    return _fx.s2quad_project_sh(Lout, n_theta, n_phi)


@lru_cache(maxsize=None)
def quad_sample_fourier(L: int, n_theta: int, n_phi: int) -> np.ndarray:
    """M [2*(2L+1)*(L+1), G]: real-stacked half grid -> quadrature samples."""
    return _fx.s2quad_sample_fourier(L, n_theta, n_phi)


@lru_cache(maxsize=None)
def quad_project_fourier(L: int, n_theta: int, n_phi: int) -> np.ndarray:
    """Z [G, 2L+1, L+1] complex128: quadrature samples -> half product grid."""
    return _fx.s2quad_project_fourier(L, n_theta, n_phi)


@lru_cache(maxsize=None)
def gaunt_dense(L1: int, L2: int, Lout: int, dtype: str = "float32") -> np.ndarray:
    """The exact dense real-Gaunt tensor [(L1+1)^2, (L2+1)^2, (Lout+1)^2]."""
    return real_gaunt_tensor(L1, L2, Lout).astype(dtype)


# --------------------------------------------------------------------------
# introspection
# --------------------------------------------------------------------------

_CACHED = (
    _y_raw, _z_raw, y_dense, z_dense, y_packed, z_packed, y_half, z_half,
    pack_index, escn_coupling, cg_11_blocks, fused_matrices,
    chain_matrices, chain_sample_sh, chain_sample_grid, chain_project_sh,
    chain_project_grid, chain_l0, quad_sample_sh, quad_project_sh,
    quad_sample_fourier, quad_project_fourier, gaunt_dense,
)


def cache_stats() -> dict[str, tuple[int, int, int]]:
    """{builder name: (hits, misses, currsize)} over every cached builder."""
    return {f.__name__: (ci.hits, ci.misses, ci.currsize)
            for f in _CACHED for ci in (f.cache_info(),)}


def clear_all() -> None:
    """Drop every cached constant (tests / memory pressure)."""
    for f in _CACHED:
        f.cache_clear()
