"""Equivariant Convolution (paper §3.3, class 2): x_i (x)_Gaunt Y(r_ij).

Two paths, tested equal:

general : evaluate the SH filter Y(r_hat) directly and run the Gaunt TP.

escn    : Passaro & Zitnick insight adapted to our z-up convention —
          rotate the frame so the edge lands on the zenith; the filter then
          has only m = 0 components,  S_{l,m}(e_z) = delta_{m0} sqrt((2l+1)/4pi),
          so the Gaunt product with it is a fixed real linear map of the
          rotated coefficients, m-conserving and precontracted once per
          degree triple (`constants.escn_coupling`): one small real matmul
          per row.  out = D^T [ (D x) (x)_Gaunt Y(e_z) ].

`so2_conv` is the learned counterpart in the same edge frame (eSCN's SO(2)
convolution, as EquiformerV2 uses it): of the rotated coefficients it keeps
the orders |m| <= M (`reduced_rows`) and applies one linear map per m that
pairs +m with -m.

Wigner rotations are built *differentiably* from the rotation matrix by the
CG intertwiner recursion  D^l = C^T (D^{l-1} (x) D^1) C  — no Euler angles on
the hot path (TPU adaptation; eSCN's CUDA code uses host-precomputed Wigner
matrices instead).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import constants as _const

__all__ = [
    "align_rotation",
    "wigner_blocks_from_rotmat",
    "apply_wigner_blocks",
    "WignerBlocks",
    "reduced_rows",
    "so2_conv",
    "EquivariantConv",
]


def align_rotation(rhat):
    """[..., 3] unit vectors -> rotation matrices R with R @ rhat = e_z.

    Differentiable away from the (measure-zero) frame-switch boundary.
    """
    r = rhat / jnp.linalg.norm(rhat, axis=-1, keepdims=True)
    ex = jnp.broadcast_to(jnp.array([1.0, 0.0, 0.0], dtype=r.dtype), r.shape)
    ez = jnp.broadcast_to(jnp.array([0.0, 0.0, 1.0], dtype=r.dtype), r.shape)
    use_z = (jnp.abs(r[..., 0:1]) > 0.9).astype(r.dtype)
    u = use_z * ez + (1 - use_z) * ex
    b1 = jnp.cross(u, r)
    b1 = b1 / jnp.linalg.norm(b1, axis=-1, keepdims=True)
    b2 = jnp.cross(r, b1)
    return jnp.stack([b1, b2, r], axis=-2)  # rows


def wigner_blocks_from_rotmat(L: int, R):
    """Real Wigner-D blocks [D^0, ..., D^L] for rotation matrices R [..., 3, 3].

    D^1 = P R P^T with P the (x,y,z) -> (m=-1,0,1)=(y,z,x) reordering;
    D^l = C^T (D^{l-1} (x) D^1) C  (orthogonality of the real CG block).
    """
    shp = R.shape[:-2]
    Ds = [jnp.ones(shp + (1, 1), dtype=R.dtype)]
    if L == 0:
        return Ds
    P = jnp.asarray(
        np.array([[0, 1, 0], [0, 0, 1], [1, 0, 0]], dtype=np.float32), dtype=R.dtype
    )  # row m=-1 <- y, m=0 <- z, m=1 <- x
    D1 = jnp.einsum("ai,...ij,bj->...ab", P, R, P)
    Ds.append(D1)
    for l in range(2, L + 1):
        C = jnp.asarray(_const.cg_11_blocks(L)[l - 2], dtype=R.dtype)
        Dl = jnp.einsum(
            "ijk,...ia,...jb,abm->...km", C, Ds[l - 1], D1, C
        )
        Ds.append(Dl)
    return Ds


def apply_wigner_blocks(Ds, x, transpose: bool = False):
    """Apply block-diagonal Wigner rotation to packed features x [..., (L+1)^2]."""
    outs = []
    for l, D in enumerate(Ds):
        blk = x[..., l * l : (l + 1) ** 2]
        eq = "...ji,...j->...i" if transpose else "...ij,...j->...i"
        outs.append(jnp.einsum(eq, D, blk))
    return jnp.concatenate(outs, axis=-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class WignerBlocks:
    """Precomputed rotation-aligned geometry for the eSCN conv path.

    Holds the Wigner-D blocks [D^0, ..., D^L] built from `align_rotation` of
    a fixed edge geometry — the analogue of `EquivariantConv.filter_rep` for
    the rotation-aligned backend: edge geometry is layer-constant in a model
    stack, so the alignment rotation and the CG Wigner recursion run ONCE per
    geometry instead of once per layer.  A pytree (the blocks are the leaves),
    so it flows through jit/vmap/grad and the engine's batched bucket layout
    (each block is a [..., 2l+1, 2l+1] row-parallel leaf).
    """

    blocks: tuple

    @property
    def L(self) -> int:
        return len(self.blocks) - 1

    def tree_flatten(self):
        return tuple(self.blocks), len(self.blocks)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children))

    @classmethod
    def from_rhat(cls, rhat, L: int) -> "WignerBlocks":
        R = align_rotation(rhat.astype(jnp.promote_types(rhat.dtype, jnp.float32)))
        return cls(tuple(wigner_blocks_from_rotmat(L, R)))


def reduced_rows(L: int, M: int) -> np.ndarray:
    """Packed indices l*l + l + m of the edge frame's rows with |m| <= M, in
    the order the SO(2) convolutions read them: m=0 for l=0..L, then for
    each m=1..M, +m for l=m..L and -m for l=m..L."""
    rows = [l * l + l for l in range(L + 1)]
    for m in range(1, M + 1):
        rows += [l * l + l + m for l in range(m, L + 1)]
        rows += [l * l + l - m for l in range(m, L + 1)]
    return np.asarray(rows, np.int32)


def so2_conv(p, u, L: int, M: int, n_out: int, extra: int = 0, rad=None):
    """The SO(2) convolution of edge-frame features u [..., R, Ci] (rows as
    `reduced_rows`).  ``rad`` [..., sum_m (L-m+1) Ci] scales the inputs of
    each order m (shared by +m and -m).  -> (y [..., R, n_out],
    extra invariant outputs [..., extra])."""
    ci = u.shape[-1]
    lead = u.shape[:-2]
    n0 = L + 1
    x0 = u[..., :n0, :].reshape(*lead, n0 * ci)
    if rad is not None:
        x0 = x0 * rad[..., :n0 * ci]
    y0 = x0 @ p["w0"] + p["b0"]
    ex, pieces = y0[..., :extra], [y0[..., extra:].reshape(*lead, n0, n_out)]
    row, off = n0, n0 * ci
    for m in range(1, M + 1):
        nm = L - m + 1
        xp = u[..., row:row + nm, :].reshape(*lead, nm * ci)
        xm = u[..., row + nm:row + 2 * nm, :].reshape(*lead, nm * ci)
        if rad is not None:
            r = rad[..., off:off + nm * ci]
            xp, xm = xp * r, xm * r
        yp, ym = xp @ p[f"w{m}"], xm @ p[f"w{m}"]
        half = nm * n_out
        pieces.append((yp[..., :half] - ym[..., half:]).reshape(*lead, nm, n_out))
        pieces.append((ym[..., :half] + yp[..., half:]).reshape(*lead, nm, n_out))
        row, off = row + 2 * nm, off + nm * ci
    return jnp.concatenate(pieces, axis=-2), ex


class EquivariantConv:
    """Gaunt-accelerated equivariant convolution  (x (x) Y(rhat)) with the
    paper's w_{l1} w_{l2} w_l weight reparameterization.

    Thin wrapper over the unified engine (kind='conv_filter'), routed through
    a batched plan: the edge leading dims ([n, n, C] in the models) are
    flattened to one row axis and executed as a single fused invocation, with
    optional operand-buffer donation (`donate`) and sharded dispatch over the
    mesh's data axes (`shard_spec`, see engine.ShardSpec / DESIGN.md §5).

    method='escn' -> the 'escn_aligned' backend (rotation-alignment sparsity,
    default); method='general' -> a generic pairwise backend with the SH
    filter materialized; method='auto' -> engine selection.  `backend` pins
    any registered backend directly.

    Fourier-resident filters (DESIGN.md §6): when the edge geometry is fixed
    across several products (a layer stack over one graph), materialize the
    filter ONCE with :meth:`filter_rep` and pass the resulting Rep instead of
    ``rhat`` — the call routes through a Fourier-boundary pairwise plan that
    skips the filter's SH->Fourier conversion on every reuse.
    """

    def __init__(self, L1: int, L2: int, Lout: int | None = None, method: str = "escn",
                 cdtype=jnp.complex64, rdtype=jnp.float32,
                 backend: str | None = None, batch_hint: int | None = None,
                 tune: str = "heuristic", donate: bool = False,
                 shard_spec=None):
        from . import engine as _engine

        self.L1, self.L2 = L1, L2
        self.Lout = L1 + L2 if Lout is None else Lout
        self.method = method
        self.cdtype, self.rdtype = cdtype, rdtype
        dtype = _engine._dtype_str(cdtype)
        if backend is None:
            if method == "escn":
                backend = "escn_aligned"
            elif method == "general":
                backend = "direct" if max(L1, L2) <= 4 else "fft"
            elif method == "auto":
                backend = None
            else:
                raise ValueError(f"unknown method {method!r}")
        self._bplan = _engine.plan_batch(
            [_engine.BatchItem(L1=L1, L2=L2, Lout=self.Lout, size=batch_hint)],
            kind="conv_filter", dtype=dtype, backend=backend, tune=tune,
            donate=donate, shard_spec=shard_spec,
        )
        self._plan = self._bplan.buckets[0].plan
        self.backend = self._plan.backend
        self._donate, self._shard_spec = donate, shard_spec
        self._tune = tune
        self._resident_plan = None
        self._resident_bplan = None
        self._geom_bplan = None

    @property
    def plan(self):
        return self._plan

    @property
    def batched_plan(self):
        return self._bplan

    # -- Fourier-resident filters -----------------------------------------

    def _spectral_backend(self) -> str:
        """A Fourier-boundary-capable backend matching this conv's choice."""
        from .engine import spectral_default

        if self.backend in ("fft", "direct", "packed", "rfft"):
            return self.backend
        return spectral_default(self.L1, self.L2)

    def filter_rep(self, rhat, w2=None):
        """Materialize Y(rhat) and convert it to a Fourier-resident Rep once.

        ``w2`` (per-degree filter weights [..., L2+1]) must be folded in here
        — a resident operand cannot take per-degree weights downstream."""
        from .gaunt import expand_degree_weights
        from .rep import Rep
        from .so3 import real_sph_harm_jax

        filt = real_sph_harm_jax(self.L2, rhat)
        if w2 is not None:
            filt = filt * expand_degree_weights(w2, self.L2).astype(filt.dtype)
        conversion = "half" if self._spectral_backend() == "rfft" else "dense"
        return Rep.from_sh(filt, self.L2).to_fourier(conversion, self.cdtype)

    def geometry_rep(self, rhat) -> "WignerBlocks":
        """Precompute the rotation-aligned geometry (eSCN path) ONCE.

        `align_rotation` + the CG Wigner recursion are the dominant per-call
        setup of the 'escn_aligned' backend; edge geometry is layer-constant
        in a model stack, so hoist them per geometry and pass the resulting
        :class:`WignerBlocks` in place of ``rhat`` — the analogue of
        :meth:`filter_rep` for the aligned path."""
        if self.backend != "escn_aligned":
            raise ValueError("geometry_rep is the eSCN (rotation-aligned) "
                             f"residency hook; this conv uses {self.backend!r} "
                             "— use filter_rep for the general path")
        return WignerBlocks.from_rhat(rhat, max(self.L1, self.Lout))

    def _resident_batched(self):
        """The Fourier-boundary batched plan (built lazily): same execution
        knobs (donate/shard_spec/tune) as the raw-rhat route, so residency
        and batched/donated/sharded dispatch compose instead of excluding
        each other."""
        from . import engine as _engine

        if self._resident_bplan is None:
            self._resident_bplan = _engine.plan_batch(
                [_engine.BatchItem(
                    L1=self.L1, L2=self.L2, Lout=self.Lout,
                    options=(("boundary", ("sh", "fourier", "sh")),))],
                kind="pairwise", dtype=_engine._dtype_str(self.cdtype),
                backend=self._spectral_backend(), tune=self._tune,
                donate=self._donate, shard_spec=self._shard_spec,
            )
        return self._resident_bplan

    def _geometry_batched(self):
        """The precomputed-Wigner batched plan for WignerBlocks operands."""
        from . import engine as _engine

        if self._geom_bplan is None:
            self._geom_bplan = _engine.plan_batch(
                [_engine.BatchItem(L1=self.L1, L2=self.L2, Lout=self.Lout,
                                   options=(("geometry", "wigner"),))],
                kind="conv_filter", dtype=_engine._dtype_str(self.cdtype),
                backend="escn_aligned", tune=self._tune,
                donate=self._donate, shard_spec=self._shard_spec,
            )
        return self._geom_bplan

    def __call__(self, x, rhat, w1=None, w2=None, w3=None):
        """x [..., (L1+1)^2], rhat [..., 3] (or a resident Rep from
        :meth:`filter_rep`, or WignerBlocks from :meth:`geometry_rep`)
        -> [..., (Lout+1)^2]."""
        from .rep import Rep

        if isinstance(rhat, WignerBlocks):
            out = self._geometry_batched().apply([(x, rhat)],
                                                 weights=[(w1, w2, w3)])[0]
            return out.astype(self.rdtype)
        if isinstance(rhat, Rep):
            from . import engine as _engine

            if w2 is not None:
                raise ValueError("fold w2 into filter_rep(rhat, w2=...) — a "
                                 "resident filter cannot be reweighted")
            if self._donate or self._shard_spec is not None:
                # resident x batched: the boundary-aware bucket flattens the
                # filter's half/dense grid rows like SH rows (DESIGN.md §5/§6)
                out = self._resident_batched().apply(
                    [(x, rhat)], weights=[(w1, None, w3)])[0]
                return out.astype(self.rdtype)
            if self._resident_plan is None:
                self._resident_plan = _engine.plan(
                    self.L1, self.L2, self.Lout, kind="pairwise",
                    backend=self._spectral_backend(),
                    dtype=_engine._dtype_str(self.cdtype),
                    options={"boundary": ("sh", "fourier", "sh")})
            out = self._resident_plan.apply(x, rhat, w1, None, w3)
            return out.astype(self.rdtype)
        out = self._bplan.apply([(x, rhat)], weights=[(w1, w2, w3)])[0]
        return out.astype(self.rdtype)
