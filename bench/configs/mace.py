"""Plain reference of the MACE-style force field that the `mace-*`
configurations run, and the benchmark's own weights for it.

The reference follows the layer equations of the served model and nothing
of its code: features are real spherical-harmonic coefficients per atom and
channel; every Gaunt product is evaluated as what it is, the product of the
two (or nu) spherical functions on an exact Gauss-Legendre x uniform-phi
sphere quadrature, projected back onto degrees <= L.  The edge filter
Y(r_ij) enters through the addition theorem,
sum_m Y_lm(a) Y_lm(b) = (2l+1)/(4 pi) P_l(a.b), so no spherical harmonics of
the edge direction are built.  Per layer:

    h_ij   = silu(bessel(|r_ij|) W1) W2                  [C, L+1] per pair
    m_i    = P_L sum_j mask_ij (sum_l h_ijl x_jl)(s) F_ij(s)   (conv)
    A_i    = mix(m_i) + x_i
    B_i    = P_L prod_k (sum_l w_kl A_il)(s)             (nu-fold product)
    x_i   += gate(mb_mix(B_i))
    E      = sum_i mask_i silu(x_i0 R1) R2

with F_ij(s) = sum_{l<=L_edge} (2l+1)/(4 pi) P_l(rhat_ij . s) and mix,
mb_mix degree-wise channel mixes.  Any orthonormal real basis of each
degree gives the same energy, so the reference's basis need not match the
program's.  Dense n x n pairs, masked by the cutoff and by ``atom_mask``.

``dtype='float32'`` runs every contraction at the highest matmul precision.
The two controls, one precision below a configuration's: ``'bf16x3'``
keeps float32 everywhere but computes every contraction as XLA's three-pass
``high`` precision does (each operand split into a bfloat16 high and low
part, the products of all parts but those with two low parts summed in
float32), the same on every backend; ``'bfloat16'`` holds weights,
features and the quadrature tables in bfloat16 (geometry stays float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_params", "energy", "energy_forces", "loss",
           "quadrature_degree", "gaunt_nnz", "forward_flops"]

RADIAL_HIDDEN = 32   # the served model's radial MLP width (see "assumed")
GATE_HIDDEN = 32     # the served model's gate MLP width


# ---------------------------------------------------------------- weights

def init_params(cfg: dict, key):
    """The benchmark's weights, in the served model's parameter layout.
    Jit this: one call makes every leaf on the device.  ``mb_w`` (the
    per-degree weights of the nu operands) is drawn around 1/nu so that a
    path that ignored them would show."""
    C, L, nu, R = cfg["channels"], cfg["L"], cfg["nu"], cfg["n_radial"]
    n_layers, hidden = cfg["n_layers"], cfg["hidden"]
    ks = iter(jax.random.split(key, 3 + 7 * n_layers))
    nrm = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)  # noqa: E731
    params = {
        "species": nrm((cfg["n_species"], C)) * 0.5,
        "layers": [],
        "readout": {"w1": nrm((C, hidden)) / math.sqrt(C),
                    "w2": nrm((hidden, 1)) / math.sqrt(hidden)},
    }
    for _ in range(n_layers):
        params["layers"].append({
            "radial": {"w1": nrm((R, RADIAL_HIDDEN)) / math.sqrt(R),
                       "w2": nrm((RADIAL_HIDDEN, C * (L + 1))) / RADIAL_HIDDEN},
            "mix": nrm((L + 1, C, C)) / math.sqrt(C),
            "mb_mix": nrm((L + 1, C, C)) / math.sqrt(C),
            "mb_w": (1.0 + 0.2 * nrm((nu, L + 1))) / nu,
            "gate": {"w1": nrm((C, GATE_HIDDEN)) / math.sqrt(C),
                     "w2": nrm((GATE_HIDDEN, C)) / math.sqrt(GATE_HIDDEN)},
        })
    return params


# ------------------------------------------------------ sphere quadrature

def quadrature_degree(cfg: dict) -> int:
    """Highest polynomial degree a projection integrates: the conv's
    L + L_edge + L and the many-body product's nu*L + L."""
    L = cfg["L"]
    return max(2 * L + cfg["L_edge"], (cfg["nu"] + 1) * L)


def _real_sh_np(L: int, xyz: np.ndarray) -> np.ndarray:
    """Orthonormal real spherical harmonics, float64, [G, (L+1)^2], from
    the associated Legendre recursion in theta and cos/sin in phi."""
    z = np.clip(xyz[:, 2], -1.0, 1.0)
    st = np.sqrt(1.0 - z * z)
    phi = np.arctan2(xyz[:, 1], xyz[:, 0])
    P = {}
    for m in range(L + 1):
        P[m, m] = np.prod(np.arange(1, 2 * m, 2, dtype=np.float64)) * st ** m
        if m + 1 <= L:
            P[m + 1, m] = (2 * m + 1) * z * P[m, m]
        for l in range(m + 2, L + 1):
            P[l, m] = ((2 * l - 1) * z * P[l - 1, m]
                       - (l + m - 1) * P[l - 2, m]) / (l - m)
    cols = []
    for l in range(L + 1):
        for m in range(-l, l + 1):
            a = abs(m)
            n = math.sqrt((2 * l + 1) / (4 * math.pi) * math.factorial(l - a)
                          / math.factorial(l + a))
            if m == 0:
                cols.append(n * P[l, 0])
            elif m > 0:
                cols.append(math.sqrt(2) * n * P[l, a] * np.cos(a * phi))
            else:
                cols.append(math.sqrt(2) * n * P[l, a] * np.sin(a * phi))
    return np.stack(cols, axis=-1)


def _sphere_grid(degree: int):
    """Points [G, 3] and weights [G] integrating every polynomial of
    degree <= ``degree`` on the sphere exactly: Gauss-Legendre in cos(theta)
    (exact to 2*nt-1) times a uniform phi grid (exact to np-1)."""
    nt, nphi = degree // 2 + 1, degree + 1
    t, wt = np.polynomial.legendre.leggauss(nt)
    phi = 2 * np.pi * np.arange(nphi) / nphi
    st = np.sqrt(1 - t * t)
    pts = np.stack([np.outer(st, np.cos(phi)), np.outer(st, np.sin(phi)),
                    np.outer(t, np.ones_like(phi))], -1).reshape(-1, 3)
    w = np.outer(wt, np.full(nphi, 2 * np.pi / nphi)).reshape(-1)
    return pts, w


def _tables(cfg: dict):
    """(grid points [G,3], SH values by degree [L+1, K, G], weighted
    projection [K, G]) for the configuration's degrees."""
    L = cfg["L"]
    pts, w = _sphere_grid(quadrature_degree(cfg))
    Y = _real_sh_np(L, pts).T                      # [K, G]
    deg = np.concatenate([np.full(2 * l + 1, l) for l in range(L + 1)])
    by_deg = np.stack([Y * (deg == l)[:, None] for l in range(L + 1)])
    return pts, by_deg, Y * w[None, :]


def _legendre(l_max: int, t):
    out = [jnp.ones_like(t), t]
    for l in range(2, l_max + 1):
        out.append(((2 * l - 1) * t * out[-1] - (l - 1) * out[-2]) / l)
    return out[: l_max + 1]


# ------------------------------------------------------------------ model

def _radial_basis(r, n: int, cutoff: float):
    rs = jnp.clip(r, 1e-4, None)
    k = jnp.arange(1, n + 1, dtype=r.dtype) * math.pi / cutoff
    rb = jnp.sin(k * rs[..., None]) / rs[..., None]
    env = jnp.where(r < cutoff, 0.5 * (jnp.cos(math.pi * r / cutoff) + 1.0), 0.0)
    return rb * env[..., None]


def _einsum_bf16x3(eq, *ops):
    """``einsum`` of float32 operands as three-pass bfloat16 computes it:
    x = hi + lo with hi, lo bfloat16; the terms with at most one low part
    are multiplied exactly (a product of two bfloat16 numbers is exact in
    float32) and summed in float32."""
    def bf16(x):  # rounded to bfloat16, held in float32 (exact)
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    parts = []
    for a in ops:
        a = a.astype(jnp.float32)
        hi = bf16(a)
        parts.append((hi, bf16(a - hi)))
    total = None
    for low in range(-1, len(ops)):
        t = jnp.einsum(eq, *(p[1] if i == low else p[0]
                             for i, p in enumerate(parts)),
                       precision=jax.lax.Precision.HIGHEST)
        total = t if total is None else total + t
    return total


def energy(params, species, pos, atom_mask, cfg: dict, dtype: str = "float32"):
    """Energy of one structure: species [n] int, pos [n,3], atom_mask [n]
    (1 for real atoms, 0 for padding)."""
    if dtype == "bf16x3":
        dt, ein = jnp.dtype(jnp.float32), _einsum_bf16x3
    else:
        dt = jnp.dtype(dtype)
        prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                else jax.lax.Precision.DEFAULT)
        ein = lambda eq, *a: jnp.einsum(eq, *a, precision=prec)  # noqa: E731
    L, C, Le = cfg["L"], cfg["channels"], cfg["L_edge"]
    pts, by_deg, proj = _tables(cfg)
    pts_f = jnp.asarray(pts, jnp.float32)
    by_deg, proj = jnp.asarray(by_deg, dt), jnp.asarray(proj, dt)
    deg = np.concatenate([np.full(2 * l + 1, l) for l in range(L + 1)])
    p = jax.tree.map(lambda a: a.astype(dt), params)

    n = pos.shape[0]
    pos = pos.astype(jnp.float32)
    am = atom_mask.astype(jnp.float32)
    eye = jnp.eye(n, dtype=jnp.float32)
    diff = pos[None, :, :] - pos[:, None, :]               # r_j - r_i
    dist = jnp.sqrt(jnp.sum(diff * diff, -1) + eye)
    pair = (1.0 - eye) * am[:, None] * am[None, :] * (dist < cfg["cutoff"])
    rhat = diff / dist[..., None]
    cosg = jnp.einsum("ijx,gx->ijg", rhat, pts_f,
                      precision=jax.lax.Precision.HIGHEST)
    Pl = _legendre(Le, cosg)
    filt = sum((2 * l + 1) / (4 * math.pi) * Pl[l] for l in range(Le + 1))
    filt = (filt * pair[..., None]).astype(dt)               # [n, n, G]
    rb = _radial_basis(dist, cfg["n_radial"], cfg["cutoff"]).astype(dt)

    x = jnp.zeros((n, C, (L + 1) ** 2), dt)
    x = x.at[..., 0].set(p["species"][species])
    for lp in p["layers"]:
        h = ein("ijr,rk->ijk", rb, lp["radial"]["w1"])
        h = ein("ijk,kd->ijd", jax.nn.silu(h), lp["radial"]["w2"])
        h = h.reshape(n, n, C, L + 1)
        xl = ein("jck,lkg->jclg", x, by_deg)                # x_j by degree
        msg = ein("ijg,ijcl,jclg->icg", filt, h, xl)
        m = ein("icg,kg->ick", msg, proj)
        A = ein("ick,kcd->idk", m, lp["mix"][deg]) + x
        al = ein("ick,lkg->iclg", A, by_deg)
        prod = None
        for k in range(cfg["nu"]):
            f = ein("iclg,l->icg", al, lp["mb_w"][k])
            prod = f if prod is None else prod * f
        B = ein("icg,kg->ick", prod, proj)
        y = ein("ick,kcd->idk", B, lp["mb_mix"][deg])
        s = y[..., 0]
        g = jax.nn.sigmoid(ein("ic,ch->ih", jax.nn.silu(
            ein("ic,ch->ih", s, lp["gate"]["w1"])), lp["gate"]["w2"]))
        gated = jnp.concatenate([jax.nn.silu(s)[..., None],
                                 y[..., 1:] * g[..., None]], axis=-1)
        x = x + gated
    feat = x[..., 0]
    e_atom = ein("ih,ho->io", jax.nn.silu(
        ein("ic,ch->ih", feat, p["readout"]["w1"])), p["readout"]["w2"])
    return jnp.sum(e_atom[:, 0].astype(jnp.float32) * am)


def energy_forces(params, species, pos, atom_mask, cfg: dict,
                  dtype: str = "float32"):
    e, g = jax.value_and_grad(energy, argnums=2)(params, species, pos,
                                                 atom_mask, cfg, dtype)
    return e, -g


def loss(params, batch, cfg: dict, dtype: str = "float32",
         w_e: float = 1.0, w_f: float = 10.0):
    """Energy + force-matched loss, mean over the batch's structures:
    w_e (E - E_ref)^2 + w_f mean((F - F_ref)^2)."""
    def one(species, pos, e_ref, f_ref):
        e, f = energy_forces(params, species, pos,
                             jnp.ones(species.shape, jnp.float32), cfg, dtype)
        return (w_e * (e - e_ref) ** 2
                + w_f * jnp.mean((f.astype(jnp.float32) - f_ref) ** 2))

    return jnp.mean(jax.vmap(one)(batch["species"], batch["pos"],
                                  batch["energy"], batch["forces"]))


# ------------------------------------------------------------ FLOP count

def gaunt_nnz(l1: int, l2: int, l3: int) -> int:
    """Non-zero couplings of the real Gaunt tensor between degrees <= l1,
    <= l2 and <= l3: the terms of the product's sparse contraction in the
    spherical-harmonic basis."""
    pts, w = _sphere_grid(l1 + l2 + l3)
    y1, y2, y3 = (_real_sh_np(l, pts) for l in (l1, l2, l3))
    g = np.einsum("ga,gb,gc,g->abc", y1, y2, y3, w)
    return int(np.sum(np.abs(g) > 1e-10))


def forward_flops(cfg: dict, n_atoms: int, n_pairs: int) -> int:
    """Operations of one forward pass on ``n_atoms`` real atoms with
    ``n_pairs`` ordered pairs inside the cutoff.  A multiply-add counts 2.

    Per layer and pair: the radial MLP, then per channel the per-degree
    weighting of x_j, the Gaunt contraction with Y(r_ij) and the sum into
    m_i.  Per layer and atom: the two degree-wise channel mixes, the
    nu-fold product as a chain of pairwise Gaunt contractions (degrees
    grow without truncation, the last projects to L) with its per-degree
    weights, the gate and the residuals.  Then the readout.  What the
    implementation pads or recomputes is not counted."""
    L, Le, C, nu = cfg["L"], cfg["L_edge"], cfg["channels"], cfg["nu"]
    R, H = cfg["n_radial"], cfg["hidden"]
    K = (L + 1) ** 2
    radial = 2 * R * RADIAL_HIDDEN + 2 * RADIAL_HIDDEN * C * (L + 1)
    conv = C * (K + 2 * gaunt_nnz(L, Le, L) + K)
    chain, deg = 0, L
    for k in range(1, nu):
        out = L if k == nu - 1 else deg + L
        chain += 2 * gaunt_nnz(deg, L, out)
        deg = out
    many = C * (nu * K + chain)
    mixes = 2 * 2 * K * C * C
    gate = 2 * C * GATE_HIDDEN * 2 + K * C
    residual = 2 * K * C
    per_layer = (n_pairs * (radial + conv)
                 + n_atoms * (mixes + many + gate + residual))
    readout = n_atoms * (2 * C * H + 2 * H)
    return int(cfg["n_layers"] * per_layer + readout)
