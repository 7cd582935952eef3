"""Plain reference of EquiformerV2 with the Gaunt Selfmix layer, the model
that the `eqv2-*` configurations run, with the benchmark's own weights,
neighbour graph and FLOP count for it.

The reference follows the layer equations (EquiformerV2, Liao et al., ICLR
2024; the Selfmix of Luo et al., ICLR 2024, section 5) and nothing of the
served model's code.  Features are real spherical-harmonic coefficients
``x [n, (L+1)^2, C]``; edge ``j -> i`` runs over ``nbr[i]``:

- Wigner matrices come from real spherical harmonics on an exact sphere
  quadrature, ``D^l_mn(R) = int Y_lm(R s) Y_ln(s) ds``, for the edge frame
  ``R`` that takes ``r_ij = r_j - r_i`` to the z axis (the gauge about the
  edge is the configuration's assumed one, `frame`);
- the SO(2) convolutions are written out per order m: at m=0 one linear
  over (l, channel) with a bias, at m>0 ``y+ = W_r x+ - W_i x-``,
  ``y- = W_r x- + W_i x+``;
- the S^2 nonlinearities sample on the configuration's grid (``grid``:
  Gauss-Legendre in cos(theta) times uniform phi) and project back with its
  weights;
- the Selfmix product is what it is, the product of two spherical functions
  on an exact Gauss-Legendre x uniform-phi quadrature, projected onto
  degrees <= L.

Per block: ``x += Attn(LN_sh(x)); x += FFN(LN_sh(x)); x += Selfmix(LN_sh(x))``;
the energy is ``sum_i mask_i FFN_out(LN_sh(x_i))[l=0] / avg_num_nodes``, of
which only the l=0 path (``W_0 silu(W_s x_0 + b_s) + b``) is computed: the
degree-wise output linear and the FFN's replacement of l=0 by its gating
scalars leave nothing else in it.  `neighbours` is the reference's own
float64 k-nearest-neighbour builder.

``dtype='float32'`` runs every contraction at the highest matmul precision;
the control ``'bf16x3'`` keeps float32 everywhere but computes every
contraction as XLA's three-pass ``high`` precision does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the MACE reference's real spherical harmonics (float64, numpy), its
# three-pass bfloat16 einsum and its count of Gaunt couplings
from bench.configs.mace import _einsum_bf16x3, _real_sh_np, gaunt_nnz

__all__ = ["init_params", "neighbours", "frame", "grid", "energy",
           "energy_forces", "gaunt_nnz", "forward_flops", "so2_conv_flops"]

EPS = 1e-5


# ---------------------------------------------------------------- weights

def init_params(cfg: dict, key):
    """The benchmark's weights, in the served model's parameter layout (the
    blocks' leaves stacked on a leading axis), cut from one normal draw.
    Jit this.  Scales, biases and norm weights are drawn away from 1 and 0
    so that a path that ignored one would show."""
    L, M, C = cfg["lmax"], cfg["mmax"], cfg["sphere_channels"]
    H = cfg["attn_hidden_channels"]
    heads, A = cfg["num_heads"], cfg["attn_alpha_channels"]
    V = heads * cfg["attn_value_channels"]
    F, Ec, Z = cfg["ffn_hidden_channels"], cfg["edge_channels"], \
        cfg["max_num_elements"]
    nb = (cfg["n_blocks"],)
    d_in = cfg["num_distance_basis"] + 2 * Ec

    # leaves as (shape, scale, centre): centre + scale * N(0, 1)
    def w(shape, fan_in, lead=nb):
        return (lead + shape, 1.0 / math.sqrt(fan_in), 0.0)

    def b(shape, lead=nb, centre=0.0):
        return (lead + shape, 0.1, centre)

    def radial(d_out, lead=nb):
        return {"w1": w((d_in, Ec), d_in, lead), "b1": b((Ec,), lead),
                "ln1_w": b((Ec,), lead, 1.0), "ln1_b": b((Ec,), lead),
                "w2": w((Ec, Ec), Ec, lead), "b2": b((Ec,), lead),
                "ln2_w": b((Ec,), lead, 1.0), "ln2_b": b((Ec,), lead),
                "w3": w((Ec, d_out), Ec, lead), "b3": b((d_out,), lead)}

    def so2(ci, co, extra):
        p = {"w0": w(((L + 1) * ci, extra + (L + 1) * co), (L + 1) * ci),
             "b0": b((extra + (L + 1) * co,))}
        for m in range(1, M + 1):
            n_m = L - m + 1
            p[f"w{m}"] = w((n_m * ci, 2 * n_m * co), 2 * n_m * ci)
        return p

    def norm(lead=nb):
        return {"l0_w": b((C,), lead, 1.0), "l0_b": b((C,), lead),
                "w": b((L, C), lead, 1.0)}

    n_rad = sum(L - m + 1 for m in range(M + 1)) * 2 * C
    blocks = {
        "norm1": norm(),
        "attn": {"src": w((Z, Ec), 1), "tgt": w((Z, Ec), 1),
                 "rad": radial(n_rad), "conv1": so2(2 * C, H, heads * A + H),
                 "alpha_ln_w": b((A,), nb, 1.0), "alpha_ln_b": b((A,)),
                 "alpha_dot": w((heads, A), A), "conv2": so2(H, V, 0),
                 "proj_w": w((L + 1, V, C), V), "proj_b": b((C,))},
        "norm2": norm(),
        "norm3": norm(),
        "ffn": {"scalar_w": w((C, F), C), "scalar_b": b((F,)),
                "lin1_w": w((L + 1, C, F), C), "lin1_b": b((F,)),
                "grid_w1": w((F, F), F), "grid_w2": w((F, F), F),
                "grid_w3": w((F, F), F),
                "lin2_w": w((L + 1, F, C), F), "lin2_b": b((C,))},
        "selfmix": {"w1": (nb + (L + 1,), 0.2, 1.0),
                    "w2": (nb + (L + 1,), 0.2, 1.0),
                    "w3": (nb + (2 * L + 1,), 0.2, 1.0),
                    "mix": w((L + 1, C, C), C)},
    }
    spec = {"embed": w((Z, C), 1, ()),
            "edge_deg": {"src": w((Z, Ec), 1, ()), "tgt": w((Z, Ec), 1, ()),
                         "rad": radial((L + 1) * C, ())},
            "blocks": blocks, "norm": norm(()),
            "head": {"scalar_w": w((C, F), C, ()), "scalar_b": b((F,), ()),
                     "w": w((F, 1), F, ()), "b": b((1,), ())}}
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    sizes = [math.prod(shape) for shape, _, _ in leaves]
    z = jax.random.normal(key, (sum(sizes),), jnp.float32)
    offs = np.cumsum([0] + sizes)
    return jax.tree.unflatten(tree, [
        centre + scale * z[o:o + n].reshape(shape)
        for (shape, scale, centre), o, n in zip(leaves, offs, sizes)])


# ------------------------------------------------------------- the graph

def neighbours(pos, mask, cutoff: float, k: int):
    """Each real atom's k nearest real atoms closer than ``cutoff``, in
    float64, nearest first and, at equal distance, the lower index first.
    -> (nbr [n, k] int32, nbr_mask [n, k] float32); missing edges point at
    atom 0 and are masked."""
    pos = np.asarray(pos, np.float64)
    real = np.flatnonzero(np.asarray(mask) > 0)
    n = len(pos)
    nbr = np.zeros((n, k), np.int32)
    nbr_mask = np.zeros((n, k), np.float32)
    for i in real:
        cand = []
        for j in real:
            if j != i:
                d = math.sqrt(float(np.sum((pos[j] - pos[i]) ** 2)))
                if d < cutoff:
                    cand.append((d, int(j)))
        cand.sort()
        for slot, (_, j) in enumerate(cand[:k]):
            nbr[i, slot] = j
            nbr_mask[i, slot] = 1.0
    return nbr, nbr_mask


# ------------------------------------------------------ spherical tables

def _real_sh(L: int, xyz):
    """`_real_sh_np` in jax.numpy, differentiable: for unit vectors,
    sin(theta)^m e^{i m phi} = (x + i y)^m and the rest of P_l^m is a
    polynomial in z."""
    x, y, z = xyz[..., 0], xyz[..., 1], xyz[..., 2]
    re, im = [jnp.ones_like(z)], [jnp.zeros_like(z)]
    for _ in range(L):
        re, im = re + [re[-1] * x - im[-1] * y], im + [re[-1] * y + im[-1] * x]
    cols = []
    for l in range(L + 1):
        for m in range(-l, l + 1):
            a = abs(m)
            q = {a: jnp.full_like(z, float(np.prod(np.arange(1, 2 * a, 2))))}
            for d in range(a + 1, l + 1):
                q[d] = ((2 * d - 1) * z * q[d - 1]
                        - (d + a - 1) * q.get(d - 2, 0.0)) / (d - a)
            nrm = math.sqrt((2 * l + 1) / (4 * math.pi) * math.factorial(l - a)
                            / math.factorial(l + a))
            if m == 0:
                cols.append(nrm * q[l])
            else:
                part = re[a] if m > 0 else im[a]
                cols.append(math.sqrt(2) * nrm * q[l] * part)
    return jnp.stack(cols, axis=-1)


def _gl_grid(n_theta: int, n_phi: int):
    """Gauss-Legendre nodes in cos(theta) times a uniform phi grid: points
    [G, 3] and weights [G], the Gauss-Legendre weights times 2 pi / n_phi;
    exact for polynomials of degree <= min(2 n_theta - 1, n_phi - 1)."""
    t, wt = np.polynomial.legendre.leggauss(n_theta)
    phi = 2 * np.pi * np.arange(n_phi) / n_phi
    st = np.sqrt(1 - t * t)
    pts = np.stack([np.outer(st, np.cos(phi)), np.outer(st, np.sin(phi)),
                    np.outer(t, np.ones_like(phi))], -1).reshape(-1, 3)
    return pts, np.outer(wt, np.full(n_phi, 2 * np.pi / n_phi)).reshape(-1)


def _sphere_grid(degree: int):
    """A grid integrating every polynomial of degree <= ``degree`` on the
    sphere exactly."""
    return _gl_grid(degree // 2 + 1, degree + 1)


def grid(cfg: dict):
    """The configuration's S^2 grid of the nonlinearities: (points [G, 3],
    weights [G])."""
    g = cfg["grid"]
    if (g["theta"], g["phi"]) != ("gauss_legendre", "uniform"):
        raise ValueError(f"unknown grid {g}")
    return _gl_grid(g["n_theta"], g["n_phi"])


def frame(v):
    """Rotations R [..., 3, 3] with R r = e_z for r = v / |v|; the gauge
    about the edge: the first row is u x r normalised, u = e_z where
    |r_x| > 0.9 and e_x elsewhere, the second row r x (first row)."""
    r = v / jnp.sqrt(jnp.sum(v * v, -1, keepdims=True))
    ex = jnp.zeros_like(r).at[..., 0].set(1.0)
    ez = jnp.zeros_like(r).at[..., 2].set(1.0)
    u = jnp.where(jnp.abs(r[..., :1]) > 0.9, ez, ex)
    b1 = jnp.cross(u, r)
    b1 = b1 / jnp.sqrt(jnp.sum(b1 * b1, -1, keepdims=True))
    return jnp.stack([b1, jnp.cross(r, b1), r], axis=-2)


# ------------------------------------------------------------------ model

def _einsum(dtype: str):
    if dtype == "bf16x3":
        return _einsum_bf16x3
    if dtype != "float32":
        raise ValueError(f"unknown reference dtype {dtype!r}")
    return lambda eq, *a: jnp.einsum(eq, *a,
                                     precision=jax.lax.Precision.HIGHEST)


def _ln(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * w + b


def _norm_sh(p, x, L):
    """LayerNorm at l=0; at l>0 one RMS over channels and coefficients,
    each degree weighted 1/(2l+1), times a scale per (l, channel)."""
    ms = 0.0
    for l in range(1, L + 1):
        ms = ms + jnp.sum(x[:, l * l:(l + 1) ** 2] ** 2, 1) / (2 * l + 1)
    inv = 1.0 / jnp.sqrt(jnp.mean(ms / L, -1) + EPS)            # [n]
    out = [_ln(x[:, 0], p["l0_w"], p["l0_b"])[:, None]]
    for l in range(1, L + 1):
        out.append(x[:, l * l:(l + 1) ** 2] * inv[:, None, None]
                   * p["w"][l - 1])
    return jnp.concatenate(out, 1)


def _mlp(p, e, ein):
    h = jax.nn.silu(_ln(ein("...i,io->...o", e, p["w1"]) + p["b1"],
                        p["ln1_w"], p["ln1_b"]))
    h = jax.nn.silu(_ln(ein("...i,io->...o", h, p["w2"]) + p["b2"],
                        p["ln2_w"], p["ln2_b"]))
    return ein("...i,io->...o", h, p["w3"]) + p["b3"]


def _lin_l(w, b, x, L, ein):
    out = [ein("nkc,cd->nkd", x[:, l * l:(l + 1) ** 2], w[l])
           for l in range(L + 1)]
    out[0] = out[0] + b
    return jnp.concatenate(out, 1)


def _so2(p, parts, L, M, n_out, extra, ein, rad=None):
    """SO(2) convolution of edge-frame features given per order m:
    ``parts[0]`` [n, k, L+1, Ci] (m=0, l=0..L) and, for m>0, ``parts[m]``
    = (x+ [n, k, L-m+1, Ci], x- alike) over l=m..L.  ``rad`` scales the
    flattened (l, channel) inputs of each m, the same for +m and -m.
    -> (outputs per m alike, extra invariant outputs)."""
    x0 = parts[0]
    n, k, _, ci = x0.shape
    flat = x0.reshape(n, k, -1)
    off = flat.shape[-1]
    if rad is not None:
        flat = flat * rad[..., :off]
    y0 = ein("ijf,fo->ijo", flat, p["w0"]) + p["b0"]
    out = {0: y0[..., extra:].reshape(n, k, L + 1, n_out)}
    for m in range(1, M + 1):
        n_m = L - m + 1
        xp, xm = (a.reshape(n, k, n_m * ci) for a in parts[m])
        if rad is not None:
            r = rad[..., off:off + n_m * ci]
            xp, xm = xp * r, xm * r
        off += n_m * ci
        wr, wi = p[f"w{m}"][:, :n_m * n_out], p[f"w{m}"][:, n_m * n_out:]
        yp = ein("ijf,fo->ijo", xp, wr) - ein("ijf,fo->ijo", xm, wi)
        ym = ein("ijf,fo->ijo", xm, wr) + ein("ijf,fo->ijo", xp, wi)
        out[m] = (yp.reshape(n, k, n_m, n_out), ym.reshape(n, k, n_m, n_out))
    return out, y0[..., :extra]


def _wigner(L, R, ein):
    """D^l(R) [..., 2l+1, 2l+1] for l <= L, D_mn = int Y_lm(R s) Y_ln(s) ds
    on an exact quadrature (degree 2L)."""
    pts, w = _sphere_grid(2 * L)
    rot = jnp.einsum("...ab,gb->...ga", R, jnp.asarray(pts, jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    y_rot = _real_sh(L, rot)                                  # [..., G, K]
    y_w = jnp.asarray(_real_sh_np(L, pts) * w[:, None], jnp.float32)
    return [ein("...ga,gb->...ab", y_rot[..., l * l:(l + 1) ** 2],
                y_w[:, l * l:(l + 1) ** 2]) for l in range(L + 1)]


def _split(full, L, M):
    """Coefficients [n, k, K, c] -> the orders |m| <= M as `_so2` reads
    them."""
    def rows(ms):
        return jnp.stack([full[:, :, l * l + l + m] for l, m in ms], 2)

    parts = {0: rows([(l, 0) for l in range(L + 1)])}
    for m in range(1, M + 1):
        parts[m] = (rows([(l, m) for l in range(m, L + 1)]),
                    rows([(l, -m) for l in range(m, L + 1)]))
    return parts


def _full(parts, L, M):
    """The inverse of `_split`: the kept orders, zeros elsewhere."""
    n, k, _, c = parts[0].shape
    rows = [jnp.zeros((n, k, c), parts[0].dtype)] * (L + 1) ** 2
    for l in range(L + 1):
        rows[l * l + l] = parts[0][:, :, l]
    for m in range(1, M + 1):
        for i, l in enumerate(range(m, L + 1)):
            rows[l * l + l + m] = parts[m][0][:, :, i]
            rows[l * l + l - m] = parts[m][1][:, :, i]
    return jnp.stack(rows, 2)


def _into_frame(D, x, L, M, ein):
    """Rotate edge features x [n, k, K, c] into the edge frame and keep the
    orders |m| <= M."""
    return _split(jnp.concatenate(
        [ein("ijab,ijbc->ijac", D[l], x[:, :, l * l:(l + 1) ** 2])
         for l in range(L + 1)], 2), L, M)


def _out_of_frame(D, parts, L, M, ein):
    """The kept orders, zeros elsewhere, rotated back with D^T.
    -> [n, k, K, c]."""
    full = _full(parts, L, M)
    return jnp.concatenate(
        [ein("ijab,ijac->ijbc", D[l], full[:, :, l * l:(l + 1) ** 2])
         for l in range(L + 1)], 2)


def energy(params, species, pos, atom_mask, nbr, nbr_mask, cfg: dict,
           dtype: str = "float32"):
    """Energy of one structure on its graph: species [n] int, pos [n, 3],
    atom_mask [n], nbr [n, k] int, nbr_mask [n, k]."""
    ein = _einsum(dtype)
    L, M, C = cfg["lmax"], cfg["mmax"], cfg["sphere_channels"]
    heads, A = cfg["num_heads"], cfg["attn_alpha_channels"]
    H, Vc = cfg["attn_hidden_channels"], cfg["attn_value_channels"]
    K = (L + 1) ** 2
    n, k = nbr.shape
    pos = pos.astype(jnp.float32)
    emask = nbr_mask.astype(jnp.float32)

    # edges: geometry, Wigner matrices and distance features
    vec = pos[nbr] - pos[:, None, :]
    vec = jnp.where(emask[..., None] > 0, vec, jnp.asarray([0.0, 0.0, 1.0]))
    dist = jnp.sqrt(jnp.sum(vec * vec, -1))
    D = _wigner(L, frame(vec), ein)
    B = cfg["num_distance_basis"]
    centres = np.linspace(0.0, cfg["max_radius"], B)
    width = cfg["distance_width"] * cfg["max_radius"] / (B - 1)
    gauss = jnp.exp(-0.5 * ((dist[..., None] - centres) / width) ** 2)

    def edge_in(p):
        return jnp.concatenate([gauss, p["src"][species[nbr]],
                                jnp.broadcast_to(p["tgt"][species][:, None],
                                                 (n, k, p["tgt"].shape[1]))], -1)

    # S^2 grid of the nonlinearities, and the orders |m| <= M on it
    g_pts, g_w = grid(cfg)
    Yg = _real_sh_np(L, g_pts)                                # [G, K]
    keep = [l * l + l + m for m in range(-M, M + 1) for l in range(abs(m), L + 1)]
    Yg_red = np.zeros_like(Yg)
    Yg_red[:, keep] = Yg[:, keep]
    Yg, Pg = jnp.asarray(Yg, jnp.float32), jnp.asarray(Yg * g_w[:, None], jnp.float32)
    Yr, Pr = jnp.asarray(Yg_red, jnp.float32), jnp.asarray(Yg_red * g_w[:, None],
                                                          jnp.float32)
    # exact quadrature of the Selfmix product
    s_pts, s_w = _sphere_grid(3 * L)
    Ys = _real_sh_np(L, s_pts)
    Ys, Ps = jnp.asarray(Ys, jnp.float32), jnp.asarray(Ys * s_w[:, None], jnp.float32)
    deg = np.concatenate([np.full(2 * l + 1, l) for l in range(L + 1)])

    # embedding: species, plus the edge-degree embedding at m=0
    ed = params["edge_deg"]
    r0 = _mlp(ed["rad"], edge_in(ed), ein).reshape(n, k, L + 1, C) \
        * emask[..., None, None]
    zeros = {m: (jnp.zeros((n, k, L - m + 1, C)),) * 2 for m in range(1, M + 1)}
    x = jnp.sum(_out_of_frame(D, {0: r0, **zeros}, L, M, ein), 1) \
        / cfg["avg_degree"]
    x = x.at[:, 0].add(params["embed"][species])

    def attn(p, xn):
        xe = jnp.concatenate([xn[nbr], jnp.broadcast_to(xn[:, None],
                                                        (n, k, K, C))], -1)
        parts = _into_frame(D, xe, L, M, ein)
        h, ex = _so2(p["conv1"], parts, L, M, H, heads * A + H, ein,
                     rad=_mlp(p["rad"], edge_in(p), ein))
        # separable S^2 activation of the kept orders, l=0 from the gate
        act = ein("gk,ijkh->ijgh", Yr, _full(h, L, M))
        back = ein("gk,ijgh->ijkh", Pr, jax.nn.silu(act))
        back = back.at[:, :, 0].set(jax.nn.silu(ex[..., heads * A:]))
        h = _split(back, L, M)
        v, _ = _so2(p["conv2"], h, L, M, heads * Vc, 0, ein)
        a = _ln(ex[..., :heads * A].reshape(n, k, heads, A), p["alpha_ln_w"],
                p["alpha_ln_b"])
        a = 0.6 * a + 0.4 * a * (2 * jax.nn.sigmoid(a) - 1)   # SmoothLeakyReLU 0.2
        logit = ein("ijha,ha->ijh", a, p["alpha_dot"])
        logit = jnp.where(emask[..., None] > 0, logit, -1e30)
        wgt = jnp.exp(logit - jax.lax.stop_gradient(jnp.max(logit, 1,
                                                            keepdims=True)))
        wgt = wgt * emask[..., None]
        alpha = wgt / jnp.maximum(jnp.sum(wgt, 1, keepdims=True), 1e-30)
        scale = jnp.repeat(alpha, Vc, axis=-1)[:, :, None, :]    # [n,k,1,V]
        v = {m: (t * scale if m == 0 else (t[0] * scale, t[1] * scale))
             for m, t in v.items()}
        msg = jnp.sum(_out_of_frame(D, v, L, M, ein), 1)
        return _lin_l(p["proj_w"], p["proj_b"], msg, L, ein)

    def ffn(p, xn):
        gate = jax.nn.silu(ein("nc,cf->nf", xn[:, 0], p["scalar_w"])
                           + p["scalar_b"])
        h = _lin_l(p["lin1_w"], p["lin1_b"], xn, L, ein)
        g = ein("gk,nkf->ngf", Yg, h)
        g = jax.nn.silu(ein("ngf,fo->ngo", g, p["grid_w1"]))
        g = jax.nn.silu(ein("ngf,fo->ngo", g, p["grid_w2"]))
        g = ein("ngf,fo->ngo", g, p["grid_w3"])
        h = ein("gk,ngf->nkf", Pg, g)
        h = h.at[:, 0].set(gate)
        return _lin_l(p["lin2_w"], p["lin2_b"], h, L, ein)

    def selfmix(p, xn):
        f1 = ein("nkc,gk->ngc", xn * p["w1"][deg][None, :, None], Ys)
        f2 = ein("nkc,gk->ngc", xn * p["w2"][deg][None, :, None], Ys)
        y = ein("ngc,gk->nkc", f1 * f2, Ps) * p["w3"][deg][None, :, None]
        return _lin_l(p["mix"], jnp.zeros(C), y, L, ein)

    def block(x, p):
        x = x + attn(p["attn"], _norm_sh(p["norm1"], x, L))
        x = x + ffn(p["ffn"], _norm_sh(p["norm2"], x, L))
        return x + selfmix(p["selfmix"], _norm_sh(p["norm3"], x, L)), None

    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    x0 = _norm_sh(params["norm"], x, L)[:, 0]
    hd = params["head"]
    e = ein("nf,fo->no", jax.nn.silu(ein("nc,cf->nf", x0, hd["scalar_w"])
                                     + hd["scalar_b"]), hd["w"])[:, 0] + hd["b"][0]
    return jnp.sum(e * atom_mask.astype(jnp.float32)) / cfg["avg_num_nodes"]


def energy_forces(params, species, pos, atom_mask, nbr, nbr_mask, cfg: dict,
                  dtype: str = "float32"):
    e, g = jax.value_and_grad(energy, argnums=2)(
        params, species, pos, atom_mask, nbr, nbr_mask, cfg, dtype)
    return e, -g


# ------------------------------------------------------------ FLOP count

def _sizes(cfg):
    L, M = cfg["lmax"], cfg["mmax"]
    n_m = [L - m + 1 for m in range(M + 1)]
    return dict(L=L, M=M, K=(L + 1) ** 2, C=cfg["sphere_channels"],
                H=cfg["attn_hidden_channels"], heads=cfg["num_heads"],
                A=cfg["attn_alpha_channels"],
                V=cfg["num_heads"] * cfg["attn_value_channels"],
                F=cfg["ffn_hidden_channels"], Ec=cfg["edge_channels"],
                Din=cfg["num_distance_basis"] + 2 * cfg["edge_channels"],
                G=cfg["grid"]["n_theta"] * cfg["grid"]["n_phi"], n_m=n_m,
                R=n_m[0] + 2 * sum(n_m[1:]),
                # rows with |m| <= M of each degree's rotation, by degree
                rot=sum((2 * l + 1) * min(2 * l + 1, 2 * M + 1)
                        for l in range(L + 1)))


def _conv_flops(s, ci, co, extra):
    """One SO(2) convolution: the m=0 linear, and per m>0 the four
    products W_r x+, W_i x-, W_r x-, W_i x+."""
    n0 = s["n_m"][0]
    f = 2 * n0 * ci * (n0 * co + extra)
    for n_m in s["n_m"][1:]:
        f += 4 * 2 * (n_m * ci) * (n_m * co)
    return f


def _radial_flops(s, d_out):
    return 2 * (s["Din"] * s["Ec"] + s["Ec"] * s["Ec"] + s["Ec"] * d_out)


def so2_conv_flops(cfg: dict, n_atoms: int, n_edges: int) -> int:
    """Operations of the work under the ``eqv2.attn_conv`` scope in one
    forward pass: per block and edge the rotation into the edge frame (the
    kept rows only), the radial MLP and its scaling of the inputs, both
    SO(2) convolutions, the rotation back and the sum into the atom; per
    block and atom the output projection."""
    s = _sizes(cfg)
    C2 = 2 * s["C"]
    n_rad = sum(s["n_m"]) * C2
    edge = (2 * s["rot"] * C2 + _radial_flops(s, n_rad) + s["R"] * C2
            + _conv_flops(s, C2, s["H"], s["heads"] * s["A"] + s["H"])
            + _conv_flops(s, s["H"], s["V"], 0)
            + 2 * s["rot"] * s["V"] + s["K"] * s["V"])
    atom = 2 * s["K"] * s["V"] * s["C"]
    return int(cfg["n_blocks"] * (n_edges * edge + n_atoms * atom))


def forward_flops(cfg: dict, n_atoms: int, n_edges: int) -> int:
    """Operations of one forward pass on ``n_atoms`` real atoms and
    ``n_edges`` real edges.  A multiply-add counts 2, a lone multiply or add
    1; nonlinearities are not counted.

    Per block: `so2_conv_flops`' work; per edge the S^2 activation (to the
    grid and back over the kept orders), the attention logits and the
    weighting of the values; per atom the three norms, the FFN (two
    degree-wise linears, the gate, the grid and back, the 3-layer grid MLP),
    the Selfmix (its per-degree weights, the Gaunt product as a sparse
    contraction in the SH basis, the channel mix) and the residuals.  Once:
    the edge-degree embedding (radial MLP, rotation back of the m=0 rows,
    sum) and the readout.  What the implementation pads or recomputes is
    not counted."""
    s = _sizes(cfg)
    K, C, F, G = s["K"], s["C"], s["F"], s["G"]
    edge = (2 * 2 * s["R"] * G * s["H"] + 2 * s["heads"] * s["A"]
            + s["R"] * s["V"])
    norm = 3 * K * C
    ffn = (2 * K * C * F + 2 * C * F + 2 * K * G * F + 3 * 2 * G * F * F
           + 2 * G * K * F + 2 * K * F * C)
    selfmix = C * (3 * K + 2 * gaunt_nnz(s["L"], s["L"], s["L"])) \
        + 2 * K * C * C
    atom = 3 * norm + ffn + selfmix + 3 * K * C
    blocks = so2_conv_flops(cfg, n_atoms, n_edges) + cfg["n_blocks"] * (
        n_edges * edge + n_atoms * atom)
    embed = n_edges * (_radial_flops(s, (s["L"] + 1) * C)
                       + 2 * (s["L"] + 1) ** 2 * C + K * C)
    readout = n_atoms * (norm + 2 * C * F + 2 * F)
    return int(blocks + embed + readout)
