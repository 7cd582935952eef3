"""EquiformerV2 with the Gaunt Selfmix layer, on a k-nearest-neighbour graph.

EquiformerV2 (Liao, Shuaibi, Zitnick, Smidt, ICLR 2024) at the settings of
`EquiformerV2Config`, with the Equivariant Feature Interaction ("Selfmix")
that Luo et al. (ICLR 2024, section 5) add to it for OC20.

Features ``x`` are ``[n, (L+1)^2, C]``: real spherical-harmonic coefficients
(the packed layout of `core.so3`) per atom and channel.  Edge ``j -> i`` runs
over ``nbr[i]``, atom i's k nearest atoms within ``max_radius``, built on the
host (`repro.serve.pools.neighbour_graph`); ``nbr_mask`` marks the real
edges.  ``D_ij`` is the Wigner rotation that takes ``r_ij = r_j - r_i`` to
the z axis (`core.conv.align_rotation`); on the way in only the rows with
``|m| <= M`` are kept, ordered by m: m=0 for l=0..L, then +m and -m for
l=m..L, m=1..M.

    embed   x_i[l=0] = E[z_i]
            x_i += (1/avg_degree) sum_j D_ij^-1 [RadialMLP(e_ij) at m=0]
            e_ij = [Gauss(|r_ij|), E_src[z_j], E_tgt[z_i]]
    block   x += Attn(LN_sh(x));  x += FFN(LN_sh(x));  x += Selfmix(LN_sh(x))
    Attn    u_ij = D_ij [x_j || x_i]
            (h, a, g) = SO2Conv1(u_ij * RadialMLP(e_ij))
            alpha_ij = softmax_j(w_h . SmoothLeakyReLU(LN(a_ij^h)))
            v_ij = SO2Conv2(S2Act_sep(g, h))
            out_i = Linear_l(sum_j D_ij^-1 (alpha_ij * v_ij))
    FFN     Linear_l, a 3-layer MLP on the S^2 grid, l=0 replaced by
            silu(Linear(x_0)), Linear_l
    energy  sum_i mask_i FFN_out(LN_sh(x_i))[l=0] / avg_num_nodes

``SO2Conv`` is a linear map per order m in the edge frame: at m=0 one linear
over (l, channel) with a bias, whose first outputs are the extra invariant
channels; at m>0 ``y+ = W_r x+ - W_i x-``, ``y- = W_r x- + W_i x+``.
``S2Act_sep`` replaces l=0 by ``silu(g)`` and applies SiLU to the rest on
the S^2 grid.  ``LN_sh`` is a LayerNorm at l=0 and, at l>0, a division by
the RMS over channels and coefficients (each degree weighted 1/(2l+1)) with
a per-(l, channel) scale.  Selfmix is `SelfmixLayer.interaction`:
``mix(P_L[(w1 . x)(s) (w2 . x)(s)])``, a Gaunt self-product through the
chain engine, under its own LN_sh like the block's other two sublayers (a
quadratic residual update on unnormalised features would grow without
bound over the blocks).  The blocks run as one ``lax.scan`` over stacked block
parameters, each block under ``jax.checkpoint``: the force backward then
keeps one block's edge activations at a time.

Layer scopes ``eqv2.edge``, ``eqv2.embed``, ``eqv2.norm``,
``eqv2.attn_conv`` (rotations and SO(2) convolutions), ``eqv2.attn_softmax``,
``eqv2.s2_act``, ``eqv2.ffn``, ``eqv2.selfmix`` and ``eqv2.readout`` name
the device time of each layer in a profiler trace.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gaunt_ff import EquiformerV2Config
from repro.core.conv import (align_rotation, reduced_rows, so2_conv,
                             wigner_blocks_from_rotmat)
from repro.core.fourier import s2quad_project_sh, s2quad_sample_sh
from repro.models.equivariant import SelfmixLayer

__all__ = ["EquiformerV2", "EquiformerV2Config", "smooth_leaky_relu"]

LN_EPS = 1e-5


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def smooth_leaky_relu(x, slope: float = 0.2):
    return 0.5 * (1 + slope) * x + 0.5 * (1 - slope) * x * (
        2 * jax.nn.sigmoid(x) - 1)


def _radial(p, e):
    """Linear, LayerNorm, SiLU, Linear, LayerNorm, SiLU, Linear."""
    h = jax.nn.silu(_layer_norm(e @ p["w1"] + p["b1"], p["ln1_w"], p["ln1_b"]))
    h = jax.nn.silu(_layer_norm(h @ p["w2"] + p["b2"], p["ln2_w"], p["ln2_b"]))
    return h @ p["w3"] + p["b3"]


def _linear_l(w, b, x, L: int):
    """Degree-wise linear x [n, (L+1)^2, Ci] -> [n, (L+1)^2, Co] with
    w [L+1, Ci, Co]; the bias b [Co] acts at l=0 only."""
    outs = [x[:, l * l:(l + 1) ** 2] @ w[l] for l in range(L + 1)]
    outs[0] = outs[0] + b
    return jnp.concatenate(outs, axis=1)


class EquiformerV2:
    """EquiformerV2 + Gaunt Selfmix, energy and forces on a host-built
    neighbour graph (see the module docstring)."""

    def __init__(self, cfg: EquiformerV2Config):
        self.cfg = cfg
        L, M = cfg.lmax, cfg.mmax
        self.rows = reduced_rows(L, M)
        nt, nph = cfg.grid_theta, cfg.grid_phi
        to_grid = s2quad_sample_sh(L, nt, nph)            # [K, G]
        project = s2quad_project_sh(L, nt, nph)           # [G, K]
        self._grid = (np.float32(to_grid.T), np.float32(project.T))
        self._grid_red = (np.float32(to_grid[self.rows].T),
                          np.float32(project[:, self.rows].T))
        self.selfmix = SelfmixLayer(L=L, channels=cfg.sphere_channels)
        # LN_sh's weight of each l>0 coefficient: 1/(2l+1), over L degrees
        deg = np.concatenate([np.full(2 * l + 1, l) for l in range(1, L + 1)])
        self._balance = np.float32(1.0 / ((2 * deg + 1) * L))
        self._deg = deg - 1

    # ------------------------------------------------------------ weights
    def init(self, key):
        """Seeded weights in the layout `energy_graph` reads, cut from one
        normal draw; the blocks' leaves are stacked along a leading axis of
        length ``n_blocks``."""
        c = self.cfg
        L, M, C = c.lmax, c.mmax, c.sphere_channels
        H, V = c.attn_hidden_channels, c.num_heads * c.attn_value_channels
        A, F, Ec = c.num_heads * c.attn_alpha_channels, c.ffn_hidden_channels, \
            c.edge_channels
        Z = c.max_num_elements
        d_in = c.num_distance_basis + 2 * Ec
        b = (c.n_blocks,)

        # each leaf as (shape, scale, offset): offset + scale * N(0, 1)
        def lin(shape, fan_in, lead=b):
            return (lead + shape, 1.0 / math.sqrt(fan_in), 0.0)

        def vec(shape, lead=b, offset=0.0, scale=0.1):
            return (lead + shape, scale, offset)

        def radial(d_out, lead=b):
            return {"w1": lin((d_in, Ec), d_in, lead), "b1": vec((Ec,), lead),
                    "ln1_w": vec((Ec,), lead, 1.0), "ln1_b": vec((Ec,), lead),
                    "w2": lin((Ec, Ec), Ec, lead), "b2": vec((Ec,), lead),
                    "ln2_w": vec((Ec,), lead, 1.0), "ln2_b": vec((Ec,), lead),
                    "w3": lin((Ec, d_out), Ec, lead), "b3": vec((d_out,), lead)}

        def conv(ci, co, extra):
            p = {"w0": lin(((L + 1) * ci, extra + (L + 1) * co), (L + 1) * ci),
                 "b0": vec((extra + (L + 1) * co,))}
            for m in range(1, M + 1):
                nm = L - m + 1
                p[f"w{m}"] = lin((nm * ci, 2 * nm * co), 2 * nm * ci)
            return p

        def norm(lead=b):
            return {"l0_w": vec((C,), lead, 1.0), "l0_b": vec((C,), lead),
                    "w": vec((L, C), lead, 1.0)}

        n_rad = sum(L - m + 1 for m in range(M + 1)) * 2 * C
        blocks = {
            "norm1": norm(),
            "attn": {"src": lin((Z, Ec), 1), "tgt": lin((Z, Ec), 1),
                     "rad": radial(n_rad), "conv1": conv(2 * C, H, A + H),
                     "alpha_ln_w": vec((c.attn_alpha_channels,), b, 1.0),
                     "alpha_ln_b": vec((c.attn_alpha_channels,)),
                     "alpha_dot": lin((c.num_heads, c.attn_alpha_channels),
                                      c.attn_alpha_channels),
                     "conv2": conv(H, V, 0),
                     "proj_w": lin((L + 1, V, C), V), "proj_b": vec((C,))},
            "norm2": norm(),
            "norm3": norm(),
            "ffn": {"scalar_w": lin((C, F), C), "scalar_b": vec((F,)),
                    "lin1_w": lin((L + 1, C, F), C), "lin1_b": vec((F,)),
                    "grid_w1": lin((F, F), F), "grid_w2": lin((F, F), F),
                    "grid_w3": lin((F, F), F),
                    "lin2_w": lin((L + 1, F, C), F), "lin2_b": vec((C,))},
            "selfmix": {"w1": vec((L + 1,), b, 1.0, 0.2),
                        "w2": vec((L + 1,), b, 1.0, 0.2),
                        "w3": vec((2 * L + 1,), b, 1.0, 0.2),
                        "mix": lin((L + 1, C, C), C)},
        }
        spec = {"embed": lin((Z, C), 1, ()),
                "edge_deg": {"src": lin((Z, Ec), 1, ()),
                             "tgt": lin((Z, Ec), 1, ()),
                             "rad": radial((L + 1) * C, ())},
                "blocks": blocks, "norm": norm(()),
                "head": {"scalar_w": lin((C, F), C, ()),
                         "scalar_b": vec((F,), ()),
                         "w": lin((F, 1), F, ()), "b": vec((1,), ())}}
        leaves, tree = jax.tree.flatten(
            spec, is_leaf=lambda x: isinstance(x, tuple)
            and isinstance(x[0], tuple))
        sizes = [math.prod(shape) for shape, _, _ in leaves]
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, o = [], 0
        for (shape, scale, offset), n in zip(leaves, sizes):
            out.append(offset + scale * z[o:o + n].reshape(shape))
            o += n
        return jax.tree.unflatten(tree, out)

    # ------------------------------------------------------------ layers
    def _norm(self, p, x):
        with jax.named_scope("eqv2.norm"):
            y0 = _layer_norm(x[:, 0], p["l0_w"], p["l0_b"])
            rest = x[:, 1:]
            ms = jnp.mean(jnp.einsum("nkc,k->nc", jnp.square(rest),
                                     self._balance), -1)
            scale = jax.lax.rsqrt(ms + LN_EPS)[:, None, None] * p["w"][self._deg]
            return jnp.concatenate([y0[:, None], rest * scale], axis=1)

    def _edge_geometry(self, pos, nbr, nbr_mask):
        """-> (Gaussian distance features [n, k, B], reduced Wigner rows
        [n, k, R, K]).  Missing edges take the unit z direction, so that no
        NaN enters the rotation or its gradient; the mask removes them."""
        c = self.cfg
        L = c.lmax
        diff = pos[nbr] - pos[:, None, :]
        ez = jnp.asarray([0.0, 0.0, 1.0], pos.dtype)
        diff = jnp.where(nbr_mask[..., None] > 0, diff, ez)
        dist = jnp.sqrt(jnp.sum(jnp.square(diff), -1))
        R = align_rotation(diff / dist[..., None])
        K = (L + 1) ** 2
        blocks = []
        for l, D in enumerate(wigner_blocks_from_rotmat(L, R)):
            pad = [(0, 0)] * (D.ndim - 1) + [(l * l, K - (l + 1) ** 2)]
            blocks.append(jnp.pad(D, pad))
        W = jnp.concatenate(blocks, axis=-2)[..., self.rows, :]
        offsets = jnp.linspace(0.0, c.max_radius, c.num_distance_basis)
        step = c.max_radius / (c.num_distance_basis - 1)
        coeff = -0.5 / (c.distance_width * step) ** 2
        gauss = jnp.exp(coeff * jnp.square(dist[..., None] - offsets))
        return gauss, W

    def _edge_features(self, p, species, nbr, gauss):
        src = p["src"][species][nbr]
        tgt = jnp.broadcast_to(p["tgt"][species][:, None, :], src.shape)
        return jnp.concatenate([gauss, src, tgt], axis=-1)

    def _embed(self, params, species, nbr, nbr_mask, gauss, W):
        c = self.cfg
        L, C = c.lmax, c.sphere_channels
        p = params["edge_deg"]
        r = _radial(p["rad"], self._edge_features(p, species, nbr, gauss))
        r = r.reshape(*nbr.shape, L + 1, C) * nbr_mask[..., None, None]
        msg = jnp.einsum("ijak,ijac->ikc", W[..., :L + 1, :], r) / c.avg_degree
        x0 = params["embed"][species]
        return msg.at[:, 0].add(x0)

    def _s2_act(self, gate, h):
        to_grid, project = self._grid_red
        g = jax.nn.silu(jnp.einsum("gr,...rh->...gh", to_grid, h))
        y = jnp.einsum("rg,...gh->...rh", project, g)
        return jnp.concatenate([jax.nn.silu(gate)[..., None, :], y[..., 1:, :]],
                               axis=-2)

    def _attn(self, p, x, species, nbr, nbr_mask, gauss, W):
        c = self.cfg
        L, M = c.lmax, c.mmax
        heads, A = c.num_heads, c.attn_alpha_channels
        H, Vc = c.attn_hidden_channels, c.attn_value_channels
        with jax.named_scope("eqv2.attn_conv"):
            xi = jnp.broadcast_to(x[:, None], (*nbr.shape, *x.shape[1:]))
            u = jnp.einsum("ijak,ijkc->ijac", W,
                           jnp.concatenate([x[nbr], xi], axis=-1))
            rad = _radial(p["rad"], self._edge_features(p, species, nbr, gauss))
            h, ex = so2_conv(p["conv1"], u, L, M, H, heads * A + H, rad)
        with jax.named_scope("eqv2.s2_act"):
            h = self._s2_act(ex[..., heads * A:], h)
        with jax.named_scope("eqv2.attn_conv"):
            v, _ = so2_conv(p["conv2"], h, L, M, heads * Vc)
        with jax.named_scope("eqv2.attn_softmax"):
            a = ex[..., :heads * A].reshape(*nbr.shape, heads, A)
            a = smooth_leaky_relu(_layer_norm(a, p["alpha_ln_w"], p["alpha_ln_b"]))
            logit = jnp.einsum("ijha,ha->ijh", a, p["alpha_dot"])
            real = nbr_mask[..., None] > 0
            logit = jnp.where(real, logit, -1e30)
            top = jax.lax.stop_gradient(jnp.max(logit, axis=1, keepdims=True))
            w = jnp.where(real, jnp.exp(logit - top), 0.0)
            alpha = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-30)
            v = (v.reshape(*v.shape[:-1], heads, Vc) * alpha[:, :, None, :, None]
                 ).reshape(v.shape)
        with jax.named_scope("eqv2.attn_conv"):
            out = jnp.einsum("ijak,ijac->ikc", W, v)
            return _linear_l(p["proj_w"], p["proj_b"], out, L)

    def _ffn(self, p, x):
        L = self.cfg.lmax
        to_grid, project = self._grid
        with jax.named_scope("eqv2.ffn"):
            gate = jax.nn.silu(x[:, 0] @ p["scalar_w"] + p["scalar_b"])
            h = _linear_l(p["lin1_w"], p["lin1_b"], x, L)
            g = jnp.einsum("gk,nkf->ngf", to_grid, h)
            g = jax.nn.silu(g @ p["grid_w1"])
            g = jax.nn.silu(g @ p["grid_w2"])
            h = jnp.einsum("kg,ngf->nkf", project, g @ p["grid_w3"])
            h = jnp.concatenate([gate[:, None], h[:, 1:]], axis=1)
            return _linear_l(p["lin2_w"], p["lin2_b"], h, L)

    # ------------------------------------------------------------ model
    def energy_graph(self, params, species, pos, mask, nbr, nbr_mask):
        """Energy of one structure: species [n] int, pos [n, 3], mask [n]
        (1 for real atoms), nbr [n, k] int (the sources of each atom's
        edges), nbr_mask [n, k] (1 for real edges).  Forces are minus its
        gradient in ``pos`` with the graph held fixed."""
        c = self.cfg
        with jax.named_scope("eqv2.edge"):
            gauss, W = self._edge_geometry(pos, nbr, nbr_mask)
        with jax.named_scope("eqv2.embed"):
            x = self._embed(params, species, nbr, nbr_mask, gauss, W)

        def block(x, bp):
            x = x + self._attn(bp["attn"], self._norm(bp["norm1"], x), species,
                               nbr, nbr_mask, gauss, W)
            x = x + self._ffn(bp["ffn"], self._norm(bp["norm2"], x))
            xn = jnp.swapaxes(self._norm(bp["norm3"], x), 1, 2)
            with jax.named_scope("eqv2.selfmix"):
                y = self.selfmix.interaction(bp["selfmix"], xn)
                return x + jnp.swapaxes(y, 1, 2), None

        x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
        x = self._norm(params["norm"], x)
        with jax.named_scope("eqv2.readout"):
            # the energy head's l=0 output: Linear_l is degree-diagonal and
            # the FFN replaces l=0 by its gating scalars, so its grid MLP
            # and its l>0 weights do not reach the energy
            hd = params["head"]
            e = jax.nn.silu(x[:, 0] @ hd["scalar_w"] + hd["scalar_b"]) @ hd["w"]
            e = e[:, 0] + hd["b"][0]
            return jnp.sum(e * mask) / c.avg_num_nodes
