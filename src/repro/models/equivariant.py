"""The paper's model family — equivariant networks built on the Gaunt ops.

Three models mirroring the paper's experiments:
  * MACE-like force field (Table 2 / 3BPA): equivariant convolution message
    passing + many-body Gaunt self-products, energy readout, forces = -dE/dr.
  * SEGNN-like N-body net (Fig. 1 sanity check): steerable message passing;
    `tp_impl` switches Gaunt vs Clebsch-Gordan parameterization.
  * EquiformerV2-like Selfmix layer (Table 1): the Equivariant Feature
    Interaction the paper adds to EquiformerV2.

Feature layout: x [n_nodes, C, (L+1)^2] (channel-wise products, paper §3.3).
All graph ops are dense masked pairwise (the synthetic molecular/N-body
systems are small); radial weights follow h = MLP(radial basis of |r|).
"""
from __future__ import annotations

import dataclasses
import math
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gaunt_ff import EquivariantConfig
from repro.core.cg import cg_full_tensor_product
from repro.core.conv import EquivariantConv
from repro.core.gaunt import expand_degree_weights
from repro.core.irreps import l_array, num_coeffs
from repro.core.manybody import manybody_selfmix
from repro.core.so3 import real_sph_harm_jax

__all__ = ["EquivariantConfig", "MaceGaunt", "SegnnNBody", "SelfmixLayer"]


def equi_linear_init(key, L, c_in, c_out):
    return jax.random.normal(key, (L + 1, c_in, c_out)) / math.sqrt(c_in)


def equi_linear(w, x, L):
    """Degree-wise channel mixing: x [..., C, (L+1)^2] @ w [L+1, C, C']."""
    wl = w[jnp.asarray(l_array(L).astype(np.int32))]  # [(L+1)^2, C, C']
    return jnp.einsum("...ck,kcd->...dk", x, wl)


def gate_init(key, c, hidden=32):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c, hidden)) / math.sqrt(c),
            "w2": jax.random.normal(k2, (hidden, c)) / math.sqrt(hidden)}


def gate_apply(p, x, L):
    """Scalars gate higher degrees (equivariant nonlinearity)."""
    s = x[..., :, 0]  # l=0 channel scalars [n, C]
    g = jax.nn.sigmoid(jax.nn.silu(s @ p["w1"]) @ p["w2"])  # [n, C]
    scal = jax.nn.silu(s)
    rest = x[..., 1:] * g[..., None]
    return jnp.concatenate([scal[..., None], rest], axis=-1)


def _gate_quad(p, x, L, os: int = 2):
    """`gate_apply` evaluated on the S^2 quadrature grid (DESIGN.md §6.5).

    The gate is affine in the signal once its scalars are known — f ->
    g*f + beta*Y00 with (g, beta) functions of the l=0 channel scalars
    only — so the grid evaluation is exact at any quadrature order (an
    affine map does not raise the bandlimit); oversampling matters for
    nonlinearities applied to the *samples* themselves.  Ticks the
    sh_to_quad / quad_to_sh conversion counters: this is the Rep-level
    grid-resident gate, used where no chain is adjacent to absorb the
    gate as a fused pointwise stage (SEGNN's post-mix gate).
    """
    from repro.core.engine import _GATE_C0, _gate_coeffs
    from repro.core.rep import Rep

    s = x[..., :, 0]
    g, beta = _gate_coeffs(p, s)
    rep = Rep.from_sh(x, L).to_quad(os=os)
    gated = rep.apply_pointwise(
        lambda v: v * g[..., None, None].astype(v.dtype)
        + (beta * _GATE_C0)[..., None, None].astype(v.dtype))
    return gated.to_sh(L).data.astype(x.dtype)


def _resolve_grid_gate(cfg, Ls, Lout, batch_hint=None, share_hint=None) -> bool:
    """Resolve ``cfg.grid_gate`` to a concrete on/off for one gated chain
    workload.  'auto' consults the engine's measured gate policy
    (`engine.select_gate`, keyed like chain plans) and requires
    chain_tune='measure' — an unmeasured 'auto' stays off.  NOTE for MACE
    grid_gate is a *parameterization* choice (gate-before-mb_mix): fix it
    per checkpoint; the measured 'auto' policy is per-host but persists
    via the autotune cache, and serve warmup() seeds it."""
    mode = getattr(cfg, "grid_gate", "off")
    if mode in ("off", None, False):
        return False
    if mode in ("on", "grid", True):
        return True
    if mode != "auto":
        raise ValueError(f"unknown grid_gate {mode!r}")
    if getattr(cfg, "chain_tune", "heuristic") != "measure":
        return False
    from repro.core import engine as _engine

    return _engine.get_engine().select_gate(
        Ls, Lout, dtype=_model_dtype(cfg), batch_hint=batch_hint,
        entry_hint=("sh",) * len(Ls), share_hint=share_hint) == "grid"


def radial_basis(r, n: int, cutoff: float):
    """Bessel-like radial basis with smooth cutoff envelope. r [...]."""
    rs = jnp.clip(r, 1e-4, None)
    k = jnp.arange(1, n + 1) * math.pi / cutoff
    rb = jnp.sin(k * rs[..., None]) / rs[..., None]
    env = jnp.where(r < cutoff, 0.5 * (jnp.cos(math.pi * r / cutoff) + 1.0), 0.0)
    return rb * env[..., None]


def _pair_geometry(pos, cutoff):
    """Dense pairwise edges with cutoff mask.  pos [n,3].

    Masked pairs (self-pairs / beyond cutoff) get a *unit* placeholder
    direction: align_rotation of a zero vector is NaN, and NaN * mask = NaN
    — the masking must happen before the rotation math, not after.
    """
    n = pos.shape[0]
    diff = pos[None, :, :] - pos[:, None, :]  # r_ij = r_j - r_i
    dist = jnp.linalg.norm(diff + jnp.eye(n)[..., None], axis=-1) * (1 - jnp.eye(n))
    mask = (dist > 1e-6) & (dist < cutoff)
    rhat = diff / jnp.maximum(dist[..., None], 1e-6)
    ez = jnp.broadcast_to(jnp.asarray([0.0, 0.0, 1.0], rhat.dtype), rhat.shape)
    rhat = jnp.where(mask[..., None], rhat, ez)
    return rhat, dist, mask


# tp_impl -> engine backend (None = historical spectral default mapping,
# 'auto' = engine selection); anything not listed falls back to CG.
_TP_BACKEND = {"gaunt": None, "gaunt_fused": "fused_xla", "gaunt_auto": "auto"}


def _resolve_tp_backend(impl: str, L1: int, L2: int):
    """Map a tp_impl name to a concrete engine backend name (or None=auto)."""
    from repro.core.engine import spectral_default

    backend = _TP_BACKEND[impl]
    if impl == "gaunt":
        # historical spectral default (GauntTensorProduct's conv='auto' rule)
        backend = spectral_default(L1, L2)
    elif backend == "auto":
        backend = None
    return backend


def _model_dtype(cfg) -> str:
    """The config's Gaunt storage dtype ('float32' when absent)."""
    return getattr(cfg, "compute_dtype", "float32")


def _cast_sd(x, dts: str):
    """Cast an SH operand to the configured storage dtype at the product
    boundary (the model-side mirror of the engine's chain-entry cast rule).
    'auto' leaves operands alone — the plan resolves its own dtype."""
    if dts in ("float32", "bfloat16", "float64") and x.dtype != jnp.dtype(dts):
        return x.astype(dts)
    return x


def _tp(cfg: EquivariantConfig, L1, L2, Lout):
    """Resolve the configured tensor-product impl to a batched engine plan.

    tp_impl: 'gaunt' (historical spectral default), 'gaunt_fused'
    (collocation backend), 'gaunt_auto' (engine cost-model pick among
    grad-supporting backends), or anything else -> the CG baseline.  The
    Gaunt impls route through one batched plan (engine.plan_batch) so the
    edge x channel leading dims execute as a single fused — and optionally
    donated/sharded — invocation.
    """
    from repro.core import engine as _engine

    if cfg.tp_impl in _TP_BACKEND:
        dts = _model_dtype(cfg)
        # no donation here: model loops reuse operand buffers (edge_sh is
        # shared across layers) — donation is for callers that own the
        # buffer lifetime (e.g. the serving engine)
        bp = _engine.plan_batch(
            [(L1, L2, Lout)], kind="pairwise",
            backend=_resolve_tp_backend(cfg.tp_impl, L1, L2), dtype=dts,
            shard_spec=_engine.ShardSpec() if getattr(cfg, "shard_data", False) else None,
        )
        return lambda a, b: bp.apply([(_cast_sd(a, dts), _cast_sd(b, dts))])[0]
    return lambda a, b: cg_full_tensor_product(a, b, L1, L2, Lout)


def _tp_resident(cfg: EquivariantConfig, L1, L2, Lout):
    """A Fourier-resident tensor product for a *layer-constant* second
    operand (DESIGN.md §6), or None when the config cannot use one.

    Returns (to_rep, tp): ``to_rep(filt)`` converts the SH filter to a
    Fourier-resident Rep ONCE; ``tp(x, rep)`` runs the product with the
    filter conversion elided — a stack of n layers over one graph pays 1
    filter conversion instead of n.  The unsharded route is a 2-operand
    chain plan, so it inherits the engine's chain-backend dispatch
    (DESIGN.md §6.4): with ``cfg.chain_tune='measure'`` the measured
    autotuner may collapse the whole product into the collocation kernel
    (the resident filter then enters as a grid).  Residency composes with
    ``shard_data``: the sharded config routes the same boundary contract
    through a row-sharded batched bucket (Rep grids shard like SH rows)
    instead of falling back to per-layer filter conversions.
    """
    from repro.core import engine as _engine
    from repro.core.rep import Rep

    if (cfg.tp_impl not in ("gaunt", "gaunt_auto")
            or not getattr(cfg, "fourier_resident", True)):
        return None
    backend = _resolve_tp_backend("gaunt", L1, L2)  # spectral: fft | direct
    dts = _model_dtype(cfg)
    to_rep = lambda filt: Rep.from_sh(filt, L2).to_fourier("dense")  # noqa: E731
    if getattr(cfg, "shard_data", False):
        bp = _engine.plan_batch(
            [_engine.BatchItem(L1=L1, L2=L2, Lout=Lout,
                               options=(("boundary", ("sh", "fourier", "sh")),))],
            kind="pairwise", backend=backend, dtype=dts,
            shard_spec=_engine.ShardSpec(),
        )
        return to_rep, (lambda a, rep: bp.apply([(_cast_sd(a, dts), rep)])[0])
    tune = getattr(cfg, "chain_tune", "heuristic")

    def tp(a, rep):
        # plan per call so chain_tune='measure' measures on the REAL row
        # count (n*n*channels, known from the operand here) — plans and
        # measured selections are engine-cached, so this is lookup-cost
        # after the first call.  Measurement needs a clean trace: under a
        # whole-model jit the first trace stays on 'tree' unless the key
        # was seeded eagerly beforehand (see plan_chain's docstring).
        hint = int(np.prod(a.shape[:-1])) if tune == "measure" else None
        cp = _engine.plan_chain((L1, L2), Lout, tune=tune, batch_hint=hint,
                                entry_hint=("sh", "fourier"), dtype=dts)
        # eager apply (one dispatch per layer, like the historical boundary
        # plan): the layer loop re-binds a fresh activation every call, and
        # the trace-time conversion counters stay per-layer-visible
        return cp.apply([a, rep])

    return to_rep, tp


# --------------------------------------------------------------------------
# MACE-like force field
# --------------------------------------------------------------------------


@dataclasses.dataclass
class MaceGaunt:
    cfg: EquivariantConfig

    def init(self, key):
        c = self.cfg
        dim = num_coeffs(c.L)
        # one key per random leaf group, each consumed exactly once (reusing
        # a key across leaves makes them bitwise-correlated — see the
        # test_no_duplicate_init_leaves regression test)
        ks = jax.random.split(key, 3 + 5 * c.n_layers)
        params = {
            "species": jax.random.normal(ks[0], (c.n_species, c.channels)) * 0.5,
            "layers": [],
            "readout": {
                "w1": jax.random.normal(ks[1], (c.channels, c.hidden)) / math.sqrt(c.channels),
                "w2": jax.random.normal(ks[2], (c.hidden, 1)) / math.sqrt(c.hidden),
            },
        }
        for i in range(c.n_layers):
            k1, k2, k3, k4, k5 = ks[3 + 5 * i : 8 + 5 * i]
            params["layers"].append({
                "radial": {
                    "w1": jax.random.normal(k1, (c.n_radial, 32)) / math.sqrt(c.n_radial),
                    "w2": jax.random.normal(k2, (32, c.channels * (c.L + 1))) / 32.0,
                },
                "mix": equi_linear_init(k3, c.L, c.channels, c.channels),
                "mb_mix": equi_linear_init(k4, c.L, c.channels, c.channels),
                "mb_w": jnp.ones((c.nu, c.L + 1)) / c.nu,
                "gate": gate_init(k5, c.channels),
            })
        return params

    def features(self, params, species, pos):
        """-> per-atom invariant energy features.

        Basis residency (DESIGN.md §6): the many-body self-product runs as
        ONE chain plan per layer — A converts to the Fourier basis once
        (degree-resolved, serving all nu reweighted operands) and projects
        back once, instead of nu conversions and nu-1 round trips.  The
        layer-constant edge geometry converts once for the whole stack:
        conv_impl='general' keeps the filter Y(rhat) Fourier-resident
        (`EquivariantConv.filter_rep`); conv_impl='escn' hoists the
        alignment rotation + Wigner recursion (`geometry_rep`) out of the
        layer loop.  Both compose with ``shard_data`` — resident grids and
        Wigner blocks row-shard like SH rows.  SH checkpoints stay where
        the math demands them: equi_linear mixes and the gate act
        degree-wise on SH coefficients.
        """
        c = self.cfg
        n = pos.shape[0]
        from repro.core.engine import ShardSpec

        shard = ShardSpec() if getattr(c, "shard_data", False) else None
        # no donation: rhat is reused by every layer's conv call
        conv = EquivariantConv(c.L, c.L_edge, c.L, method=c.conv_impl,
                               shard_spec=shard)
        with jax.named_scope("mace.edge"):
            rhat, dist, mask = _pair_geometry(pos, c.cutoff)
            geom = None
            if getattr(c, "fourier_resident", True):
                if c.conv_impl == "general":
                    geom = conv.filter_rep(rhat[:, :, None, :])
                elif c.conv_impl == "escn":
                    geom = conv.geometry_rep(rhat[:, :, None, :])
        with jax.named_scope("mace.readout"):
            x = jnp.zeros((n, c.channels, num_coeffs(c.L)))
            x = x.at[..., 0].set(params["species"][species])
        # grid-resident gate policy (DESIGN.md §6.5), resolved once for the
        # stack: every layer's selfmix chain shares one workload shape
        grid_gate = _resolve_grid_gate(c, (c.L,) * c.nu, c.L,
                                       batch_hint=n * c.channels,
                                       share_hint=(0,) * c.nu)
        for lp in params["layers"]:
            with jax.named_scope("mace.radial"):
                rb = radial_basis(dist, c.n_radial, c.cutoff)  # [n,n,R]
                h = jax.nn.silu(rb @ lp["radial"]["w1"]) @ lp["radial"]["w2"]
                h = h.reshape(n, n, c.channels, c.L + 1)  # per-edge per-degree weights
            with jax.named_scope("mace.conv"):
                # messages: conv(x_j, r_ij) summed over j (channel-wise, eSCN path)
                xj = jnp.broadcast_to(x[None, :, :, :], (n, n, c.channels, x.shape[-1]))
                m = conv(xj, geom if geom is not None else rhat[:, :, None, :], w1=h)
                m = jnp.sum(m * mask[:, :, None, None], axis=1)  # [n, C, dim]
            with jax.named_scope("mace.mix_gate"):
                A = equi_linear(lp["mix"], m, c.L) + x
            with jax.named_scope("mace.chain"):
                # many-body: nu-fold Gaunt self-product, per-degree weights
                mb_kw = dict(
                    weights=[jnp.broadcast_to(w, (n, c.channels, c.L + 1))
                             for w in lp["mb_w"]],
                    shard_spec=shard,  # the chain route honors sharding directly
                    tune=getattr(c, "chain_tune", "heuristic"),
                    dtype=_model_dtype(c),  # storage precision (chain-entry cast)
                )
                if grid_gate:
                    # grid-resident gate (DESIGN.md §6.5): the affine gate
                    # runs as a pointwise stage on the selfmix chain's
                    # resident product grid — the whole many-body stage is
                    # one region with one entry + one exit conversion.  The
                    # gate cannot cross the mb_mix channel mix, so this
                    # variant gates B *before* the mix (an equally
                    # expressive reparameterization — fix grid_gate per
                    # checkpoint).
                    B = manybody_selfmix(A, c.L, c.nu, Lout=c.L,
                                         gate_params=lp["gate"], **mb_kw)
                else:
                    B = manybody_selfmix(A, c.L, c.nu, Lout=c.L, **mb_kw)
            with jax.named_scope("mace.mix_gate"):
                y = equi_linear(lp["mb_mix"], B, c.L)
                x = x + (y if grid_gate else gate_apply(lp["gate"], y, c.L))
        with jax.named_scope("mace.readout"):
            return x[..., 0]  # invariant channels [n, C]

    def _readout(self, params, feat, mask=None):
        """Per-atom energies through the readout MLP, summed over ``mask``
        [n] (all atoms when None)."""
        with jax.named_scope("mace.readout"):
            e_atom = (jax.nn.silu(feat @ params["readout"]["w1"])
                      @ params["readout"]["w2"])[:, 0]
            return jnp.sum(e_atom if mask is None else e_atom * mask)

    def energy(self, params, species, pos):
        return self._readout(params, self.features(params, species, pos))

    def energy_masked(self, params, species, pos, mask):
        """Energy of the atoms selected by ``mask`` [n] (serving: padded
        slots place ghost atoms beyond the cutoff and mask them out here)."""
        return self._readout(params, self.features(params, species, pos),
                             mask)

    def energy_forces(self, params, species, pos):
        e, g = jax.value_and_grad(self.energy, argnums=2)(params, species, pos)
        return e, -g

    def loss(self, params, batch, w_e=1.0, w_f=10.0):
        def one(species, pos, e_ref, f_ref):
            e, f = self.energy_forces(params, species, pos)
            return w_e * (e - e_ref) ** 2 + w_f * jnp.mean((f - f_ref) ** 2)

        return jnp.mean(jax.vmap(one)(batch["species"], batch["pos"],
                                      batch["energy"], batch["forces"]))


# --------------------------------------------------------------------------
# SEGNN-like N-body
# --------------------------------------------------------------------------


@dataclasses.dataclass
class SegnnNBody:
    cfg: EquivariantConfig

    def init(self, key):
        c = self.cfg
        # 5 keys per layer, each consumed once: sharing k3 between mix and
        # self_mix (and k1 between radial and gate) made those leaves
        # bitwise-correlated at init
        ks = jax.random.split(key, 2 + 5 * c.n_layers)
        params = {
            "embed": equi_linear_init(ks[0], c.L, 2, c.channels),  # charge,|v| + v irreps
            "out": equi_linear_init(ks[1], c.L, c.channels, 1),
            "layers": [],
        }
        for i in range(c.n_layers):
            k1, k2, k3, k4, k5 = ks[2 + 5 * i : 7 + 5 * i]
            params["layers"].append({
                "radial": {
                    "w1": jax.random.normal(k1, (c.n_radial, 32)) / math.sqrt(c.n_radial),
                    "w2": jax.random.normal(k2, (32, c.channels * (c.L + 1))) / 32.0,
                },
                "mix": equi_linear_init(k3, c.L, c.channels, c.channels),
                "self_mix": equi_linear_init(k4, c.L, c.channels, c.channels),
                "gate": gate_init(k5, c.channels),
            })
        return params

    def _node_feats(self, charge, vel):
        """2-channel input irreps: ch0 = (charge; velocity as l=1),
        ch1 = (|v|; velocity)."""
        n = charge.shape[0]
        L = self.cfg.L
        x = jnp.zeros((n, 2, num_coeffs(L)))
        x = x.at[:, 0, 0].set(charge)
        x = x.at[:, 1, 0].set(jnp.linalg.norm(vel, axis=-1))
        # l=1 slot order (m=-1,0,1) ~ (y,z,x)
        v_sh = jnp.stack([vel[:, 1], vel[:, 2], vel[:, 0]], axis=-1)
        x = x.at[:, 0, 1:4].set(v_sh)
        x = x.at[:, 1, 1:4].set(v_sh)
        return x

    def forward(self, params, charge, pos, vel):
        c = self.cfg
        n = pos.shape[0]
        rhat, dist, mask = _pair_geometry(pos, cutoff=1e9)  # fully connected
        x = equi_linear(params["embed"], self._node_feats(charge, vel), c.L)
        edge_sh = real_sph_harm_jax(c.L_edge, rhat)  # [n,n,(Le+1)^2]
        # the edge filter is layer-constant: with the resident path it
        # converts to the Fourier basis ONCE for the whole layer stack
        # (n_layers - 1 conversions elided) instead of once per layer
        res = _tp_resident(c, c.L, c.L_edge, c.L)
        if res is not None:
            to_rep, tp_res = res
            edge_rep = to_rep(edge_sh[:, :, None, :])  # [n,n,1,...] broadcasts over C
            tp = lambda a: tp_res(a, edge_rep)  # noqa: E731
        else:
            tp0 = _tp(c, c.L, c.L_edge, c.L)
            tp = lambda a: tp0(a, jnp.broadcast_to(  # noqa: E731
                edge_sh[:, :, None, :], (n, n, c.channels, edge_sh.shape[-1])))
        # SEGNN's gate sits after the channel mix, so no adjacent chain can
        # absorb it; grid_gate='on' evaluates it on the S^2 quadrature grid
        # (`_gate_quad` — exact, same function as 'off') to keep the
        # Rep-level residency path exercised.  It adds a quadrature
        # conversion pair rather than eliding one, so the measured 'auto'
        # policy never selects it here — 'auto' resolves to off.
        gg = getattr(c, "grid_gate", "off")
        use_quad_gate = gg in ("on", "grid", True)
        if gg not in ("off", "on", "grid", "auto", True, False, None):
            raise ValueError(f"unknown grid_gate {gg!r}")
        for lp in params["layers"]:
            rb = radial_basis(dist, c.n_radial, cutoff=10.0)
            h = jax.nn.silu(rb @ lp["radial"]["w1"]) @ lp["radial"]["w2"]
            h = h.reshape(n, n, c.channels, c.L + 1)
            xj = jnp.broadcast_to(x[None], (n, n, c.channels, x.shape[-1]))
            hw = expand_degree_weights(h, c.L)
            m = tp(xj * hw)
            m = jnp.sum(m * mask[:, :, None, None], axis=1)[..., : num_coeffs(c.L)]
            y = equi_linear(lp["mix"], m, c.L)
            if use_quad_gate:
                x = x + _gate_quad(lp["gate"], y, c.L)
            else:
                x = x + gate_apply(lp["gate"], y, c.L)
            x = x + equi_linear(lp["self_mix"], x, c.L)
        out = equi_linear(params["out"], x, c.L)[:, 0]  # [n, dim]
        dsh = out[:, 1:4]  # l=1 block (y,z,x)
        dpos = jnp.stack([dsh[:, 2], dsh[:, 0], dsh[:, 1]], axis=-1)
        return pos + dpos

    def loss(self, params, batch):
        def one(charge, pos, vel, target):
            pred = self.forward(params, charge, pos, vel)
            return jnp.mean((pred - target) ** 2)

        return jnp.mean(jax.vmap(one)(batch["charge"], batch["pos"],
                                      batch["vel"], batch["target"]))


# --------------------------------------------------------------------------
# EquiformerV2-like Selfmix (Equivariant Feature Interaction)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class SelfmixLayer:
    """x -> x + mix(GauntTP(w1 . x, w2 . x)) — the paper's added layer.

    With ``resident`` (default) the spectral 'gaunt' impl runs as a chain
    plan: the two operands are the SAME tensor under different per-degree
    weights, so ONE degree-resolved conversion serves both (DESIGN.md §6) —
    one sh->Fourier elided per call versus the looped per-operand path.
    The residual and channel mix are degree-diagonal SH ops, so the layer
    output checkpoints back to SH (as every gate/mix boundary must).

    ``shard_spec`` row-shards the layer's product over the mesh's data axes
    on BOTH routes (the resident chain and the batched fallback) — residency
    no longer forces single-device execution.
    """

    L: int
    channels: int
    tp_impl: str = "gaunt"
    resident: bool = True
    shard_spec: object = None
    # chain-backend policy (DESIGN.md §6.4): 'measure' lets the engine's
    # measured autotuner collapse the shared-operand chain into the
    # collocation kernel when that wins on this host
    tune: str = "heuristic"
    # Gaunt storage precision ('float32' | 'bfloat16' | 'auto', §3.6)
    compute_dtype: str = "float32"

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w1": jnp.ones((self.L + 1,)),
            "w2": jnp.ones((self.L + 1,)),
            "w3": jnp.ones((2 * self.L + 1,)),
            "mix": equi_linear_init(k3, self.L, self.channels, self.channels),
        }

    def __call__(self, params, x):
        return x + self.interaction(params, x)

    def interaction(self, params, x):
        """The layer without its residual: mix(GauntTP(w1 . x, w2 . x))."""
        L = self.L
        if self.tp_impl == "gaunt" and self.resident:
            from repro.core import engine as _engine

            # under 'measure', mirror the real call in the measurement: the
            # layer's actual row count and the shared-operand [x, x] pattern
            hint = (int(np.prod(x.shape[:-1]))
                    if self.tune == "measure" else None)
            cp = _engine.plan_chain([L, L], Lout=L, shard_spec=self.shard_spec,
                                    tune=self.tune, batch_hint=hint,
                                    share_hint=(0, 0) if hint else None,
                                    dtype=self.compute_dtype)
            y = cp.apply_jit([x, x], weights=[params["w1"], params["w2"]],
                             w_out=params["w3"][: L + 1])
        elif self.tp_impl in _TP_BACKEND:
            from repro.core import engine as _engine

            xd = _cast_sd(x, self.compute_dtype)
            bp = _engine.plan_batch([(L, L, L)], kind="pairwise",
                                    backend=_resolve_tp_backend(self.tp_impl, L, L),
                                    shard_spec=self.shard_spec,
                                    dtype=self.compute_dtype)
            y = bp.apply([(xd, xd)],
                         weights=[(params["w1"], params["w2"],
                                   params["w3"][: L + 1])])[0]
        else:  # cg baseline
            xw = x * expand_degree_weights(params["w1"], L)
            yw = x * expand_degree_weights(params["w2"], L)
            y = cg_full_tensor_product(xw, yw, L, L, L) * expand_degree_weights(
                params["w3"][: L + 1], L)
        return equi_linear(params["mix"], y, L)
