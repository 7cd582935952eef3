"""Unified Gaunt execution engine — one plan/dispatch layer for every Gaunt op.

This repo grew several concrete realizations of the paper's O(L^3) Gaunt
tensor product (dense/packed spectral conversions x fft/direct convolution,
the fused collocation kernel, the eSCN rotation-aligned convolution).  The
engine makes them *backends* behind a single planning API (DESIGN.md §4):

    plan = engine.plan(L1, L2, Lout, kind="pairwise", batch_hint=4096)
    out  = plan.apply(x1, x2, w1=w1)          # paper's w_{l1} w_{l2} w_l hooks

A plan is keyed by ``(L1, L2, Lout, kind, batch_hint, dtype)`` (+ kind
specific extras) and resolved to a registered backend:

    kind         backends
    pairwise     dense_einsum | fft | direct | packed | rfft | fused_xla | fused_pallas
    conv_filter  escn_aligned + every pairwise backend (filter materialized)
    manybody     dense_einsum | fft | direct | packed | rfft
    channel_mix  dense_einsum | fused_xla

Backends carry capability flags (grad support, dtype support, whether Pallas
must run in interpret mode off-TPU); selection is either a closed-form cost
model (``tune="heuristic"``) or measured wall-time on synthetic inputs with
an in-process autotune cache (``tune="measure"``).  Plans and their constants
are cached: planning twice is free, and all numpy precompute lives in the
central :mod:`repro.core.constants` cache.

Thin public wrappers (`GauntTensorProduct`, `EquivariantConv`,
`manybody_gaunt_product`, `gaunt_tp_channel_mix`, the model `_tp` hook) keep
their historical signatures and route here.

Basis residency (DESIGN.md §6): spectral plans accept ``options={"boundary":
(in1, in2, out)}`` with entries in {'sh', 'fourier'} — 'fourier' operands
arrive as Fourier-resident :class:`repro.core.rep.Rep` grids (their SH->F
conversion is skipped), and a 'fourier' output returns a Rep without the
final projection.  ``engine.plan_chain(Ls, Lout)`` plans a whole chained
product (the many-body tree, selfmix stacks): every operand is converted at
most once — identical operands share one (degree-resolved) conversion even
under different per-degree weights — grids combine by 2D convolution, and a
single projection happens at the chain exit, eliminating the interior
``fourier_to_sh . sh_to_fourier`` pairs the looped per-product path pays.
Chains additionally carry their own backend dispatch (DESIGN.md §6.4,
:data:`CHAIN_BACKENDS`): the resident 'tree', the per-product 'looped'
fold, or the n-way collocation kernel ('fused_xla' / 'fused_pallas' — ONE
MXU-resident pallas_call for the whole chain), selected by the measured
autotuner under ``tune='measure'`` and keyed like plans.

Batched execution (DESIGN.md §5): ``engine.plan_batch(items, ...)`` buckets a
ragged multi-degree workload (items sharing an (L1, L2, Lout) signature) into
one padded fused invocation per bucket, with operand buffer donation on the
hot path and sharding-aware dispatch over the mesh's data axes:

    bp  = engine.plan_batch([(2, 2, 4, nE), (1, 1, 2, nN)], donate=True,
                            shard_spec=ShardSpec(mode="shard_map"))
    o1, o2 = bp.apply([(x1, x2), (a, b)])

Residency and batching COMPOSE (no "resident OR scaled" fork): buckets key
on (degree signature, basis/geometry options), so batched items may carry
Fourier-resident ``Rep`` operands (their half/dense grids flatten, concat,
pad, shard, and donate like SH rows), a 'fourier' output boundary returns
resident Reps per item, and ``plan_chain(..., donate=..., shard_spec=...)``
runs whole chains donated/sharded with <= 1 conversion per operand.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import constants
from .irreps import num_coeffs

__all__ = [
    "PlanKey",
    "Backend",
    "GauntPlan",
    "BatchItem",
    "ShardSpec",
    "BatchedGauntPlan",
    "ChainPlan",
    "CHAIN_BACKENDS",
    "GauntEngine",
    "register_backend",
    "available_backends",
    "spectral_default",
    "expand_degree_weights",
    "get_engine",
    "plan",
    "plan_batch",
    "plan_chain",
]

KINDS = ("pairwise", "conv_filter", "manybody", "channel_mix")

_RDTYPE = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}
_CDTYPE = {"float32": "complex64", "bfloat16": "complex64", "float64": "complex128"}


def _dtype_str(dtype) -> str:
    """Normalize any dtype spec (incl. the wrappers' cdtype) to a plan key.

    float64/complex128 requests are demoted to float32 when jax runs with
    x64 disabled (the default): arrays would silently degrade to f32 anyway,
    and keying plans on the *requested* precision would hash
    otherwise-identical plans to different cache entries and build complex128
    constants that every apply immediately downcasts.
    """
    s = jnp.dtype(dtype).name
    if s.startswith("complex"):
        s = "float64" if s == "complex128" else "float32"
    if s == "float64" and not jax.config.jax_enable_x64:
        return "float32"
    if s not in _RDTYPE:
        raise ValueError(f"unsupported dtype {s!r} (expected one of {sorted(_RDTYPE)})")
    return s


def _acc_dtype_str(storage: str) -> str:
    """Accumulation dtype for a storage dtype: always >= f32, never below
    the storage precision — f32 for f32/bf16 storage, f64 for f64 storage.
    The one place the storage/accumulation split is defined (DESIGN.md §3.6).
    """
    return "float64" if storage == "float64" else "float32"


def spectral_default(*Ls: int) -> str:
    """The dense-spectral conv crossover (DESIGN.md §3.2): shift-and-add
    'direct' wins on small grids, 'fft' above.  The ONE home of the
    historical ``conv='auto'`` rule — wrappers, models, and benches all
    call this instead of re-stating the threshold."""
    return "direct" if max(Ls) <= 4 else "fft"


def expand_degree_weights(w, L: int):
    """w [..., L+1] per-degree -> [..., (L+1)^2] packed broadcast.

    The canonical implementation (gaunt.py re-exports it for back-compat).
    Built from per-degree broadcasts of ``w[..., l:l+1]`` to width 2l+1,
    concatenated: the same values as the gather ``w[..., l_array(L)]``, but
    its transpose is a slice-and-sum that XLA fuses.  The gather's transpose
    is a scatter-add, which a force step (-dE/dpos through the per-edge
    radial weights) would run as a serial while loop on the TPU.  ``take``,
    fancy indexing and ``repeat`` with an array of repeats all lower to that
    gather; a 0/1 [L+1, (L+1)^2] matmul costs several MXU passes at
    ``highest`` precision.
    """
    lead = jnp.shape(w)[:-1]
    return jnp.concatenate(
        [jnp.broadcast_to(w[..., l:l + 1], lead + (2 * l + 1,))
         for l in range(L + 1)], axis=-1)


def _wmul(x, w, L: int):
    return x if w is None else x * expand_degree_weights(w, L).astype(x.dtype)


def _chain_entry_cast(x, rd):
    """THE chain-entry dtype rule — one rule for every chain backend, not
    backend-dependent drift: a non-resident SH operand arriving in a storage
    dtype other than the plan's is cast ONCE here, at entry.  Fourier-
    resident operands are untouched (residency is complex and complex has no
    bf16; the plan's storage dtype re-applies at the SH exit)."""
    return x if jnp.result_type(x) == jnp.dtype(rd) else x.astype(rd)


# --------------------------------------------------------------------------
# the affine gate (DESIGN.md §6.5) — models.gate_apply, given its l=0 scalars
# --------------------------------------------------------------------------

# Y_00 = 1/(2 sqrt(pi)): one unit of SH coefficient 0 is this constant on S^2
_GATE_C0 = 0.5 / math.sqrt(math.pi)


def _gate_mlp(p, s):
    """The gate's scalar MLP: l=0 scalars s [..., C] -> gate g [..., C]."""
    return jax.nn.sigmoid(jax.nn.silu(s @ p["w1"]) @ p["w2"])


def _gate_coeffs(p, s):
    """(g, beta): models.gate_apply in its affine form.

    Given the l=0 scalars s, the gate is  gate(x) = g*x + beta*e0  on packed
    SH coefficients — equivalently  gate(f) = g*f + beta*Y00  pointwise on
    sphere samples — with beta = silu(s) - g*s, so coefficient 0 lands
    exactly on silu(s) while every l > 0 coefficient scales by g.  Being
    affine in the signal (g and beta depend only on s), the gate commutes
    with every linear stage (projection, degree truncation), which is what
    lets it fuse into the collocation kernel as a per-row scale+bias on the
    VMEM-resident product grid — exactly, with zero aliasing.
    """
    g = _gate_mlp(p, s)
    return g, jax.nn.silu(s) - g * s


def _gate_sh(p, x):
    """Apply the gate on packed SH coefficients (== models.gate_apply)."""
    s = x[..., 0]
    g = _gate_mlp(p, s)
    return (x * g[..., None]).at[..., 0].set(jax.nn.silu(s))


def _gate_rep(p, rep):
    """Apply the gate on a Fourier-resident Rep WITHOUT leaving the basis.

    The l=0 scalars come from the z-transform's l0 row — the torus (0,0)
    coefficient is NOT the spherical mean (higher-degree S_l0 modes have
    nonzero torus means), so a bare grid read would be wrong.  The whole
    grid then scales by g, and beta*Y00 lands on the (u,v) = (0,0) mode
    (a constant on the grid IS a pure (0,0) torus coefficient).
    """
    F = rep.data
    L = rep.L
    z0 = jnp.asarray((constants.z_half if rep.form == "half"
                      else constants.z_dense)(L, 0, F.dtype.name)[:, :, 0])
    s = jnp.einsum("...uv,uv->...", F, z0).real
    g, beta = _gate_coeffs(p, s)
    F = F * g[..., None, None].astype(F.dtype)
    vc = 0 if rep.form == "half" else L
    F = F.at[..., L, vc].add((beta * _GATE_C0).astype(F.dtype))
    return dataclasses.replace(rep, data=F)


# --------------------------------------------------------------------------
# plan keys and backend registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Identity of a planned Gaunt op (hashable; the plan-cache key).

    ``dtype`` is the *storage* dtype — what operands, SH-side constants and
    outputs are held in ('float32' | 'bfloat16' | 'float64').  The
    accumulation dtype is derived, never stored: always >= f32
    (``acc_dtype``), so a bf16 key means bf16 bytes moved with f32 math.
    """

    L1: int
    L2: int
    Lout: int
    kind: str = "pairwise"
    batch_hint: int | None = None
    dtype: str = "float32"
    # kind/backend-specific knobs, as a sorted tuple of (name, value) pairs:
    # manybody carries ("Ls", (...)); packed carries ("conv", "fft"|"direct").
    extra: tuple = ()

    @property
    def acc_dtype(self) -> str:
        return _acc_dtype_str(self.dtype)

    def opt(self, name: str, default=None):
        return dict(self.extra).get(name, default)

    def with_dtype(self, dtype: str) -> "PlanKey":
        """The same op at a different storage dtype — the 'key family' the
        precision-aware autotuner walks (f32 <-> bf16 siblings)."""
        return dataclasses.replace(self, dtype=dtype)


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered Gaunt realization with capability flags."""

    name: str
    kinds: frozenset
    build: Callable[[PlanKey], Callable] = dataclasses.field(repr=False, compare=False, default=None)
    cost: Callable[[PlanKey], float] = dataclasses.field(repr=False, compare=False, default=None)
    supports_grad: bool = True
    dtypes: frozenset = frozenset({"float32", "bfloat16", "float64"})
    needs_interpret: bool = False  # Pallas: off-TPU only via (slow) interpret mode
    # spectral backends can take/return Fourier-resident operands (Rep grids)
    fourier_boundary: bool = False
    # conv_filter backends that accept precomputed WignerBlocks geometry
    wigner_geometry: bool = False

    def eligible(self, key: PlanKey, requires_grad: bool) -> bool:
        if key.dtype not in self.dtypes:
            return False
        if requires_grad and not self.supports_grad:
            return False
        bound = key.opt("boundary")
        if bound and "fourier" in bound and not self.fourier_boundary:
            return False
        if key.opt("geometry") and not self.wigner_geometry:
            return False
        if key.kind in self.kinds:
            return True
        # any pairwise backend can serve conv_filter by materializing Y(rhat)
        return key.kind == "conv_filter" and "pairwise" in self.kinds


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    return backend


def available_backends(kind: str = "pairwise", dtype: str = "float32",
                       requires_grad: bool = True) -> list[str]:
    # same normalization as plan(): a float64 query on an x64-disabled runtime
    # must see the float32 capability set, not a phantom-precision one
    key = PlanKey(1, 1, 2, kind=kind, dtype=_dtype_str(dtype))
    return [b.name for b in _REGISTRY.values() if b.eligible(key, requires_grad)]


@dataclasses.dataclass(frozen=True)
class GauntPlan:
    """A resolved (key, backend) pair; ``apply`` runs the op."""

    key: PlanKey
    backend: str
    apply: Callable = dataclasses.field(repr=False, compare=False)

    def describe(self) -> str:
        k = self.key
        return (f"{k.kind}(L1={k.L1}, L2={k.L2}, Lout={k.Lout}, "
                f"dtype={k.dtype}, batch_hint={k.batch_hint}) -> {self.backend}")


# --------------------------------------------------------------------------
# batched execution (DESIGN.md §5): ragged multi-degree workloads in one
# padded invocation per degree bucket, with donation + sharded dispatch
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BatchItem:
    """One entry of a batched workload: a degree signature + expected rows.

    ``size`` is a planning hint (feeds the bucket's batch_hint); the actual
    row count comes from the arrays at apply time.  manybody items carry
    ``Ls`` instead of (L1, L2).
    """

    L1: int | None = None
    L2: int | None = None
    Lout: int | None = None
    Ls: tuple | None = None
    size: int | None = None
    options: tuple = ()

    def signature(self) -> tuple:
        return (self.L1, self.L2, self.Lout, self.Ls, self.options)


def _as_batch_item(it) -> BatchItem:
    if isinstance(it, BatchItem):
        return it
    if isinstance(it, dict):
        d = dict(it)
        if "options" in d:
            d["options"] = tuple(sorted(dict(d["options"]).items()))
        if "Ls" in d and d["Ls"] is not None:
            d["Ls"] = tuple(int(L) for L in d["Ls"])
        return BatchItem(**d)
    it = tuple(it)
    if len(it) == 3:
        return BatchItem(L1=it[0], L2=it[1], Lout=it[2])
    if len(it) == 4:
        return BatchItem(L1=it[0], L2=it[1], Lout=it[2], size=it[3])
    raise ValueError(f"batch item {it!r}: expected (L1, L2, Lout[, size]), "
                     "a dict, or a BatchItem")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How a batched apply is laid out over a device mesh.

    mesh : a jax Mesh, or None to use the launcher-registered activation
           mesh (``distributed.sharding.set_activation_mesh``); with neither,
           the spec is inert and execution stays single-device.
    axes : mesh axis names eligible to shard the row axis (dim0 of every
           flattened operand); the subset present in the mesh is used.
    mode : 'constraint' — pjit-style ``with_sharding_constraint`` on operands
           and outputs (SPMD partitioner does the rest); 'shard_map' — the
           bucket body runs per-shard under ``shard_map`` (row-parallel by
           construction, so no collectives are needed).
    """

    mesh: object = None
    axes: tuple = ("pod", "data")
    mode: str = "constraint"

    def resolve(self):
        """-> (mesh, dp_axes) or (None, ()) when no mesh is available.

        The mesh comes back with every axis Auto: the row layout is imposed
        with ``with_sharding_constraint``/``shard_map`` specs, which JAX only
        accepts on Auto axes, and ``jax.make_mesh`` builds Explicit axes by
        default."""
        from repro.distributed import sharding as _sh  # lazy: keep core light

        mesh = self.mesh if self.mesh is not None else _sh.get_activation_mesh()
        if mesh is None:
            return None, ()
        mesh = mesh.update(
            axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
        axes = _sh.dp_axes(mesh, tuple(self.axes))
        return mesh, axes


def _split_leads(leads: list) -> tuple:
    """Split operand leading shapes into (row prefix, inner broadcast dims).

    The *prefix* is the longest run of leading dims on which every operand
    agrees exactly (after numpy-style right-aligned rank padding) — those
    flatten into the row axis.  The remaining *inner* dims are where the
    operands exploit broadcasting (e.g. one edge direction against C channel
    features); they pass through to the backend, which broadcasts natively —
    flattening them instead would materialize the broadcast and repeat
    shared per-row work (the eSCN Wigner blocks) per inner element.
    """
    full = jnp.broadcast_shapes(*leads)
    n = len(full)
    padded = [(1,) * (n - len(ld)) + tuple(ld) for ld in leads]
    k = 0
    while k < n and all(p[k] == full[k] for p in padded):
        k += 1
    return full[:k], full[k:]


def _n_operands(kind: str, item: BatchItem) -> int:
    return len(item.Ls) if kind == "manybody" else 2


def _weight_degrees(kind: str, item: BatchItem) -> tuple:
    """Per-weight-slot packed width (L+1) for an item's apply signature."""
    if kind == "manybody":
        return tuple(L + 1 for L in item.Ls)
    return (item.L1 + 1, item.L2 + 1, item.Lout + 1)


def _bucket_runner(plan: GauntPlan, kind: str) -> Callable:
    """The (ops, ws) -> out body executed once per bucket invocation."""
    if kind == "manybody":
        def run(ops, ws):
            ws_list = list(ws)
            if all(w is None for w in ws_list):
                ws_list = None
            return plan.apply(list(ops), ws_list)
        return run

    def run(ops, ws):
        return plan.apply(ops[0], ops[1], *ws)

    return run


def _op_parts(op) -> tuple:
    """Decompose a (possibly structured) operand into row-layout leaves.

    Returns ``(leaves, event_ranks, rebuild)``: each leaf batches over its
    leading dims, with ``event_rank`` trailing dims belonging to the math —
    1 for packed SH rows and raw conv directions, 2 for Fourier coefficient
    grids (Rep) and Wigner rotation blocks.  ``rebuild(leaves)`` reassembles
    the operand around new (flattened/concatenated/padded) leaves, so half-
    Hermitian grids concat/pad/slice through the bucket layout exactly like
    SH rows (DESIGN.md §5.1/§6).
    """
    from .conv import WignerBlocks  # lazy: conv routes through the engine
    from .rep import Rep

    if isinstance(op, Rep):
        meta = (op.L, op.basis, op.form)
        return [op.data], (2,), lambda ls: Rep(ls[0], *meta)
    if isinstance(op, WignerBlocks):
        return list(op.blocks), (2,) * len(op.blocks), \
            lambda ls: WignerBlocks(tuple(ls))
    return [op], (1,), lambda ls: ls[0]


def _norm_operand(op, j: int, kind: str, item: BatchItem, form: str):
    """Validate/canonicalize one operand before leaf decomposition: SH Reps
    unwrap to their data, Fourier Reps check their bandlimit against the
    item's degree and coerce to the bucket plan's storage form."""
    from .rep import Rep

    if isinstance(op, Rep):
        if op.basis == "sh":
            return op.data
        degs = item.Ls if kind == "manybody" else (item.L1, item.L2)
        if j < len(degs) and op.L != degs[j]:
            raise ValueError(f"operand {j}: resident bandlimit {op.L} != "
                             f"planned degree {degs[j]}")
        return op.with_form(form)
    return op


def _bucket_batch_body(run: Callable, kind: str, item: BatchItem,
                       granularity: int, rd, form: str, item_ops, item_ws):
    """Trace-time batching: flatten/broadcast/concat/pad the per-item
    operands, execute the core once, slice per-item results back out.

    Operands may be plain SH arrays, Fourier-resident ``Rep`` grids, or
    precomputed ``WignerBlocks`` geometry — each decomposes into row-layout
    leaves (`_op_parts`).  Every item's leading dims split into (row prefix,
    inner broadcast dims) via `_split_leads`; rows concatenate across items
    and tail-pad to `granularity`.  All of this is shape logic + cheap jnp
    ops that XLA fuses into the single bucket dispatch.  A bucket whose plan
    has a 'fourier' output boundary returns resident Reps per item.
    """
    from .rep import Rep

    n_ops = _n_operands(kind, item)
    wdeg = _weight_degrees(kind, item)
    item_parts = []   # per item: per operand (leaves, event_ranks, rebuild)
    for ops_i in item_ops:
        item_parts.append([_op_parts(_norm_operand(op, j, kind, item, form))
                           for j, op in enumerate(ops_i)])
    # structure check per EVENT-RANK signature, not leaf count: a Fourier
    # Rep and a plain SH array both decompose to one leaf, but their grids
    # cannot concatenate — catch the mix here with a real message instead
    # of an opaque downstream concat shape error
    n_leaves = [len(item_parts[0][j][0]) for j in range(n_ops)]
    struct0 = [p[1] for p in item_parts[0]]
    for t, parts in enumerate(item_parts):
        if [p[1] for p in parts] != struct0:
            raise ValueError(f"item {t}: operand structure (Rep/WignerBlocks/"
                             "array mix) differs from the bucket's first item "
                             f"({[p[1] for p in parts]} vs {struct0})")
    # pass 1: per-item lead splits; concatenation needs identical post-row
    # shapes, so if items disagree on inner dims fall back to a full flatten
    splits = []
    for parts_i, ws_i in zip(item_parts, item_ws):
        leads = [jnp.shape(leaf)[: len(jnp.shape(leaf)) - er]
                 for leaves, ers, _ in parts_i
                 for leaf, er in zip(leaves, ers)]
        prefix, inner = _split_leads(leads)
        # weights usually broadcast INTO prefix+inner (they are materialized
        # per row below).  A weight whose lead extends BEYOND the operands'
        # broadcast shape broadens the output instead (plan.apply contract:
        # 'w [..., L+1]'), which the row layout cannot express — degrade the
        # item to all-inner (rows=1) and let the backend broadcast natively.
        w_leads = [jnp.shape(w)[:-1] for w in ws_i if w is not None]
        pi = prefix + inner
        if any(jnp.broadcast_shapes(wl, pi) != pi for wl in w_leads):
            prefix, inner = (), jnp.broadcast_shapes(pi, *w_leads)
        splits.append((prefix, inner))
    if len({inner for _, inner in splits}) > 1:
        splits = [(prefix + inner, ()) for prefix, inner in splits]
    prefixes, inner_leads, rows = [], [], []
    # per operand, per leaf: per item [rows, *inner, *event]
    leaf_cols = [[[] for _ in range(n_leaves[j])] for j in range(n_ops)]
    ws_used = [any(ws[j] is not None for ws in item_ws)
               for j in range(len(wdeg))]
    for t, parts_i in enumerate(item_parts):
        prefix, inner = splits[t]
        r = int(np.prod(prefix)) if prefix else 1
        prefixes.append(prefix)
        inner_leads.append(inner)
        rows.append(r)
        np_ = len(prefix)
        rank = np_ + len(inner)
        for j, (leaves, ers, _) in enumerate(parts_i):
            for q, (x, er) in enumerate(zip(leaves, ers)):
                shp = jnp.shape(x)
                ev = tuple(shp[len(shp) - er:])
                pl = (1,) * (rank - (len(shp) - er)) + tuple(shp[: len(shp) - er])
                x = jnp.reshape(x, pl + ev)
                x = jnp.broadcast_to(x, prefix + pl[np_:] + ev)
                leaf_cols[j][q].append(jnp.reshape(x, (r,) + pl[np_:] + ev))
    if len(item_ops) > 1:
        # same broadcast inner dims, but a leaf may still carry an
        # un-materialized size-1 inner dim on one item only
        for j in range(n_ops):
            for q, col in enumerate(leaf_cols[j]):
                er = item_parts[0][j][1][q]
                if len({jnp.shape(x)[1: x.ndim - er] for x in col}) > 1:
                    for t, x in enumerate(col):
                        ev = tuple(jnp.shape(x)[x.ndim - er:])
                        col[t] = jnp.broadcast_to(
                            x, (rows[t],) + inner_leads[t] + ev)
    # weights: flatten each used slot per item (ones where absent) so the
    # concatenation stays row-aligned with the operands
    ws_cat = []
    for j, used in enumerate(ws_used):
        if not used:
            ws_cat.append(None)
            continue
        cols = []
        for t, ws in enumerate(item_ws):
            w = ws[j]
            if w is None:
                cols.append(jnp.ones((rows[t],) + inner_leads[t] + (wdeg[j],),
                                     dtype=rd))
            else:
                w = jnp.broadcast_to(w, prefixes[t] + inner_leads[t] + (wdeg[j],))
                cols.append(jnp.reshape(
                    w, (rows[t],) + inner_leads[t] + (wdeg[j],)).astype(rd))
        ws_cat.append(jnp.concatenate(cols, axis=0))
    total = sum(rows)
    pad = -(-total // granularity) * granularity - total
    ops_cat = []
    for j in range(n_ops):
        _, ers, rebuild = item_parts[0][j]
        cat = []
        for q, col in enumerate(leaf_cols[j]):
            x = jnp.concatenate(col, axis=0)
            if pad:
                if kind == "conv_filter" and j == 1 and ers[q] == 1:
                    # raw conv directions pad with e_z, not zeros —
                    # align_rotation of a zero vector is NaN (precomputed
                    # Wigner blocks and grids pad with inert zero rows)
                    ez = jnp.broadcast_to(jnp.asarray([0.0, 0.0, 1.0], x.dtype),
                                          (pad,) + x.shape[1:])
                    x = jnp.concatenate([x, ez], axis=0)
                else:
                    x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            cat.append(x)
        ops_cat.append(rebuild(cat))
    if pad:
        ws_cat = [None if w is None else
                  jnp.pad(w, [(0, pad)] + [(0, 0)] * (w.ndim - 1),
                          constant_values=1.0)
                  for w in ws_cat]
    out = run(tuple(ops_cat), tuple(ws_cat))
    out_leaf = out.data if isinstance(out, Rep) else out
    res, off = [], 0
    for t in range(len(item_ops)):
        o = jnp.reshape(out_leaf[off:off + rows[t]],
                        prefixes[t] + out_leaf.shape[1:])
        if isinstance(out, Rep):
            o = Rep(o, out.L, out.basis, out.form)
        res.append(o)
        off += rows[t]
    return tuple(res)


def _row_constraint(mesh, dp: tuple) -> Callable:
    """The one home of the rank-aware row rule: dim0 of a leaf shards over
    the dp axes, everything else replicates (used by `_shard_rows`'
    constraint mode and the chain plans' grid/exit constraints)."""
    from repro.distributed.sharding import row_sharding

    def con(a):
        return jax.lax.with_sharding_constraint(
            a, row_sharding(mesh, jnp.ndim(a), dp))

    return con


def _shard_rows(run: Callable, mesh, dp: tuple, mode: str) -> Callable:
    """Wrap a row-layout callable in sharded dispatch over the mesh's data
    axes.  Every array leaf entering/leaving ``run`` is [rows, ...] with dim0
    the concatenated row axis, but ranks differ per leaf (SH rows [rows, k],
    half/dense grids [rows, n, nv], Wigner blocks [rows, d, d]) — so specs
    are built rank-aware per leaf at trace time: dim0 shards over ``dp``,
    everything else replicates.
    """
    if mesh is None or not dp:
        return run
    from repro.distributed.sharding import row_pspec

    if mode == "constraint":
        con = _row_constraint(mesh, dp)

        def sharded(*args):
            args = jax.tree.map(con, args)
            return jax.tree.map(con, run(*args))

        return sharded
    if mode == "shard_map":

        def sharded(*args):
            in_specs = jax.tree.map(lambda a: row_pspec(jnp.ndim(a), dp), args)
            out_sds = jax.eval_shape(run, *args)
            out_specs = jax.tree.map(
                lambda s: row_pspec(len(s.shape), dp), out_sds)
            # row-parallel bodies hold no collectives, so there is no
            # replication to check; with the check on, the grad of the
            # complex grid combine fails its cotangent type test
            return jax.shard_map(run, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=out_specs, check_vma=False)(*args)

        return sharded
    raise ValueError(f"unknown shard mode {mode!r} "
                     "(expected 'constraint' or 'shard_map')")


def _make_bucket_fn(plan: GauntPlan, kind: str, item: BatchItem, donate: bool,
                    mesh, dp: tuple, mode: str, granularity: int) -> Callable:
    """Jit the whole bucket step: flatten/concat/pad -> core -> slice out.

    The pre/post layout work traces into the SAME jitted call as the backend
    math, so one bucket invocation is one dispatch — otherwise the eager
    reshapes/concats would cost more dispatches than the loop being replaced.
    The concatenated layout entering the core is a uniform row layout, so
    sharding is the rank-aware row spec per leaf (`_shard_rows`).
    """
    run = _shard_rows(_bucket_runner(plan, kind), mesh, dp, mode)
    rd = _RDTYPE[plan.key.dtype]
    # the storage form resident (Rep) operands are coerced to before their
    # grids enter the row layout — must match what the backend consumes
    form = "half" if plan.backend == "rfft" else "dense"

    def full(item_ops, item_ws):
        return _bucket_batch_body(run, kind, item, granularity, rd, form,
                                  item_ops, item_ws)

    # donation hands the per-item operand buffers to XLA (callers must not
    # reuse them after a donated apply); only meaningful on accelerators
    donate_args = (0,) if donate and jax.default_backend() != "cpu" else ()
    return jax.jit(full, donate_argnums=donate_args)


@dataclasses.dataclass(frozen=True)
class _Bucket:
    """Items sharing one degree signature, resolved to one inner plan."""

    item_ids: tuple
    plan: GauntPlan
    fn: Callable = dataclasses.field(repr=False, compare=False)


@dataclasses.dataclass(frozen=True)
class BatchedGauntPlan:
    """A bucketed multi-degree workload; ``apply`` runs one fused invocation
    per bucket (see GauntEngine.plan_batch)."""

    kind: str
    dtype: str
    items: tuple
    buckets: tuple
    granularity: int = 1
    donate: bool = False
    shard: ShardSpec | None = None

    def plans(self) -> list[GauntPlan]:
        return [b.plan for b in self.buckets]

    def describe(self) -> str:
        lines = [f"plan_batch(kind={self.kind}, dtype={self.dtype}, "
                 f"items={len(self.items)}, buckets={len(self.buckets)}, "
                 f"granularity={self.granularity}, donate={self.donate})"]
        for b in self.buckets:
            lines.append(f"  items {list(b.item_ids)} -> {b.plan.describe()}")
        return "\n".join(lines)

    # -- execution ---------------------------------------------------------

    def apply(self, inputs, weights=None):
        """Run every item; returns outputs aligned with ``items``.

        inputs  : sequence (len == len(items)); element i is the operand
                  tuple of item i — (x1, x2) for pairwise, (x, rhat) for
                  conv_filter, the xs sequence for manybody.  Operands of one
                  item share their leading (batch) dims.
        weights : optional sequence aligned with items; element i is the
                  weight tuple of item i ((w1, w2, w3), or per-operand list
                  for manybody; None entries allowed) or None.
        """
        inputs = list(inputs)
        if len(inputs) != len(self.items):
            raise ValueError(f"apply got {len(inputs)} inputs for "
                             f"{len(self.items)} items")
        if weights is None:
            weights = [None] * len(self.items)
        weights = list(weights)
        if len(weights) != len(self.items):
            raise ValueError(f"apply got {len(weights)} weight entries for "
                             f"{len(self.items)} items")
        if self.donate and jax.default_backend() != "cpu":
            inputs, weights = self._copy_donation_aliases(inputs, weights)
        outs = [None] * len(self.items)
        for bucket in self.buckets:
            self._run_bucket(bucket, inputs, weights, outs)
        return outs

    def _copy_donation_aliases(self, inputs, weights):
        """Donating one buffer twice is invalid, and a buffer donated by an
        earlier bucket is DEAD for later ones — so before any bucket runs,
        copy every repeat reference (operand or weight) to a buffer that
        will have been donated by then (e.g. selfmix's [x, x, x], or one
        rhat shared across degree items).  Dedup runs per LEAF buffer, not
        per operand object: structured operands (Rep grids, WignerBlocks)
        are freshly-wrapped pytrees whose ``id()`` differs even when their
        underlying grid buffers are shared — comparing wrapper ids would
        donate one grid twice."""
        donated: set[int] = set()
        for bucket in self.buckets:
            for i in bucket.item_ids:
                ops_i = list(inputs[i])
                for j, x in enumerate(ops_i):
                    leaves, _, rebuild = _op_parts(x)
                    fresh, copied = [], False
                    for leaf in leaves:
                        if id(leaf) in donated:
                            leaf = jnp.copy(leaf)
                            copied = True
                        else:
                            donated.add(id(leaf))
                        fresh.append(leaf)
                    if copied:
                        ops_i[j] = rebuild(fresh)
                inputs[i] = tuple(ops_i)
                w_i = weights[i]
                if w_i is not None:
                    w_i = list(w_i)
                    for j, w in enumerate(w_i):
                        if w is not None and id(w) in donated:
                            w_i[j] = jnp.copy(w)
                    weights[i] = tuple(w_i)
        return inputs, weights

    def _run_bucket(self, bucket: _Bucket, inputs, weights, outs) -> None:
        item0 = self.items[bucket.item_ids[0]]
        n_ops = _n_operands(self.kind, item0)
        wdeg = _weight_degrees(self.kind, item0)
        item_ops, item_ws = [], []
        for i in bucket.item_ids:
            ops_i = tuple(inputs[i])
            if len(ops_i) != n_ops:
                raise ValueError(f"item {i}: expected {n_ops} operands, "
                                 f"got {len(ops_i)}")
            item_ops.append(ops_i)
            w_i = weights[i]
            w_i = tuple(w_i) if w_i is not None else (None,) * len(wdeg)
            if len(w_i) != len(wdeg):
                raise ValueError(f"item {i}: expected {len(wdeg)} weight "
                                 f"slots, got {len(w_i)}")
            item_ws.append(w_i)
        res = bucket.fn(tuple(item_ops), tuple(item_ws))
        for t, i in enumerate(bucket.item_ids):
            outs[i] = res[t]


# --------------------------------------------------------------------------
# chain plans: whole chained products, Fourier-resident between steps
# (DESIGN.md §6) — each operand converts at most once, one projection at exit;
# or collapsed entirely into the n-way collocation kernel (§6.4)
# --------------------------------------------------------------------------

# chain-level backend dispatch (DESIGN.md §6.4):
#   tree         — resident spectral pass, divide-and-conquer grid combine
#   looped       — per-product pairwise fold, full round trip each step (the
#                  pre-residency strategy, kept as an autotune candidate)
#   fused_xla    — n-way collocation (sample*multiply*project) in plain jnp
#   fused_pallas — the same collocation as ONE MXU-resident pallas_call
CHAIN_BACKENDS = ("tree", "looped", "fused_xla", "fused_pallas")


@dataclasses.dataclass(frozen=True)
class ChainPlan:
    """A chained Gaunt product  x_1 (x) x_2 (x) ... (x) x_n  planned as one
    Fourier-resident pass.

    ``apply(xs, weights=None, w_out=None, out_basis='sh')``:
      xs      : per-operand SH arrays, SH Reps, or Fourier-resident Reps
                (residents skip conversion entirely).
      weights : per-operand per-degree weights [..., L_i+1] (None entries ok).
                Identical operand arrays convert ONCE even under different
                weights (degree-resolved conversion, `sh_to_fourier_bydeg`).
      w_out   : per-degree output weights, applied after the exit projection.
      out_basis: 'sh' projects to degrees <= Lout; 'fourier' returns the
                resident product Rep (requires Lout == sum(Ls), no w_out).

    Versus the looped per-product left fold (2(n-1) sh->F + (n-1) F->sh),
    a chain runs at most n sh->F and exactly one F->sh — eliminating
    ``interior_pairs_eliminated`` = n-2 interior conversion pairs, plus one
    more sh->F per duplicate operand.  Numerically identical to the looped
    path up to dtype roundoff (2D convolution is associative).

    Execution knobs (plan_chain): ``donate`` hands the unique operand
    buffers to XLA through ``apply_jit`` (callers must not reuse them);
    ``shard`` = (mesh, dp_axes, mode) runs the chain row-sharded — converted
    grids and the exit projection carry rank-aware row constraints, and with
    mode='shard_map' the grid-combination stage runs per-shard.
    """

    Ls: tuple
    Lout: int
    conversion: str          # 'dense' | 'half'
    conv: str                # 'fft' | 'direct' | 'rfft'
    dtype: str
    tree: bool
    donate: bool = False
    shard: tuple = (None, (), "constraint")   # (mesh, dp_axes, mode)
    backend: str = "tree"    # one of CHAIN_BACKENDS (DESIGN.md §6.4)
    gate: bool = False       # fused pointwise gate stage (DESIGN.md §6.5)
    apply: Callable = dataclasses.field(repr=False, compare=False, default=None)
    _jit_cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)

    def apply_jit(self, xs, weights=None, w_out=None, out_basis: str = "sh",
                  gate_params=None):
        """``apply`` behind a cached ``jax.jit`` — the default consumer route.

        Duplicate operands are detected BEFORE the jit boundary: jit hands
        two identical arrays to two distinct tracers, which would defeat the
        shared-operand single conversion, so the compiled chain closes over
        the duplication pattern and sees each unique operand exactly once.
        With ``donate`` the unique operand list is donated to XLA (dedup
        also means a shared operand's buffer is never donated twice).

        Gated plans (``plan_chain(..., gate=True)``) REQUIRE ``gate_params``
        (the models' gate MLP dict {"w1", "w2"}); ungated plans reject it —
        the gate changes the plan's math, so it must be part of the plan
        identity, not a per-call surprise.
        """
        from .rep import Rep

        if self.gate and gate_params is None:
            raise ValueError("this chain plan was built with gate=True; "
                             "apply needs gate_params={'w1', 'w2'}")
        if gate_params is not None and not self.gate:
            raise ValueError("gate_params passed to an ungated chain plan — "
                             "build it with plan_chain(..., gate=True)")
        xs = list(xs)
        uniq, idx_map, seen = [], [], {}
        for x in xs:
            # dedup by the underlying BUFFER (plus Rep meta), not the
            # wrapper: two Rep wrappers around one grid are the same
            # operand — and under donation the same donation target
            dk = (("rep", id(x.data), x.L, x.basis, x.form)
                  if isinstance(x, Rep) else id(x))
            k = seen.get(dk)
            if k is None:
                k = seen[dk] = len(uniq)
                uniq.append(x)
            idx_map.append(k)
        ws = list(weights) if weights is not None else None
        key = (tuple(idx_map),
               None if ws is None else tuple(w is not None for w in ws),
               w_out is not None, out_basis)
        fn = self._jit_cache.get(key)
        if fn is None:
            imap = tuple(idx_map)
            gated = self.gate

            def run(uniq, ws, w_out, gp):
                kw = {"gate_params": gp} if gated else {}
                return self.apply([uniq[i] for i in imap], weights=ws,
                                  w_out=w_out, out_basis=out_basis, **kw)

            donate_args = (0,) if self.donate and \
                jax.default_backend() != "cpu" else ()
            fn = self._jit_cache[key] = jax.jit(run, donate_argnums=donate_args)
        return fn(uniq, ws, w_out, gate_params)

    @property
    def interior_pairs_eliminated(self) -> int:
        """fourier_to_sh . sh_to_fourier pairs the looped path pays and this
        plan does not (excludes extra savings from duplicate operands)."""
        return max(0, len(self.Ls) - 2)

    def conversion_counts(self, n_unique: int | None = None) -> dict:
        """{'chain': (s2f, f2s), 'looped': (s2f, f2s)} conversion tallies."""
        n = len(self.Ls)
        return {"chain": (n if n_unique is None else n_unique, 1),
                "looped": (2 * (n - 1), n - 1)}

    def describe(self) -> str:
        g = " +gate" if self.gate else ""
        if self.backend.startswith("fused"):
            return (f"chain(Ls={list(self.Ls)}, Lout={self.Lout}, "
                    f"dtype={self.dtype}) -> {self.backend}{g} "
                    f"[collocation: 1 dispatch, 0 conversions"
                    f"{', fused pointwise gate' if self.gate else ''}]")
        return (f"chain(Ls={list(self.Ls)}, Lout={self.Lout}, "
                f"conversion={self.conversion}, conv={self.conv}, "
                f"dtype={self.dtype}, tree={self.tree}) -> {self.backend}{g} "
                f"[-{self.interior_pairs_eliminated} interior pairs]")


def _build_chain(Ls: tuple, Lout: int, conversion: str, conv: str,
                 dtype: str, tree: bool, mesh=None, dp: tuple = (),
                 mode: str = "constraint") -> Callable:
    cd = _CDTYPE[dtype]
    rd = _RDTYPE[dtype]
    form = "half" if conversion == "half" else "dense"
    Ltot = sum(Ls)
    _warm_spectral_constants(conversion, Ls, Ltot, Lout, cd)

    def _row_con(a, er: int):
        """Rank-aware row constraint: shard dim0 over dp, replicate the rest
        (a no-op for unbatched leaves — a bare [n, nv] grid has no row axis)."""
        if mesh is None or not dp or jnp.ndim(a) <= er:
            return a
        return _row_constraint(mesh, dp)(a)

    def apply(xs, weights=None, w_out=None, out_basis: str = "sh"):
        from .gaunt import fourier_to_sh, sh_to_fourier, sh_to_fourier_bydeg
        from .manybody import _tree_convolve
        from .rep import Rep

        xs = list(xs)
        if len(xs) != len(Ls):
            raise ValueError(f"chain got {len(xs)} operands for degrees {Ls}")
        ws = list(weights) if weights is not None else [None] * len(xs)
        if len(ws) != len(xs):
            raise ValueError(f"chain got {len(ws)} weight entries for "
                             f"{len(xs)} operands")
        grids: list = [None] * len(xs)
        groups: dict[int, list[int]] = {}
        for i, x in enumerate(xs):
            if isinstance(x, Rep):
                if x.is_fourier:
                    if x.L != Ls[i]:
                        raise ValueError(f"operand {i}: resident bandlimit "
                                         f"{x.L} != planned degree {Ls[i]}")
                    if ws[i] is not None:
                        raise ValueError("resident operands cannot take "
                                         "per-degree weights (apply in SH)")
                    grids[i] = x.with_form(form).data
                    continue
                xs[i] = x.data
            groups.setdefault(id(xs[i]), []).append(i)
        for idxs in groups.values():
            # entry cast AFTER id-grouping so shared-operand dedup still sees
            # the caller's buffers (see _chain_entry_cast)
            x, L = _chain_entry_cast(xs[idxs[0]], rd), Ls[idxs[0]]
            w_ids = {id(ws[i]) for i in idxs}
            if len(idxs) == 1 or len(w_ids) == 1:
                # one conversion; duplicates (same weights too) share the grid
                F = sh_to_fourier(_wmul(x, ws[idxs[0]], L), L, conversion,
                                  jnp.dtype(cd))
                for i in idxs:
                    grids[i] = F
            else:
                # shared operand, different weights: ONE degree-resolved
                # conversion + a cheap per-variant degree combination
                Fl = sh_to_fourier_bydeg(x, L, conversion, jnp.dtype(cd))
                for i in idxs:
                    if ws[i] is None:
                        grids[i] = jnp.sum(Fl, axis=-3)
                    else:
                        grids[i] = jnp.einsum("...l,...luv->...uv",
                                              ws[i].astype(Fl.dtype), Fl)
        def combine(gs):
            if tree:
                return _tree_convolve(list(gs), conv, herm=(form == "half"))
            from .gaunt import conv2d_full, conv2d_herm

            fn = conv2d_herm if form == "half" else conv2d_full
            F = gs[0]
            for G in gs[1:]:
                F = fn(F, G, conv)
            return F

        grids = [_row_con(g, 2) for g in grids]
        # per-shard grid combination needs every grid batched over ONE shared
        # row axis (broadcast/unbatched operands cannot row-shard).  Ragged
        # row counts are handled by a pad/slice step folded in here: rows
        # zero-pad to the dp device count (zero grids convolve to zero — the
        # pad rows are inert) and the combined grid slices back, so chains no
        # longer require dim0 to divide the device count (the batched buckets
        # already padded to the lcm; now chains do too).
        use_map = (mesh is not None and dp and mode == "shard_map"
                   and all(jnp.ndim(g) > 2 for g in grids)
                   and len({jnp.shape(g)[0] for g in grids}) == 1)
        if use_map:
            from repro.distributed import sharding as _sh

            rows = jnp.shape(grids[0])[0]
            pad = -rows % _sh.dp_size(mesh, dp)
            if pad:
                grids = [jnp.pad(g, [(0, pad)] + [(0, 0)] * (jnp.ndim(g) - 1))
                         for g in grids]
            F = _shard_rows(combine, mesh, dp, "shard_map")(tuple(grids))
            if pad:
                F = F[:rows]
        else:
            F = combine(tuple(grids))
        if out_basis == "fourier":
            if w_out is not None:
                raise ValueError("w_out applies in SH; project first")
            if Lout != Ltot:
                raise ValueError(f"out_basis='fourier' keeps the full grid "
                                 f"(L={Ltot}); plan with Lout={Ltot} or "
                                 "project to SH")
            return Rep(_row_con(F, 2), Ltot, "fourier", form)
        out = fourier_to_sh(F, Ltot, Lout, conversion, rd)
        return _row_con(_wmul(out, w_out, Lout), 1)

    return apply


def _build_chain_looped(Ls: tuple, Lout: int, dtype: str,
                        engine: "GauntEngine") -> Callable:
    """The pre-residency strategy as a chain backend: a sequential left fold
    of pairwise spectral plans, paying the full SH round trip per step —
    kept so the measured chain autotuner prices what residency buys."""
    rd = _RDTYPE[dtype]

    def apply(xs, weights=None, w_out=None, out_basis: str = "sh"):
        from .rep import Rep

        if out_basis != "sh":
            raise ValueError("the looped chain backend has no resident exit; "
                             "plan with backend='tree' for out_basis='fourier'")
        xs = list(xs)
        ws = list(weights) if weights is not None else [None] * len(xs)
        if len(xs) != len(Ls) or len(ws) != len(xs):
            raise ValueError(f"chain got {len(xs)} operands / {len(ws)} "
                             f"weight entries for degrees {Ls}")
        for i, x in enumerate(xs):
            if isinstance(x, Rep):
                # a resident operand must leave the basis here (lossless at
                # its own bandlimit) — the looped fold works in SH
                xs[i] = x.to_sh(rdtype=rd).data if x.is_fourier else x.data
            xs[i] = _chain_entry_cast(xs[i], rd)
        acc = _wmul(xs[0], ws[0], Ls[0])
        La = Ls[0]
        for i, (x, L) in enumerate(zip(xs[1:], Ls[1:]), start=1):
            Lt = Lout if i == len(Ls) - 1 else La + L
            p = engine.plan(La, L, Lt, kind="pairwise", dtype=dtype,
                            backend=spectral_default(La, L))
            acc = p.apply(acc, x, None, ws[i])
            La += L
        return _wmul(acc.astype(rd), w_out, Lout)

    return apply


def _build_chain_fused(Ls: tuple, Lout: int, dtype: str,
                       pallas: bool, gate: bool = False) -> Callable:
    """The n-way collocation chain (DESIGN.md §6.4): sample every operand
    onto the shared alias-free product grid, multiply pointwise n-way,
    project once — ONE dispatch (`fused_pallas`: one MXU-resident
    pallas_call; `fused_xla`: the same matrices in plain jnp).  Zero basis
    conversions: Fourier-resident operands enter as grids through the
    grid-evaluation sampling matrix, and a 'fourier' exit leaves the half
    product grid resident.

    ``gate=True`` fuses the models' equivariant gate into the kernel's
    pointwise stage (DESIGN.md §6.5): the product's l=0 scalars are a cheap
    multilinear form of the operands (`constants.chain_l0` — they cannot
    come from the kernel's own output without a second dispatch), the gate
    MLP turns them into per-row (g, beta) outside the kernel, and the
    kernel applies ``v <- v*g + beta*Y00`` on the VMEM-resident product
    values before projection — still ONE `pallas_call`, exact (the gate is
    affine given s), and valid for both the SH and the resident exit."""
    from repro.core import constants as _c

    rd = _RDTYPE[dtype]
    acc = jnp.dtype(_acc_dtype_str(dtype))
    Ltot = sum(Ls)
    # warm the all-SH matrices at build time with the EXACT argument tuples
    # the runners use (lru_cache keys on raw args, so entries=None would
    # warm a duplicate); resident-entry variants build lazily on first use.
    # Mixed precision requests TWO sets: T at storage dtype, P at acc dtype.
    _c.chain_matrices(tuple(Ls), Lout, ("sh",) * len(Ls), "sh", dtype=dtype)
    if dtype != _acc_dtype_str(dtype):
        _c.chain_matrices(tuple(Ls), Lout, ("sh",) * len(Ls), "sh",
                          dtype=_acc_dtype_str(dtype))
    if gate:
        _c.chain_l0(tuple(Ls), ("sh",) * len(Ls))

    def apply(xs, weights=None, w_out=None, out_basis: str = "sh",
              gate_params=None):
        from repro.kernels.gaunt_fused import (gaunt_chain_fused_pallas,
                                               gaunt_chain_fused_xla)
        from .rep import Rep

        if gate and gate_params is None:
            raise ValueError("gated chain plan requires gate_params")
        if gate_params is not None and not gate:
            raise ValueError("gate_params on an ungated chain plan — build "
                             "it with plan_chain(..., gate=True)")
        xs = list(xs)
        if len(xs) != len(Ls):
            raise ValueError(f"chain got {len(xs)} operands for degrees {Ls}")
        ws = list(weights) if weights is not None else [None] * len(xs)
        if len(ws) != len(xs):
            raise ValueError(f"chain got {len(ws)} weight entries for "
                             f"{len(xs)} operands")
        entries, arrs = [], []
        for i, x in enumerate(xs):
            if isinstance(x, Rep) and x.is_fourier:
                if x.L != Ls[i]:
                    raise ValueError(f"operand {i}: resident bandlimit {x.L} "
                                     f"!= planned degree {Ls[i]}")
                if ws[i] is not None:
                    raise ValueError("resident operands cannot take per-degree "
                                     "weights (apply in SH)")
                entries.append("grid")
                arrs.append(x.with_form("half").data)
            else:
                if isinstance(x, Rep):
                    x = x.data
                entries.append("sh")
                arrs.append(_wmul(_chain_entry_cast(x, rd), ws[i], Ls[i]))
        if out_basis == "fourier":
            if w_out is not None:
                raise ValueError("w_out applies in SH; project first")
            if Lout != Ltot:
                raise ValueError(f"out_basis='fourier' keeps the full grid "
                                 f"(L={Ltot}); plan with Lout={Ltot} or "
                                 "project to SH")
        gate_arg = None
        if gate:
            # the product's l=0 scalars as a multilinear form of the
            # (already weighted) operands; grid entries contract through
            # their real-stacked form, mirroring the kernel's preparation
            flat = []
            for a, e in zip(arrs, entries):
                if e == "grid":
                    Fl = a.reshape(a.shape[:-2] + (-1,))
                    a = jnp.concatenate([Fl.real, Fl.imag], axis=-1)
                flat.append(a.astype(acc))
            M = jnp.asarray(_c.chain_l0(tuple(Ls), tuple(entries)), acc)
            letters = "abcdefghij"[: len(Ls)]
            expr = (",".join("..." + c for c in letters)
                    + "," + letters + "->...")
            s = jnp.einsum(expr, *flat, M)
            g, beta = _gate_coeffs(gate_params, s)
            gate_arg = (g, beta * _GATE_C0)
        fn = gaunt_chain_fused_pallas if pallas else gaunt_chain_fused_xla
        out = fn(arrs, Ls, Lout, entries=tuple(entries),
                 out_entry="grid" if out_basis == "fourier" else "sh",
                 dtype=dtype, gate=gate_arg)
        if out_basis == "fourier":
            from .rep import Rep as _Rep

            return _Rep(out, Ltot, "fourier", "half")
        return _wmul(out.astype(rd), w_out, Lout)

    return apply


def _wrap_chain_gate(base: Callable, Lout: int) -> Callable:
    """Gate a spectral chain backend (tree/looped) at its exit: SH exits
    gate on the packed coefficients (before ``w_out`` — the gate acts on
    the raw chain product, matching the fused stage's placement), resident
    exits gate on the grid itself via `_gate_rep` — no conversions added
    either way.  The collocation backends never use this wrapper: they fuse
    the stage into the kernel (`_build_chain_fused(gate=True)`)."""

    def apply(xs, weights=None, w_out=None, out_basis: str = "sh",
              gate_params=None):
        if gate_params is None:
            raise ValueError("gated chain plan requires gate_params")
        out = base(xs, weights=weights, w_out=None, out_basis=out_basis)
        if out_basis == "fourier":
            return _gate_rep(gate_params, out)
        # the f32 gate MLP must not promote a bf16 chain exit: gate in f32
        # (the accumulation dtype), round once back to the storage dtype
        return _wmul(_gate_sh(gate_params, out).astype(out.dtype), w_out, Lout)

    return apply


def _constrained_chain_apply(apply: Callable, mesh, dp: tuple) -> Callable:
    """Row-shard a collocation chain: rank-aware row constraints on batched
    operands and the output (the kernel wrapper flattens leading dims to
    rows, so dim0 sharding propagates straight through the matmuls)."""
    con = _row_constraint(mesh, dp)

    def _c(x, er: int):
        from .rep import Rep

        if isinstance(x, Rep):
            return Rep(_c(x.data, 2), x.L, x.basis, x.form)
        return con(x) if jnp.ndim(x) > er else x

    def wrapped(xs, weights=None, w_out=None, out_basis: str = "sh", **kw):
        xs = [_c(x, 1) for x in xs]
        out = apply(xs, weights=weights, w_out=w_out, out_basis=out_basis,
                    **kw)
        return _c(out, 1)

    return wrapped


# --------------------------------------------------------------------------
# cost model (relative real-MAC counts, set by hand; see DESIGN.md §4)
# --------------------------------------------------------------------------

_C_CPLX = 4.0        # complex MAC = 4 real MACs
_C_SKINNY = 4.0      # skinny collocation matmuls (G >> d) are memory-bound
_C_FFT = 10.0        # per point per log2 level: tiny-grid FFTs vectorize poorly
_OVERHEAD = 3e4      # per dispatched op: favors fewer, denser ops at small sizes
_INTERPRET_PENALTY = 1e4   # Pallas interpret mode off-TPU is not a real option


def _dims(key: PlanKey):
    B = key.batch_hint or 1
    n1, n2 = 2 * key.L1 + 1, 2 * key.L2 + 1
    N = n1 + n2 - 1
    return B, num_coeffs(key.L1), num_coeffs(key.L2), num_coeffs(key.Lout), n1, n2, N


def _cost_dense_einsum(key: PlanKey) -> float:
    B, d1, d2, do, *_ = _dims(key)
    if key.kind == "channel_mix":
        return 16.0 * B * d1 * d2 * do + _OVERHEAD  # x C1*C2 (unknown): scaled proxy
    if key.kind == "manybody":
        Ls = key.opt("Ls", (key.L1, key.L2))
        total, La = 0.0, Ls[0]
        for L in Ls[1:]:
            total += B * num_coeffs(La) * num_coeffs(L) * num_coeffs(La + L)
            La += L
        return total + _OVERHEAD * len(Ls)
    return B * d1 * d2 * do + _OVERHEAD


def _spectral_common(key: PlanKey, conv: str, packed: bool) -> float:
    B, d1, d2, do, n1, n2, N = _dims(key)
    if packed:  # O(L^3) stacked matmuls
        conv_in = 4.0 * B * (key.L1 + 1) ** 3 + 4.0 * B * (key.L2 + 1) ** 3
        proj = 8.0 * B * (key.Lout + 1) ** 2 * N
    else:  # O(L^4) dense einsum conversions
        conv_in = 2.0 * B * (d1 * n1 * n1 + d2 * n2 * n2)
        proj = _C_CPLX * B * N * N * do
    if conv == "fft":
        c = 3.0 * _C_FFT * B * N * N * max(1.0, math.log2(N * N)) + _C_CPLX * B * N * N
    else:
        c = _C_CPLX * B * N * N * n2 * n2
    n_ops = 8 if not packed else 14
    return conv_in + c + proj + _OVERHEAD * n_ops


def _cost_fft(key):
    if key.kind == "manybody":
        return _cost_manybody_spectral(key, "fft", packed=False)
    return _spectral_common(key, "fft", packed=False)


def _cost_direct(key):
    if key.kind == "manybody":
        return _cost_manybody_spectral(key, "direct", packed=False)
    return _spectral_common(key, "direct", packed=False)


def _cost_packed(key):
    conv = key.opt("conv", "fft")
    if key.kind == "manybody":
        return _cost_manybody_spectral(key, conv, packed=True)
    return _spectral_common(key, conv, packed=True)


def _cost_rfft(key):
    """Half (Hermitian) conversions + real spatial rfft convolution."""
    B, d1, d2, do, n1, n2, N = _dims(key)
    if key.kind == "manybody":
        Ls = key.opt("Ls", (key.L1, key.L2))
        Lt = sum(Ls)
        Nr = 2 * Lt + 2
        conv_in = sum(2.0 * B * num_coeffs(L) * (2 * L + 1) * (L + 1) for L in Ls)
        convs = 1.5 * _C_FFT * len(Ls) * B * Nr * Nr * max(1.0, math.log2(Nr * Nr))
        proj = _C_CPLX * B * Nr * (Lt + 1) * num_coeffs(key.Lout) / 2
        return conv_in + convs + proj + _OVERHEAD * (6 + 2 * len(Ls))
    Nr = N + 1  # the even alias-free spatial grid 2(L1+L2)+2
    conv_in = 2.0 * B * (d1 * n1 * (key.L1 + 1) + d2 * n2 * (key.L2 + 1))
    c = 1.5 * _C_FFT * B * Nr * Nr * max(1.0, math.log2(Nr * Nr)) + B * Nr * Nr
    proj = _C_CPLX * B * N * (key.L1 + key.L2 + 1) * do / 2
    return conv_in + c + proj + _OVERHEAD * 9


def _cost_manybody_spectral(key: PlanKey, conv: str, packed: bool) -> float:
    Ls = key.opt("Ls", (key.L1, key.L2))
    B = key.batch_hint or 1
    Lt = sum(Ls)
    N = 2 * Lt + 1
    convs = _C_FFT * len(Ls) * B * N * N * max(1.0, math.log2(N * N)) if conv == "fft" \
        else _C_CPLX * len(Ls) * B * N * N * (2 * max(Ls) + 1) ** 2
    conv_in = sum(2.0 * B * num_coeffs(L) * (2 * L + 1) ** 2 for L in Ls)
    proj = _C_CPLX * B * N * N * num_coeffs(key.Lout)
    return conv_in + convs + proj + _OVERHEAD * (6 + 2 * len(Ls))


def _cost_fused(key: PlanKey, pallas: bool) -> float:
    B, d1, d2, do, n1, n2, N = _dims(key)
    Nf = 2 * (key.L1 + key.L2) + 2
    G = ((Nf * Nf + 127) // 128) * 128
    c = _C_SKINNY * B * G * (d1 + d2 + do) + _OVERHEAD * 4
    if key.kind == "channel_mix":
        c = 4.0 * _C_SKINNY * B * G * (d1 + d2 + do) + _OVERHEAD * 4
    if pallas:
        c *= 0.5 if jax.default_backend() == "tpu" else _INTERPRET_PENALTY
    return c


def _cost_escn(key: PlanKey) -> float:
    B, d1, d2, do, n1, n2, N = _dims(key)
    Lw = max(key.L1, key.Lout)
    wigner = B * sum((2 * l + 1) ** 4 for l in range(2, Lw + 1)) + \
        2.0 * B * sum((2 * l + 1) ** 2 for l in range(Lw + 1))
    coupling = 2.0 * B * d1 * (key.L2 + 1) * do
    return wigner + coupling + _OVERHEAD * 10


# --------------------------------------------------------------------------
# backend builders
# --------------------------------------------------------------------------


def _build_dense_einsum(key: PlanKey) -> Callable:
    # the Gaunt tensor G and operand copies live at the STORAGE dtype (bf16
    # keys move half the bytes); the einsum contractions accumulate at the
    # derived >= f32 accumulation dtype via ``preferred_element_type``
    gd = key.dtype if key.dtype == "bfloat16" else key.acc_dtype
    acc = jnp.dtype(key.acc_dtype)
    rd = _RDTYPE[key.dtype]
    if key.kind == "channel_mix":
        G = constants.gaunt_dense(key.L1, key.L2, key.Lout, gd)

        def apply_mix(x1, x2, w_mix):
            Gj = jnp.asarray(G)
            out = jnp.einsum("...ci,...dj,ijk,cde->...ek",
                             x1.astype(Gj.dtype), x2.astype(Gj.dtype), Gj,
                             w_mix.astype(Gj.dtype),
                             preferred_element_type=acc)
            return out.astype(rd)

        return apply_mix
    if key.kind == "manybody":
        Ls = key.opt("Ls")

        def apply_mb(xs, weights=None):
            xs = list(xs)
            if weights is not None:
                xs = [_wmul(x, w, L) for x, w, L in zip(xs, weights, Ls)]
            acc_x, La = xs[0], Ls[0]
            for i, (x, L) in enumerate(zip(xs[1:], Ls[1:])):
                last = i == len(Ls) - 2
                Lt = key.Lout if last else La + L
                G = jnp.asarray(constants.gaunt_dense(La, L, Lt, gd))
                acc_x = jnp.einsum("...i,...j,ijk->...k",
                                   acc_x.astype(G.dtype), x.astype(G.dtype), G,
                                   preferred_element_type=acc)
                La += L
            return acc_x.astype(rd)

        return apply_mb
    G = constants.gaunt_dense(key.L1, key.L2, key.Lout, gd)

    def apply_pair(x1, x2, w1=None, w2=None, w3=None):
        Gj = jnp.asarray(G)
        x1 = _wmul(x1, w1, key.L1).astype(Gj.dtype)
        x2 = _wmul(x2, w2, key.L2).astype(Gj.dtype)
        out = jnp.einsum("...i,...j,ijk->...k", x1, x2, Gj,
                         preferred_element_type=acc)
        return _wmul(out.astype(rd), w3, key.Lout)

    return apply_pair


def _warm_spectral_constants(conversion: str, Ls, Lf: int, Lout: int, cd) -> None:
    """Build the conversion constants at plan time so jit tracing never
    re-runs numpy precompute."""
    warm_y = {"dense": constants.y_dense, "packed": constants.y_packed,
              "half": constants.y_half}[conversion]
    warm_z = {"dense": constants.z_dense, "packed": constants.z_packed,
              "half": constants.z_half}[conversion]
    for L in Ls:
        warm_y(L, cd)
    warm_z(Lf, Lout, cd)


def _resident_grid(op, L: int, form: str):
    """A 'fourier' boundary operand: a Rep (validated) or a raw grid."""
    from .rep import Rep

    if isinstance(op, Rep):
        if op.basis != "fourier":
            raise ValueError("boundary='fourier' operand must be Fourier-resident "
                             f"(got basis={op.basis!r}; convert with .to_fourier())")
        if op.L != L:
            raise ValueError(f"resident operand bandlimit {op.L} != planned degree {L}")
        return op.with_form(form).data
    return op


def _build_spectral(key: PlanKey, conversion: str, conv: str) -> Callable:
    from .gaunt import conv2d_full, conv2d_herm, fourier_to_sh, sh_to_fourier  # lazy: gaunt imports engine

    cd = _CDTYPE[key.dtype]
    rd = _RDTYPE[key.dtype]
    form = "half" if conversion == "half" else "dense"
    conv_fn = conv2d_herm if conversion == "half" else conv2d_full

    if key.kind == "manybody":
        from .manybody import _tree_convolve

        Ls = key.opt("Ls")
        Ltot = sum(Ls)
        _warm_spectral_constants(conversion, Ls, Ltot, key.Lout, cd)

        def apply_mb(xs, weights=None):
            grids = []
            for i, (x, L) in enumerate(zip(xs, Ls)):
                if weights is not None and weights[i] is not None:
                    x = _wmul(x, weights[i], L)
                grids.append(sh_to_fourier(x, L, conversion, jnp.dtype(cd)))
            F = _tree_convolve(grids, conv, herm=(conversion == "half"))
            return fourier_to_sh(F, Ltot, key.Lout, conversion, rd)

        return apply_mb

    _warm_spectral_constants(conversion, (key.L1, key.L2), key.L1 + key.L2,
                             key.Lout, cd)
    b1, b2, bo = key.opt("boundary") or ("sh", "sh", "sh")

    def convert_in(x, w, L, b):
        if b == "fourier":
            if w is not None:
                raise ValueError("per-degree weights need an SH operand; apply "
                                 "them before converting to the Fourier basis")
            return _resident_grid(x, L, form)
        return sh_to_fourier(_wmul(x, w, L), L, conversion, jnp.dtype(cd))

    def apply_pair(x1, x2, w1=None, w2=None, w3=None):
        F1 = convert_in(x1, w1, key.L1, b1)
        F2 = convert_in(x2, w2, key.L2, b2)
        F3 = conv_fn(F1, F2, conv)
        if bo == "fourier":
            from .rep import Rep

            if w3 is not None:
                raise ValueError("w3 applies in SH; a Fourier-boundary output "
                                 "cannot carry per-degree output weights")
            return Rep(F3, key.L1 + key.L2, "fourier", form)
        out = fourier_to_sh(F3, key.L1 + key.L2, key.Lout, conversion, rd)
        return _wmul(out, w3, key.Lout)

    return apply_pair


def _build_fused(key: PlanKey, pallas: bool) -> Callable:
    # storage discipline (DESIGN.md §3.6): operands and the sampling matrices
    # T1/T2 at key.dtype, f32 MXU accumulation, f32 projection matrix P
    rd = _RDTYPE[key.dtype]
    sd = jnp.dtype(key.dtype)
    acc = jnp.float32  # fused backends are f32/bf16-storage only
    (T1, T2), _ = constants.chain_matrices(
        (key.L1, key.L2), key.Lout, ("sh", "sh"), "sh", dtype=key.dtype)
    _, P = constants.chain_matrices(
        (key.L1, key.L2), key.Lout, ("sh", "sh"), "sh", dtype="float32")

    if key.kind == "channel_mix":

        def apply_mix(x1, x2, w_mix):
            T1j, T2j, Pj = jnp.asarray(T1), jnp.asarray(T2), jnp.asarray(P)
            V1 = jnp.dot(x1.astype(sd), T1j, preferred_element_type=acc)  # [..., C1, G]
            V2 = jnp.dot(x2.astype(sd), T2j, preferred_element_type=acc)  # [..., C2, G]
            V = jnp.einsum("...cg,...dg,cde->...eg", V1, V2, w_mix.astype(V1.dtype))
            return (V @ Pj).astype(rd)

        return apply_mix

    if pallas:
        block_b = key.opt("block_b")  # None -> the kernel's per-dtype default

        def apply_pair(x1, x2, w1=None, w2=None, w3=None):
            from repro.kernels.gaunt_fused import gaunt_fused_pallas  # lazy: kernels import core

            x1 = _wmul(x1, w1, key.L1)
            x2 = _wmul(x2, w2, key.L2)
            out = gaunt_fused_pallas(x1, x2, key.L1, key.L2, key.Lout,
                                     block_b=block_b, dtype=key.dtype)
            return _wmul(out.astype(rd), w3, key.Lout)

        return apply_pair

    def apply_pair(x1, x2, w1=None, w2=None, w3=None):
        T1j, T2j, Pj = jnp.asarray(T1), jnp.asarray(T2), jnp.asarray(P)
        x1 = _wmul(x1, w1, key.L1)
        x2 = _wmul(x2, w2, key.L2)
        v1 = jnp.dot(x1.astype(sd), T1j, preferred_element_type=acc)
        v2 = jnp.dot(x2.astype(sd), T2j, preferred_element_type=acc)
        out = ((v1 * v2) @ Pj).astype(rd)
        return _wmul(out, w3, key.Lout)

    return apply_pair


def _build_escn(key: PlanKey) -> Callable:
    # in the edge-aligned frame the filter is Y(e_z), m=0 only, so the Gaunt
    # product with it is one real linear map on the rotated rows (DESIGN.md
    # §3.5): C [L2+1, d1, do] per filter degree, M = C summed over degrees
    # when no filter weights are given.  Both are a few hundred numbers at
    # most, so they stay at the accumulation dtype (as the fused backends' P)
    acc = jnp.dtype(key.acc_dtype)
    rd = _RDTYPE[key.dtype]
    L1, L2, Lout = key.L1, key.L2, key.Lout
    C = constants.escn_coupling(L1, L2, Lout, key.acc_dtype)
    M = C.sum(axis=0)
    constants.cg_11_blocks(max(L1, Lout))
    geometry = key.opt("geometry")

    def apply_conv(x, rhat, w1=None, w2=None, w3=None):
        # lazy: conv.py routes through the engine, so import its helpers at call
        from .conv import (WignerBlocks, align_rotation, apply_wigner_blocks,
                           wigner_blocks_from_rotmat)

        x = _wmul(x, w1, L1)
        if geometry == "wigner":
            # rotation residency: the caller precomputed the alignment
            # rotation + Wigner recursion once per geometry (conv.geometry_rep)
            if not isinstance(rhat, WignerBlocks):
                raise ValueError("plans with options={'geometry': 'wigner'} "
                                 "take precomputed WignerBlocks (see "
                                 "EquivariantConv.geometry_rep), got "
                                 f"{type(rhat).__name__}")
            if rhat.L < max(L1, Lout):
                raise ValueError(f"WignerBlocks cover degrees <= {rhat.L}, "
                                 f"need max(L1, Lout) = {max(L1, Lout)}")
            Ds = list(rhat.blocks)
        else:
            R = align_rotation(rhat.astype(jnp.promote_types(rhat.dtype, jnp.float32)))
            Ds = wigner_blocks_from_rotmat(max(L1, Lout), R)
        x_rot = apply_wigner_blocks(Ds[: L1 + 1], x)
        if w2 is None:
            out_rot = jnp.einsum("...i,ik->...k", x_rot, jnp.asarray(M),
                                 preferred_element_type=acc)
        else:
            out_rot = jnp.einsum("...i,...l,lik->...k", x_rot, w2.astype(acc),
                                 jnp.asarray(C), preferred_element_type=acc)
        out = apply_wigner_blocks(Ds[: Lout + 1], out_rot.astype(rd), transpose=True)
        return _wmul(out, w3, Lout)

    return apply_conv


def _wrap_conv_filter(key: PlanKey, pair_apply: Callable) -> Callable:
    """Serve kind='conv_filter' on a pairwise backend: materialize Y(rhat)."""

    def apply_conv(x, rhat, w1=None, w2=None, w3=None):
        from .so3 import real_sph_harm_jax

        filt = real_sph_harm_jax(key.L2, rhat).astype(x.dtype)
        return pair_apply(x, filt, w1, w2, w3)

    return apply_conv


register_backend(Backend(
    name="dense_einsum",
    kinds=frozenset({"pairwise", "conv_filter", "manybody", "channel_mix"}),
    build=_build_dense_einsum,
    cost=_cost_dense_einsum,
))
register_backend(Backend(
    name="fft",
    kinds=frozenset({"pairwise", "conv_filter", "manybody"}),
    build=lambda key: _build_spectral(key, "dense", "fft"),
    cost=_cost_fft,
    fourier_boundary=True,
))
register_backend(Backend(
    name="direct",
    kinds=frozenset({"pairwise", "conv_filter", "manybody"}),
    build=lambda key: _build_spectral(key, "dense", "direct"),
    cost=_cost_direct,
    fourier_boundary=True,
))
register_backend(Backend(
    name="packed",
    kinds=frozenset({"pairwise", "conv_filter", "manybody"}),
    build=lambda key: _build_spectral(key, "packed", key.opt("conv", "fft")),
    cost=_cost_packed,
    fourier_boundary=True,
))
register_backend(Backend(
    name="rfft",
    kinds=frozenset({"pairwise", "conv_filter", "manybody"}),
    build=lambda key: _build_spectral(key, "half", key.opt("conv", "rfft")),
    cost=_cost_rfft,
    fourier_boundary=True,
))
register_backend(Backend(
    name="fused_xla",
    kinds=frozenset({"pairwise", "conv_filter", "channel_mix"}),
    build=lambda key: _build_fused(key, pallas=False),
    cost=lambda key: _cost_fused(key, pallas=False),
    dtypes=frozenset({"float32", "bfloat16"}),
))
register_backend(Backend(
    name="fused_pallas",
    kinds=frozenset({"pairwise", "conv_filter"}),
    build=lambda key: _build_fused(key, pallas=True),
    cost=lambda key: _cost_fused(key, pallas=True),
    supports_grad=False,  # pallas_call has no registered VJP
    dtypes=frozenset({"float32", "bfloat16"}),
    needs_interpret=True,
))
register_backend(Backend(
    name="escn_aligned",
    kinds=frozenset({"conv_filter"}),
    build=_build_escn,
    cost=_cost_escn,
    wigner_geometry=True,
))


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


class GauntEngine:
    """Plans, caches, and autotunes Gaunt ops over the backend registry."""

    def __init__(self, cache_path: str | None = None):
        self._plans: dict[tuple, GauntPlan] = {}
        self._batched: dict[tuple, BatchedGauntPlan] = {}
        self._chains: dict[tuple, ChainPlan] = {}
        self._measured: dict[PlanKey, str] = {}
        # best measured wall time per key — lets dtype='auto' compare a key's
        # f32/bf16 siblings (one key family) without re-timing either
        self._measured_t: dict[PlanKey, float] = {}
        # persistent autotune cache (core/autotune_cache.py).  Disabled
        # unless a path is configured here, via set_autotune_cache(), or via
        # $REPRO_AUTOTUNE_CACHE — tests and one-shot scripts keep the
        # historical purely-in-process behavior.
        self._cache_path = cache_path
        self._cache_loaded = False
        # counts timed measurement passes (plan backends, chain candidates,
        # dtype siblings).  A process booted against a warm cache must
        # keep this at 0 — the warm-start acceptance proof and the CLI's
        # --verify-warm both read it.
        self.timing_runs = 0
        # candidates that RAISED while being timed (not ones that lost on
        # time): one dict per failure with the site, key, candidate and
        # error, so a backend the device's compiler refuses is visible
        # instead of silently dropping out of the race
        self.autotune_failures: list[dict] = []

    # -- persistent autotune cache -----------------------------------------

    def set_autotune_cache(self, path: str | None) -> None:
        """Point this engine at a persistent cache file (None -> fall back
        to $REPRO_AUTOTUNE_CACHE, or disabled).  The next measure-mode miss
        loads it lazily; every new measurement flushes to it."""
        self._cache_path = path
        self._cache_loaded = False

    def _resolved_cache_path(self) -> str | None:
        from . import autotune_cache as _ac

        return _ac.resolve_path(self._cache_path)

    def load_autotune_cache(self) -> int:
        """Load persisted selections and timings now (idempotent;
        in-process entries win over the file's).  -> #selections adopted."""
        self._cache_loaded = True
        path = self._resolved_cache_path()
        if path is None:
            return 0
        from . import autotune_cache as _ac

        data = _ac.load(path)
        if data is None:
            return 0
        selections, timings = data
        n = 0
        for k, b in selections.items():
            if k not in self._measured:
                self._measured[k] = b
                n += 1
        for k, t in timings.items():
            self._measured_t.setdefault(k, t)
        return n

    def _maybe_load_cache(self) -> None:
        if not self._cache_loaded:
            self.load_autotune_cache()

    def flush_autotune_cache(self) -> str | None:
        """Persist the measurement stores (atomic, merging).  No-op without
        a configured cache path.  -> the path written, or None."""
        path = self._resolved_cache_path()
        if path is None:
            return None
        from . import autotune_cache as _ac

        _ac.save(path, self._measured, self._measured_t)
        return path

    def _autoflush(self) -> None:
        """Flush after a new measurement — an unwritable cache file must
        degrade to in-process-only autotune, never break planning."""
        try:
            self.flush_autotune_cache()
        except OSError:
            pass

    # -- public API --------------------------------------------------------

    def plan(self, L1: int | None = None, L2: int | None = None,
             Lout: int | None = None, *, kind: str = "pairwise",
             Ls: tuple | None = None, batch_hint: int | None = None,
             dtype="float32", backend: str | None = None,
             options: dict | None = None, tune: str = "heuristic",
             requires_grad: bool = True) -> GauntPlan:
        """Resolve (and cache) a plan.  ``backend=None`` -> engine selection.

        kind='manybody' takes ``Ls`` (per-operand degrees) instead of L1/L2.
        ``tune`` is 'heuristic' (cost model) or 'measure' (timed autotune).
        ``dtype`` is the storage dtype; 'auto' (with tune='measure') times
        the f32 and bf16 siblings and keeps bf16 only where it wins.
        """
        if kind not in KINDS:
            raise ValueError(f"unknown kind {kind!r} (expected one of {KINDS})")
        options = dict(options or {})
        bound = options.get("boundary")
        if bound is not None:
            bound = tuple(bound)
            if kind != "pairwise":
                raise ValueError("boundary options are only defined for "
                                 "pairwise plans (chains cover the rest)")
            if len(bound) != 3 or any(b not in ("sh", "fourier") for b in bound):
                raise ValueError(f"boundary must be 3 entries of 'sh'|'fourier', "
                                 f"got {bound!r}")
            if bound == ("sh", "sh", "sh"):
                options.pop("boundary")  # the default; don't fragment the cache
            else:
                options["boundary"] = bound
        geom = options.get("geometry")
        if geom is not None:
            if kind != "conv_filter":
                raise ValueError("geometry options only apply to conv_filter "
                                 "plans (precomputed Wigner alignment)")
            if geom != "wigner":
                raise ValueError(f"unknown geometry {geom!r} (expected 'wigner')")
        extra = tuple(sorted(options.items()))
        if kind == "manybody":
            if Ls is None or len(Ls) < 2:
                raise ValueError("manybody plans need Ls with >= 2 degrees")
            Ls = tuple(int(L) for L in Ls)
            L1, L2 = max(Ls), min(Ls)
            Lout = sum(Ls) if Lout is None else Lout
            extra = extra + (("Ls", Ls),)
        else:
            if L1 is None or L2 is None:
                raise ValueError(f"kind={kind!r} plans need L1 and L2")
            Lout = L1 + L2 if Lout is None else Lout
        if Lout > (sum(Ls) if kind == "manybody" else L1 + L2):
            raise ValueError("Lout cannot exceed the total degree (Gaunt selection rule)")
        if bound is not None and bound[2] == "fourier" and Lout != L1 + L2:
            raise ValueError("a Fourier-boundary output keeps the full product "
                             f"grid (L={L1 + L2}); plan with Lout={L1 + L2} and "
                             "project at the chain exit")
        if isinstance(dtype, str) and dtype == "auto":
            dts = self._select_dtype(
                lambda d: PlanKey(L1, L2, Lout, kind, batch_hint, d, extra),
                tune=tune, requires_grad=requires_grad)
        else:
            dts = _dtype_str(dtype)
        key = PlanKey(L1, L2, Lout, kind, batch_hint, dts, extra)
        cache_key = (key, backend, tune, requires_grad)
        hit = self._plans.get(cache_key)
        if hit is not None:
            return hit
        name = backend or self.select(key, tune=tune, requires_grad=requires_grad)
        spec = _REGISTRY.get(name)
        if spec is None:
            raise ValueError(f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}")
        if not spec.eligible(key, requires_grad):
            raise ValueError(f"backend {name!r} cannot serve {key} "
                             f"(requires_grad={requires_grad})")
        apply = spec.build(key)
        if key.kind == "conv_filter" and spec.name != "escn_aligned":
            # generic backends build the pairwise form; materialize Y(rhat)
            apply = _wrap_conv_filter(key, apply)
        p = GauntPlan(key=key, backend=name, apply=apply)
        self._plans[cache_key] = p
        return p

    def plan_batch(self, items, *, kind: str = "pairwise", dtype="float32",
                   backend: str | None = None, tune: str = "heuristic",
                   requires_grad: bool = True, donate: bool = False,
                   shard_spec: ShardSpec | None = None,
                   pad_to: int | None = None) -> BatchedGauntPlan:
        """Plan a ragged multi-degree workload as bucketed fused invocations.

        items: sequence of (L1, L2, Lout[, size]) tuples / dicts / BatchItems
        (manybody items carry ``Ls``).  Items sharing a degree signature form
        one *bucket*: their operands are flattened to rows, concatenated,
        tail-padded to the plan granularity, and executed by a single jitted
        call on the bucket's inner plan — per-item results are sliced back
        out, numerically identical to per-plan loops (all backends are
        row-parallel).  ``donate=True`` donates the concatenated operand
        buffers on accelerators; ``shard_spec`` shards the row axis over the
        mesh's data axes (see :class:`ShardSpec`).  ``pad_to`` forces a row
        granularity (e.g. 128 for lane alignment); the data-parallel device
        count is always folded in so shards stay equal.
        """
        if kind not in KINDS:
            raise ValueError(f"unknown kind {kind!r} (expected one of {KINDS})")
        if kind == "channel_mix":
            raise ValueError("plan_batch does not support kind='channel_mix': "
                             "w_mix is not a row-batched operand (use plan())")
        norm = []
        for it in items:
            it = _as_batch_item(it)
            if kind == "manybody":
                if it.Ls is None or len(it.Ls) < 2:
                    raise ValueError("manybody batch items need Ls with >= 2 degrees")
                if it.Lout is None:
                    it = dataclasses.replace(it, Lout=sum(it.Ls))
            else:
                if it.L1 is None or it.L2 is None:
                    raise ValueError(f"kind={kind!r} batch items need L1 and L2")
                if it.Lout is None:
                    it = dataclasses.replace(it, Lout=it.L1 + it.L2)
            norm.append(it)
        norm = tuple(norm)
        if not norm:
            raise ValueError("plan_batch needs at least one item")
        # buckets key on the STORAGE dtype; 'auto' flows through to each
        # bucket's inner plan(), which resolves it per degree-signature
        dts = "auto" if (isinstance(dtype, str) and dtype == "auto") \
            else _dtype_str(dtype)
        mesh, dp = (None, ()) if shard_spec is None else shard_spec.resolve()
        g = max(1, int(pad_to or 1))
        if mesh is not None and dp:
            from repro.distributed import sharding as _sh

            g = math.lcm(g, _sh.dp_size(mesh, dp))
        mode = shard_spec.mode if shard_spec is not None else "constraint"
        # cache the batched plan: the jitted bucket callables must be stable
        # across calls or every eager invocation would recompile
        cache_key = (norm, kind, dts, backend, tune, requires_grad, donate,
                     g, mesh, dp, mode)
        hit = self._batched.get(cache_key)
        if hit is not None:
            return hit
        groups: dict[tuple, list[int]] = {}
        for i, it in enumerate(norm):
            groups.setdefault(it.signature(), []).append(i)
        buckets = []
        for idxs in groups.values():
            it0 = norm[idxs[0]]
            known = [norm[i].size for i in idxs if norm[i].size]
            hint = sum(known) if known else None
            p = self.plan(
                it0.L1, it0.L2, it0.Lout, kind=kind, Ls=it0.Ls,
                batch_hint=hint, dtype=dts, backend=backend,
                options=dict(it0.options) or None, tune=tune,
                requires_grad=requires_grad,
            )
            fn = _make_bucket_fn(p, kind, it0, donate, mesh, dp, mode, g)
            buckets.append(_Bucket(item_ids=tuple(idxs), plan=p, fn=fn))
        bp = BatchedGauntPlan(kind=kind, dtype=dts, items=norm,
                              buckets=tuple(buckets), granularity=g,
                              donate=donate, shard=shard_spec)
        self._batched[cache_key] = bp
        return bp

    def plan_chain(self, Ls, Lout: int | None = None, *,
                   conversion: str | None = None, conv: str | None = None,
                   dtype="float32", tree: bool = True, donate: bool = False,
                   shard_spec: ShardSpec | None = None,
                   backend: str | None = None, tune: str = "heuristic",
                   batch_hint: int | None = None,
                   entry_hint: tuple | None = None,
                   out_hint: str = "sh",
                   share_hint: tuple | None = None,
                   gate: bool = False) -> ChainPlan:
        """Plan a chained product  x_1 (x) ... (x) x_n  as ONE pass.

        Ls: per-operand max degrees (n >= 2).  Lout defaults to sum(Ls).

        ``gate=True`` makes the equivariant gate (models.gate_apply) a
        chain-INTERIOR stage (DESIGN.md §6.5): applies take a required
        ``gate_params`` and return gate(product) — on the collocation
        backends the gate fuses into the kernel's pointwise stage (still
        ONE dispatch; l=0 scalars via `constants.chain_l0`), on tree/looped
        it runs at the exit (a resident 'fourier' exit gates the grid
        in-basis, so a whole TP -> gate -> selfmix layer keeps a single
        entry/exit conversion pair).  ``w_out`` applies after the gate.
        Gated plans key separately everywhere (plan cache and measured
        autotune: the measure key gains ("gate", 1), so ungated persisted
        entries stay valid).

        Backend dispatch (DESIGN.md §6.4): ``backend`` picks a chain
        realization from :data:`CHAIN_BACKENDS` — 'tree' (the resident
        spectral pass: convert each operand <= once, divide-and-conquer grid
        combine, one exit projection), 'looped' (per-product pairwise fold),
        'fused_xla' / 'fused_pallas' (the n-way collocation kernel: sample
        every operand onto the shared alias-free product grid, multiply
        pointwise n-way in VMEM, project once — the Pallas flavor is ONE
        MXU-resident `pallas_call`).  ``backend=None`` selects:

        * ``tune='measure'`` — chains fold into the engine's measured
          autotuner, keyed like plans (PlanKey kind='chain' with the Ls,
          ``batch_hint``, and ``entry_hint``): each candidate is jitted and
          timed on synthetic inputs, the winner cached in-process.
          ``entry_hint`` ('sh'|'fourier' per operand) makes the measurement
          honest for resident call sites: 'fourier' slots are timed as
          resident Reps, so a backend that must convert them back (looped)
          or sample them through the larger grid-entry matrix (fused) pays
          that cost in the timing it is judged by.  ``out_hint='fourier'``
          declares that applies will request a resident exit: 'looped'
          (which has none) is excluded, and every candidate is TIMED with
          that exit (tree skips its projection, fused projects through the
          wider grid-exit matrix — both must pay their real cost).
          ``share_hint`` gives the per-operand duplicate-group indices
          (selfmix ``[A]*nu`` -> (0,)*nu): synthetic operands repeat per
          group, so tree's single shared conversion engages in the timing
          exactly as at the real call.  Measurement needs a clean trace: planned inside a jit trace with
          no previously-seeded cache entry, selection silently stays 'tree'
          — seed the key eagerly first (serving warmup does).  This *replaces* the old
          shape-rule policy as the decision mechanism wherever measurement
          is engaged; `fused_pallas` is timed only on TPU (interpret mode is
          never a real option), and a live sharded mesh restricts candidates
          to 'tree' (the only backend with per-shard grid combination).
        * ``tune='heuristic'`` (default) — 'tree', the conservative resident
          pick whose <= 1-conversion-per-operand contract the counter tests
          certify.  An explicit ``conversion``/``conv`` also pins 'tree'
          (those knobs parameterize the spectral pipeline).

        conversion: 'half' (Hermitian real-input grids) or 'dense'; default
        (None) is 'half' — it halves conversion FLOPs for free.
        conv: grid-combination method — 'rfft' (half only), 'fft', 'direct';
        default (None) follows the measured crossover: 'direct' for a single
        small product (len == 2, max L <= 4, tiny grids where shift-and-add
        wins), 'rfft' otherwise (longer chains grow interior grids past the
        spatial-FFT crossover); dense conversions keep the historical
        direct/fft small-L rule.
        tree=True combines grids divide-and-conquer (the paper's many-body
        parallelization); False is the sequential left fold.

        dtype: the STORAGE dtype ('float32' | 'bfloat16' | 'float64';
        accumulation is always >= f32).  'auto' (with tune='measure') times
        the f32 and bf16 siblings of the measured key family and keeps bf16
        only where it actually wins; anywhere measurement cannot run it
        resolves to float32.

        donate=True donates the unique operand buffers through ``apply_jit``
        (callers must not reuse them); ``shard_spec`` runs the chain
        row-sharded over the mesh's data axes (see :class:`ShardSpec`) —
        both compose with residency, and sharded chains pad/slice their row
        axis so ragged row counts no longer need to divide the device count.

        On the spectral route every operand converts at most once
        (duplicates share a single degree-resolved conversion even with
        different per-degree weights), interior products stay in the Fourier
        basis, and a single projection runs at the exit; the collocation
        route converts *zero* times — resident operands enter as grids
        through the grid-evaluation sampling matrix — see :class:`ChainPlan`.
        """
        Ls = tuple(int(L) for L in Ls)
        if len(Ls) < 2:
            raise ValueError("chain plans need at least 2 operands")
        Lout = sum(Ls) if Lout is None else int(Lout)
        if Lout > sum(Ls):
            raise ValueError("Lout cannot exceed the total degree (Gaunt selection rule)")
        pinned_spectral = conversion is not None or conv is not None
        if conversion is None:
            conversion = "half"
        if conversion not in ("dense", "half"):
            raise ValueError(f"chain conversion must be 'dense'|'half', got {conversion!r}")
        if conv is None:
            if conversion == "half":
                conv = "direct" if (len(Ls) == 2 and max(Ls) <= 4) else "rfft"
            else:
                conv = spectral_default(*Ls)
        if conv == "rfft" and conversion != "half":
            raise ValueError("conv='rfft' operates on half grids (conversion='half')")
        mesh, dp = (None, ()) if shard_spec is None else shard_spec.resolve()
        mode = shard_spec.mode if shard_spec is not None else "constraint"
        if backend is not None and backend not in CHAIN_BACKENDS:
            raise ValueError(f"unknown chain backend {backend!r} "
                             f"(expected one of {CHAIN_BACKENDS})")
        if entry_hint is not None:
            entry_hint = tuple(entry_hint)
            if len(entry_hint) != len(Ls) or \
                    any(e not in ("sh", "fourier") for e in entry_hint):
                raise ValueError(f"entry_hint must be {len(Ls)} entries of "
                                 f"'sh'|'fourier', got {entry_hint!r}")
        if out_hint not in ("sh", "fourier"):
            raise ValueError(f"out_hint must be 'sh'|'fourier', got {out_hint!r}")
        if share_hint is not None:
            share_hint = tuple(int(g) for g in share_hint)
            if len(share_hint) != len(Ls):
                raise ValueError(f"share_hint must have {len(Ls)} group "
                                 f"indices, got {share_hint!r}")
        if isinstance(dtype, str) and dtype == "auto":
            dts = self._select_chain_dtype(
                Ls, Lout, batch_hint, sharded=bool(mesh is not None and dp),
                entry_hint=entry_hint, out_hint=out_hint,
                share_hint=share_hint, tune=tune, gate=gate)
        else:
            dts = _dtype_str(dtype)
        if backend is None:
            if pinned_spectral or tune != "measure":
                backend = "tree"
            else:
                backend = self._select_chain(Ls, Lout, dts, batch_hint,
                                             sharded=bool(mesh is not None and dp),
                                             entry_hint=entry_hint,
                                             out_hint=out_hint,
                                             share_hint=share_hint,
                                             gate=gate)
        key = (Ls, Lout, conversion, conv, dts, tree, donate, mesh, dp, mode,
               backend, gate)
        hit = self._chains.get(key)
        if hit is not None:
            return hit
        if backend == "tree":
            apply = _build_chain(Ls, Lout, conversion, conv, dts, tree,
                                 mesh, dp, mode)
            if gate:
                apply = _wrap_chain_gate(apply, Lout)
        elif backend == "looped":
            apply = _build_chain_looped(Ls, Lout, dts, self)
            if gate:
                apply = _wrap_chain_gate(apply, Lout)
        else:
            apply = _build_chain_fused(Ls, Lout, dts,
                                       pallas=(backend == "fused_pallas"),
                                       gate=gate)
            if mesh is not None and dp:
                # collocation is row-parallel: rank-aware row constraints on
                # the flattened operands/outputs let the partitioner shard it
                apply = _constrained_chain_apply(apply, mesh, dp)
        cp = ChainPlan(Ls=Ls, Lout=Lout, conversion=conversion, conv=conv,
                       dtype=dts, tree=tree, donate=donate,
                       shard=(mesh, dp, mode), backend=backend, gate=gate,
                       apply=apply)
        self._chains[key] = cp
        return cp

    def _select_chain(self, Ls: tuple, Lout: int, dts: str,
                      batch_hint: int | None, sharded: bool,
                      entry_hint: tuple | None = None,
                      out_hint: str = "sh",
                      share_hint: tuple | None = None,
                      gate: bool = False) -> str:
        """Measured chain-backend selection, cached like plan autotune.

        The measurement mirrors the real call as closely as the hints allow:
        ``entry_hint`` slots marked 'fourier' are synthesized as resident
        Reps (looped pays its per-call to_sh, fused pays the grid-entry
        sampling matrix), ``out_hint`` sets the out_basis the candidates are
        TIMED with (a resident exit skips tree's projection and widens
        fused's), and ``share_hint`` repeats one synthetic buffer per
        duplicate group so tree's shared-operand single conversion engages
        — a mismatched measurement would install a backend whose real-world
        cost was never measured.  Deliberately NOT mirrored: per-degree
        weights (their _wmul/bydeg cost is one ordinary conversion's FLOPs
        regardless of backend — a second-order effect on the ranking), and
        exact row counts — ``batch_hint`` quantizes to a power-of-two ladder
        capped at 16384, so ragged eager workloads share a handful of
        measurements instead of re-benchmarking (and re-allocating
        synthetic operands for) every distinct size.
        """
        if sharded:
            return "tree"  # the only backend with per-shard grid combination
        key = self._chain_measure_key(Ls, Lout, dts, batch_hint, entry_hint,
                                      out_hint, share_hint, gate=gate)
        batch_hint = key.batch_hint
        entries, share = key.opt("entries"), key.opt("share")
        # consult the persisted table before the trace-clean bail: loading
        # JSON is host-side Python, safe inside a trace, and a traced miss
        # should still reuse a measurement another process already ran
        self._maybe_load_cache()
        hit = self._measured.get(key)
        if hit is not None:
            return hit
        if not _trace_clean():
            return "tree"  # timing inside a trace is meaningless
        self.timing_runs += 1
        candidates = ["tree", "fused_xla"]
        if out_hint == "sh":
            candidates.insert(1, "looped")  # no resident exit on the fold
        if jax.default_backend() == "tpu":
            candidates.append("fused_pallas")
        B = batch_hint or 256
        rng = np.random.default_rng(0)
        rd = _RDTYPE[dts]
        from .rep import Rep

        xs, made = [], {}
        for L, e, g in zip(Ls, entries, share):
            x = made.get((g, L, e))
            if x is None:
                x = jnp.asarray(rng.normal(size=(B, num_coeffs(L))), dtype=rd)
                if e == "fourier":
                    x = Rep.from_sh(x, L).to_fourier("half")
                made[(g, L, e)] = x
            xs.append(x)
        # synthetic gate MLP sized so the per-row scalar path costs what the
        # real [rows, channels] call costs (the synthetic lead is bare [B],
        # so the MLP contracts B with a hidden width of 16 — same FLOPs
        # shape as the models' [n, C] @ [C, 16] gate head)
        gp = ({"w1": jnp.asarray(rng.normal(size=(B, 16)), jnp.float32),
               "w2": jnp.asarray(rng.normal(size=(16, B)), jnp.float32)}
              if gate else None)
        best_name, best_t = "tree", float("inf")
        for name in candidates:
            try:
                cp = self.plan_chain(Ls, Lout, dtype=dts, backend=name,
                                     gate=gate)
                # eager apply, not a fresh jit: apply_jit is the consumer
                # route and its pre-jit dedup is exactly what makes shared
                # operands convert once in tree's real cost
                fn = (lambda _c=cp: jax.block_until_ready(
                    _c.apply_jit(xs, out_basis=out_hint, gate_params=gp)))
                fn()  # compile + warm
                ts = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    fn()
                    ts.append(time.perf_counter() - t0)
                t = sorted(ts)[1]
            except Exception as e:  # noqa: BLE001 — recorded, then loses
                self._record_failure("chain", key, name, e)
                continue
            if t < best_t:
                best_name, best_t = name, t
        if best_t == float("inf"):
            # every candidate (including tree) raised: nothing was ever
            # successfully run, so there is no measurement to cache — return
            # the safe default WITHOUT pinning it, mirroring _measure's
            # cost-model fallback, and let a later (healthier) call re-time
            return "tree"
        self._measured[key] = best_name
        self._measured_t[key] = best_t
        self._autoflush()
        return best_name

    @staticmethod
    def _chain_measure_key(Ls: tuple, Lout: int, dts: str,
                           batch_hint: int | None, entry_hint: tuple | None,
                           out_hint: str, share_hint: tuple | None,
                           gate: bool = False) -> PlanKey:
        """The measured-autotune cache key for one chain shape.  Keys that
        differ only in ``dtype`` form one family (``PlanKey.with_dtype``);
        'auto' is a valid member naming the family's resolved winner.
        Gated chains append ("gate", 1) — ONLY when gated, so ungated keys
        (and every persisted pre-gate cache entry) stay byte-identical."""
        if batch_hint is not None:
            q = 8
            while q < min(batch_hint, 16384):
                q *= 2
            batch_hint = q
        entries = entry_hint or ("sh",) * len(Ls)
        share = share_hint or tuple(range(len(Ls)))
        extra = (("Ls", Ls), ("entries", entries),
                 ("out", out_hint), ("share", share))
        if gate:
            extra = extra + (("gate", 1),)
        return PlanKey(max(Ls), min(Ls), Lout, kind="chain",
                       batch_hint=batch_hint, dtype=dts, extra=extra)

    def _select_chain_dtype(self, Ls: tuple, Lout: int,
                            batch_hint: int | None, sharded: bool,
                            entry_hint: tuple | None, out_hint: str,
                            share_hint: tuple | None, tune: str,
                            gate: bool = False) -> str:
        """Resolve a chain ``dtype='auto'`` request: measure the f32 and bf16
        siblings of the key family and keep bf16 only where it actually wins.
        Falls back to float32 whenever measurement cannot run (heuristic
        mode, dirty trace, sharded mesh)."""
        auto_key = self._chain_measure_key(Ls, Lout, "auto", batch_hint,
                                           entry_hint, out_hint, share_hint,
                                           gate=gate)
        self._maybe_load_cache()
        hit = self._measured.get(auto_key)
        if hit is not None:
            return hit
        if sharded or tune != "measure" or not _trace_clean():
            return "float32"
        times = {}
        for dts in ("float32", "bfloat16"):
            self._select_chain(Ls, Lout, dts, batch_hint, sharded=False,
                               entry_hint=entry_hint, out_hint=out_hint,
                               share_hint=share_hint, gate=gate)
            t = self._measured_t.get(self._chain_measure_key(
                Ls, Lout, dts, batch_hint, entry_hint, out_hint, share_hint,
                gate=gate))
            if t is not None:
                times[dts] = t
        winner = "bfloat16" if times.get("bfloat16", float("inf")) < \
            times.get("float32", float("inf")) else "float32"
        if times:
            # cache the winner only when at least one sibling actually
            # produced a timing — an all-candidate failure must not become
            # a process-lifetime (or persisted) precision decision
            self._measured[auto_key] = winner
            self._autoflush()
        return winner

    def select_gate(self, Ls, Lout: int | None = None, *, dtype="float32",
                    batch_hint: int | None = None,
                    entry_hint: tuple | None = None, out_hint: str = "sh",
                    share_hint: tuple | None = None) -> str:
        """Measured grid-vs-SH gate policy for one chain workload — the
        decision behind ``cfg.grid_gate='auto'``.

        Times the gate-fused chain plan (`plan_chain(..., gate=True)`)
        against the ungated plan followed by the SH gate epilogue; for a
        resident ``out_hint='fourier'`` the epilogue pays the full
        exit -> gate -> re-entry round trip, which is exactly what fusion
        elides.  Returns 'grid' | 'sh'.  Keyed like chain plans (the chain
        measure key + ("gate", "policy")), cached in-process, persisted
        with the autotune table; inside a jit trace an unseeded key
        resolves to 'sh' (the safe no-reorder default) without caching.
        """
        Ls = tuple(int(L) for L in Ls)
        Lout = sum(Ls) if Lout is None else int(Lout)
        if isinstance(dtype, str) and dtype == "auto":
            dts = self._select_chain_dtype(
                Ls, Lout, batch_hint, sharded=False, entry_hint=entry_hint,
                out_hint=out_hint, share_hint=share_hint, tune="measure",
                gate=True)
        else:
            dts = _dtype_str(dtype)
        base = self._chain_measure_key(Ls, Lout, dts, batch_hint, entry_hint,
                                       out_hint, share_hint)
        key = dataclasses.replace(base,
                                  extra=base.extra + (("gate", "policy"),))
        self._maybe_load_cache()
        hit = self._measured.get(key)
        if hit is not None:
            return hit
        if not _trace_clean():
            return "sh"
        entries, share = base.opt("entries"), base.opt("share")
        B = base.batch_hint or 256
        rng = np.random.default_rng(0)
        rd = _RDTYPE[dts]
        from .rep import Rep

        xs, made = [], {}
        for L, e, g in zip(Ls, entries, share):
            x = made.get((g, L, e))
            if x is None:
                x = jnp.asarray(rng.normal(size=(B, num_coeffs(L))), dtype=rd)
                if e == "fourier":
                    x = Rep.from_sh(x, L).to_fourier("half")
                made[(g, L, e)] = x
            xs.append(x)
        gp = {"w1": jnp.asarray(rng.normal(size=(B, 16)), jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(16, B)), jnp.float32)}
        self.timing_runs += 1

        def _time(fn):
            fn()  # compile + warm
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            return sorted(ts)[1]

        kw = dict(dtype=dts, tune="measure", batch_hint=batch_hint,
                  entry_hint=entry_hint, out_hint=out_hint,
                  share_hint=share_hint)
        try:
            cpg = self.plan_chain(Ls, Lout, gate=True, **kw)
            cps = self.plan_chain(Ls, Lout, **kw)

            def grid_fn():
                jax.block_until_ready(
                    cpg.apply_jit(xs, out_basis=out_hint, gate_params=gp))

            if out_hint == "fourier":

                def sh_fn():
                    rep = cps.apply_jit(xs, out_basis="fourier")
                    sh = rep.to_sh()
                    out = Rep.from_sh(_gate_sh(gp, sh.data),
                                      rep.L).to_fourier("half")
                    jax.block_until_ready(out.data)

            else:

                def sh_fn():
                    jax.block_until_ready(_gate_sh(gp, cps.apply_jit(xs)))

            tg, tsh = _time(grid_fn), _time(sh_fn)
        except Exception as e:  # noqa: BLE001 — recorded; failure means 'sh'
            self._record_failure("gate", key, "grid-vs-sh", e)
            return "sh"
        winner = "grid" if tg < tsh else "sh"
        self._measured[key] = winner
        self._measured_t[key] = min(tg, tsh)
        self._autoflush()
        return winner

    def _select_dtype(self, make_key: Callable, tune: str,
                      requires_grad: bool) -> str:
        """Resolve a plan ``dtype='auto'`` request (pairwise/conv/manybody/
        channel_mix): time the best backend of each precision sibling under
        one key family and pick bf16 only where it beats f32.  Heuristic
        mode or a dirty trace resolves to float32 without measuring."""
        auto_key = make_key("auto")
        self._maybe_load_cache()
        hit = self._measured.get(auto_key)
        if hit is not None:
            return hit
        if tune != "measure" or not _trace_clean():
            return "float32"
        times = {}
        for dts in ("float32", "bfloat16"):
            key = make_key(dts)
            eligible = [b for b in _REGISTRY.values()
                        if b.eligible(key, requires_grad)]
            if not eligible:
                continue
            name = self._measured.get(key)
            if name is None:
                name, t = self._measure(key, eligible)
                if t is None:
                    continue  # cost-model fallback: nothing was timed
                self._measured[key] = name
                self._measured_t[key] = t
            t = self._measured_t.get(key)
            if t is not None:
                times[dts] = t
        winner = "bfloat16" if times.get("bfloat16", float("inf")) < \
            times.get("float32", float("inf")) else "float32"
        if times:
            # same rule as the chain variant: no timings, no cached winner
            self._measured[auto_key] = winner
            self._autoflush()
        return winner

    def select(self, key: PlanKey, tune: str = "heuristic",
               requires_grad: bool = True) -> str:
        """Pick the backend for ``key`` by cost model or measurement."""
        eligible = [b for b in _REGISTRY.values() if b.eligible(key, requires_grad)]
        if not eligible:
            raise ValueError(f"no eligible backend for {key}")
        if tune == "measure":
            # load (and consult) the persisted table even inside a trace —
            # the JSON load is host-side Python; only *timing* needs a
            # clean trace
            self._maybe_load_cache()
            hit = self._measured.get(key)
            if hit is not None and any(b.name == hit for b in eligible):
                # the eligibility re-check guards persisted hits: a file
                # written under requires_grad=False may name a gradless
                # backend this call can't use — fall through and re-measure
                return hit
            if _trace_clean():
                name, t = self._measure(key, eligible)
                if t is not None:
                    self._measured[key] = name
                    self._measured_t[key] = t
                    self._autoflush()
                return name
        return min(eligible, key=lambda b: b.cost(key)).name

    def plans(self) -> list[GauntPlan]:
        return list(self._plans.values())

    def clear(self) -> None:
        self._plans.clear()
        self._batched.clear()
        self._chains.clear()
        self._measured.clear()
        self._measured_t.clear()
        # a cleared engine must behave like a fresh one: re-arm the lazy
        # persistent-cache load
        self._cache_loaded = False
        self.timing_runs = 0
        self.autotune_failures.clear()

    # -- measured autotune -------------------------------------------------

    def _record_failure(self, site: str, key: PlanKey, candidate: str,
                        err: Exception) -> None:
        self.autotune_failures.append({
            "site": site, "key": repr(key), "candidate": candidate,
            "error": f"{type(err).__name__}: {err}"})

    def _measure(self, key: PlanKey,
                 eligible: list[Backend]) -> tuple[str, float | None]:
        """Time the eligible backends on synthetic inputs.  -> (name, t);
        ``t`` is None when every backend failed and ``name`` is only the
        cost-model fallback — callers must NOT cache that as a measurement."""
        args = _synthetic_inputs(key)
        self.timing_runs += 1
        best_name, best_t = None, float("inf")
        for spec in eligible:
            if spec.needs_interpret and jax.default_backend() != "tpu":
                continue  # interpret-mode timing is meaningless
            try:
                apply = spec.build(key)
                if key.kind == "conv_filter" and spec.name != "escn_aligned":
                    apply = _wrap_conv_filter(key, apply)
                fn = jax.jit(lambda *a: apply(*a))
                jax.block_until_ready(fn(*args))  # compile + warm
                ts = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    ts.append(time.perf_counter() - t0)
                t = sorted(ts)[1]
            except Exception as e:  # noqa: BLE001 — recorded, then loses
                self._record_failure("plan", key, spec.name, e)
                continue
            if t < best_t:
                best_name, best_t = spec.name, t
        if best_name is None:  # everything failed: fall back to the cost model
            return min(eligible, key=lambda b: b.cost(key)).name, None
        return best_name, best_t


def _trace_clean() -> bool:
    """True outside every JAX transformation (jit, grad, vmap tracing) —
    the only place a timing measures the device."""
    return jax.core.trace_ctx.is_top_level()


def _synthetic_inputs(key: PlanKey):
    B = key.batch_hint or 256
    rd = _RDTYPE[key.dtype]
    rng = np.random.default_rng(0)

    def r(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype=rd)

    if key.kind == "pairwise":
        return r(B, num_coeffs(key.L1)), r(B, num_coeffs(key.L2))
    if key.kind == "conv_filter":
        v = rng.normal(size=(B, 3))
        v /= np.linalg.norm(v, axis=-1, keepdims=True)
        return r(B, num_coeffs(key.L1)), jnp.asarray(v, dtype=jnp.float32)
    if key.kind == "manybody":
        Ls = key.opt("Ls")
        return ([r(B, num_coeffs(L)) for L in Ls],)
    # channel_mix: small representative channel counts
    C1 = C2 = E = 4
    return (r(B, C1, num_coeffs(key.L1)), r(B, C2, num_coeffs(key.L2)),
            r(C1, C2, E))


_ENGINE = GauntEngine()


def get_engine() -> GauntEngine:
    """The process-wide engine (plan + autotune caches are shared)."""
    return _ENGINE


def plan(*args, **kw) -> GauntPlan:
    """Module-level shorthand for ``get_engine().plan(...)``."""
    return _ENGINE.plan(*args, **kw)


def plan_batch(*args, **kw) -> BatchedGauntPlan:
    """Module-level shorthand for ``get_engine().plan_batch(...)``."""
    return _ENGINE.plan_batch(*args, **kw)


def plan_chain(*args, **kw) -> ChainPlan:
    """Module-level shorthand for ``get_engine().plan_chain(...)``."""
    return _ENGINE.plan_chain(*args, **kw)
