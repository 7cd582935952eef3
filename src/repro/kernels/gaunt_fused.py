"""Fused Gaunt tensor product Pallas TPU kernels — sample * multiply * project.

TPU adaptation of the paper's FFT pipeline (see DESIGN.md §3): instead of
(complex s2f -> FFT conv -> complex f2s) we use the mathematically identical
*collocation* form on the torus grid,

    out = ((x1 @ T1) .* (x2 @ T2) .* ... .* (xn @ Tn)) @ P

with  T_i[j, g]   = S_j(theta_g, psi_g)        (real SH sampled on the grid)
      P[g, k]     = Re((1/G) sum_{u,v} e^{-i(u t_g + v p_g)} z^{k}_{u,v})

for any chain length n >= 2 (`gaunt_chain_fused_pallas`; the historical
pairwise `gaunt_fused_pallas` is the n = 2 wrapper).

Exactness: the product of n bandlimited spherical functions is bandlimited
at sum(L_i) on the torus double cover; an N x N grid with N >= 2*sum(L_i)+1
samples it alias-free, so the discrete projection equals the paper's
convolution-theorem result to machine precision (tested).

Why this shape for TPU: n+1 dense real matmuls hit the MXU back-to-back with
VMEM-resident elementwise multiplies between them — a whole ChainPlan is ONE
`pallas_call` instead of n+2 XLA ops; the FFT path (VPU butterflies on tiny
grids) and gather-based sparse conversions are far from MXU peak at
practical L.  Large product grids (high sum(L_i)) are handled by blocking
the grid axis and accumulating partial projections in the output block, so
per-step VMEM stays bounded; batch rows block as before.  All operands are
zero-padded to lane/tile boundaries (8 x 128) outside the kernel.

Fourier-resident operands enter *as grids*: their real-stacked half grid
multiplies the grid-evaluation matrix (`constants.chain_sample_grid`)
instead of the SH sampling matrix — same kernel, no sh_to_fourier, and a
'grid' exit returns the resident half product grid (`chain_project_grid`).

The chain kernel carries a custom JVP (the collocation is multilinear in its
operands: dout = (sum_i (dx_i @ T_i) * prod_{j!=i} V_j) @ P, run as plain
jnp), so chain plans on the kernel backend support grad, and grad of grad —
unlike the historical pairwise `fused_pallas` backend.

Mixed precision (DESIGN.md §3.6): every runner takes a *storage* dtype
('float32' | 'bfloat16' | 'float64') governing operand and sampling-matrix
(T_i) storage; the MXU accumulates at >= f32 via ``preferred_element_type``
and the projection matrix P plus the output stay at the accumulation dtype.
bf16 halves operand/constant bytes, so the default VMEM blocks
(`block_b`/`block_g`) double and the row-block floor rises to the bf16
sublane tile (16 x 128).

``kernel_stats()`` counts kernel dispatches (ticked once per trace/eager
call), letting tests *prove* the one-`pallas_call` claim instead of assuming
it.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "gaunt_fused_matrices",
    "gaunt_fused_pallas",
    "gaunt_chain_fused_pallas",
    "gaunt_chain_fused_xla",
    "kernel_stats",
    "reset_kernel_stats",
]


# ticked once per wrapper call (eager) or trace (jit) — the proof counters
# behind "a >= 3-operand chain runs as ONE pallas_call"; interpret_calls
# counts the calls of either kernel that ran in interpret mode (any call
# off the TPU), so a chip run can prove the compiled kernel ran
_STATS = {"pairwise_pallas_calls": 0, "chain_pallas_calls": 0,
          "interpret_calls": 0}


def kernel_stats() -> dict:
    """{'pairwise_pallas_calls', 'chain_pallas_calls', 'interpret_calls'}
    counts since reset."""
    return dict(_STATS)


def reset_kernel_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def gaunt_fused_matrices(L1: int, L2: int, Lout: int, pad_lanes: bool = True,
                         dtype: str = "float32"):
    """Numpy (T1 [d1,G], T2 [d2,G], P [G,dout]) — exact.

    Back-compat alias: the builder (and its cache) lives in the engine's
    constant-cache module, `repro.core.constants.fused_matrices`.
    """
    from repro.core.constants import fused_matrices

    return fused_matrices(L1, L2, Lout, pad_lanes, dtype=dtype)


# storage-dtype resolution for every kernel entry point: an explicit request
# wins; otherwise the operands' jnp promotion decides (bfloat16 only when
# EVERY operand is bf16 — a mixed bf16/f32 chain promotes to f32 storage),
# complex residents map to their real width, and float64 storage only exists
# under x64 (it is interpret-only: no accelerator lowers it).
def _storage_dtype(xs, dtype) -> str:
    if dtype is None:
        rt = jnp.result_type(*xs)
        name = {"complex64": "float32", "complex128": "float64"}.get(
            rt.name, rt.name)
    else:
        name = dtype if isinstance(dtype, str) else jnp.dtype(dtype).name
    if name not in ("float32", "bfloat16", "float64"):
        name = "float32"
    if name == "float64" and not jax.config.jax_enable_x64:
        name = "float32"
    return name


def _kernel(x1_ref, x2_ref, t1_ref, t2_ref, p_ref, o_ref):
    v1 = jnp.dot(x1_ref[...], t1_ref[...], preferred_element_type=jnp.float32)
    v2 = jnp.dot(x2_ref[...], t2_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = jnp.dot(v1 * v2, p_ref[...], preferred_element_type=jnp.float32)


def _make_chain_kernel(n: int, acc_dt, gated: bool = False):
    """The n-operand collocation kernel body.

    Grid is (row blocks, grid blocks): for one row block the kernel walks the
    (lane-padded) sample axis in `block_g` slices — sample every operand onto
    the slice, multiply n-way in VMEM, project the slice, and accumulate into
    the output block (revisited across the minor grid axis, the standard
    k-accumulation pattern).  Padded sample columns are zero in every T AND
    carry zero projection rows, so they contribute nothing.

    ``gated`` adds the fused pointwise-gate stage (DESIGN.md §6.5): two extra
    per-row scalar inputs (gs, gb — the affine form of `gate_apply` given its
    l=0 scalars, computed outside the kernel) scale-and-shift the VMEM-
    resident product values *before* projection: ``v <- v*gs + gb``.  Padded
    sample columns pick up the constant ``gb`` but their projection rows are
    zero; padded batch rows carry gs = gb = 0, so both stay inert.
    """

    def kernel(*refs):
        xs, ts = refs[:n], refs[n: 2 * n]
        p_ref, o_ref = refs[2 * n], refs[-1]
        v = jnp.dot(xs[0][...], ts[0][...], preferred_element_type=acc_dt)
        for x_ref, t_ref in zip(xs[1:], ts[1:]):
            v = v * jnp.dot(x_ref[...], t_ref[...], preferred_element_type=acc_dt)
        if gated:
            gs_ref, gb_ref = refs[2 * n + 1], refs[2 * n + 2]
            v = v * gs_ref[...] + gb_ref[...]
        part = jnp.dot(v, p_ref[...], preferred_element_type=acc_dt)
        g = pl.program_id(1)

        @pl.when(g == 0)
        def _init():
            o_ref[...] = part

        @pl.when(g != 0)
        def _accumulate():
            o_ref[...] = o_ref[...] + part

    return kernel


def _pad_axis(a: np.ndarray, axis: int, to: int) -> np.ndarray:
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, to - a.shape[axis])
    return np.pad(a, pad)


@lru_cache(maxsize=None)
def _chain_runner(Ls: tuple, Lout: int, entries: tuple, out_entry: str,
                  block_b: int, block_g: int, interpret: bool, sdt: str,
                  gated: bool = False):
    """A cached, custom-JVP'd row-level chain runner for one static config.

    Takes the tuple of row-flattened operands ([Bp, d_i], already padded to a
    multiple of ``block_b``) and returns [Bp, dout] — ONE `pallas_call`.
    The JVP reuses the same collocation matrices in plain jnp (dout =
    (sum_i (dx_i @ T_i) * prod_{j != i} V_j) @ P, linear in the tangents), so
    JAX transposes it for reverse mode and differentiates it again for
    higher orders — force-matched training takes the gradient of forces,
    which are themselves a gradient — while every primal evaluation stays a
    single kernel.  (Pallas has no derivative rule of its own for this
    kernel: its grid program ids cannot be differentiated.)

    ``sdt`` is the storage dtype: operands and sampling matrices T_i live at
    ``sdt``, every dot accumulates at the >= f32 accumulation dtype, and the
    projection matrix P plus the output stay at the accumulation dtype.

    ``gated`` runners take two extra row-scalar arrays ([Bp, 1], at the
    accumulation dtype): ``run(arrs, gs, gb)`` applies ``v <- v*gs + gb`` to
    the product values between the n-way multiply and the projection —
    still ONE `pallas_call`.  The JVP extends accordingly: with V the
    pre-gate product grid, the tangent is (dV*gs + V*dgs + dgb) @ P.
    """
    from repro.core.constants import chain_matrices

    acc_dt = jnp.float64 if sdt == "float64" else jnp.float32
    acc_np = "float64" if sdt == "float64" else "float32"
    Ts, _ = chain_matrices(Ls, Lout, entries, out_entry, dtype=sdt)
    _, P = chain_matrices(Ls, Lout, entries, out_entry, dtype=acc_np)
    G = Ts[0].shape[1]
    Gp = -(-G // block_g) * block_g  # zero-pad: inert sample columns/rows
    Ts = tuple(_pad_axis(T, 1, Gp) for T in Ts)
    P = _pad_axis(P, 0, Gp)
    dout = P.shape[1]
    n = len(Ls)
    kernel = _make_chain_kernel(n, acc_dt, gated)

    def _call(arrs, gate_arrs=()):
        Bp = arrs[0].shape[0]
        d_in = [T.shape[0] for T in Ts]
        in_specs = (
            [pl.BlockSpec((block_b, d), lambda i, g: (i, 0)) for d in d_in]
            + [pl.BlockSpec((d, block_g), lambda i, g: (0, g)) for d in d_in]
            + [pl.BlockSpec((block_g, dout), lambda i, g: (g, 0))]
            + [pl.BlockSpec((block_b, 1), lambda i, g: (i, 0))
               for _ in gate_arrs]
        )
        return pl.pallas_call(
            kernel,
            grid=(Bp // block_b, Gp // block_g),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_b, dout), lambda i, g: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Bp, dout), acc_dt),
            interpret=interpret,
            name="gaunt_chain_fused",
        )(*arrs, *(jnp.asarray(T) for T in Ts), jnp.asarray(P), *gate_arrs)

    def _tangent(arrs, darrs, gs=None, dgs=None, dgb=None):
        # same storage discipline as the forward: operands and T stay at
        # ``sdt`` into the MXU, accumulation at acc_dt via preferred dtype
        Tj = [jnp.asarray(T) for T in Ts]
        Vs = [jnp.dot(a, T, preferred_element_type=acc_dt)
              for a, T in zip(arrs, Tj)]
        dVs = [jnp.dot(da.astype(a.dtype), T, preferred_element_type=acc_dt)
               for a, da, T in zip(arrs, darrs, Tj)]
        dV = 0.0
        for i in range(n):
            term = dVs[i]
            for j in range(n):
                if j != i:
                    term = term * Vs[j]
            dV = dV + term
        if gs is not None:
            V = Vs[0]
            for Vj in Vs[1:]:
                V = V * Vj
            dV = dV * gs.astype(acc_dt) + V * dgs.astype(acc_dt) \
                + dgb.astype(acc_dt)
        return dV @ jnp.asarray(P)

    if gated:

        @jax.custom_jvp
        def run(arrs, gs, gb):
            return _call(arrs, (gs, gb))

        @run.defjvp
        def _jvp(primals, tangents):
            (arrs, gs, gb), (darrs, dgs, dgb) = primals, tangents
            return run(arrs, gs, gb), _tangent(arrs, darrs, gs, dgs, dgb)

    else:

        @jax.custom_jvp
        def run(arrs):
            return _call(arrs)

        @run.defjvp
        def _jvp(primals, tangents):
            (arrs,), (darrs,) = primals, tangents
            return run(arrs), _tangent(arrs, darrs)

    return run, dout


def _chain_prepare(xs, Ls, entries):
    """Broadcast/flatten chain operands to row layout [B, d_i].

    'grid' entries arrive as complex half grids [..., 2L+1, L+1] and stack
    into real vectors [..., 2*(2L+1)*(L+1)] = [Re F; Im F].
    """
    flat = []
    for x, L, e in zip(xs, Ls, entries):
        if e == "grid":
            lead = x.shape[:-2]
            F = x.reshape(*lead, -1)
            x = jnp.concatenate([F.real, F.imag], axis=-1)
        flat.append(x)
    lead = jnp.broadcast_shapes(*[a.shape[:-1] for a in flat])
    B = int(np.prod(lead)) if lead else 1
    flat = [jnp.broadcast_to(a, lead + a.shape[-1:]).reshape(B, a.shape[-1])
            for a in flat]
    return flat, lead, B


def _chain_finish(out, lead, Lout: int, out_entry: str):
    if out_entry == "grid":
        half = out.shape[-1] // 2
        F = jax.lax.complex(out[..., :half], out[..., half:])
        return F.reshape(*lead, 2 * Lout + 1, Lout + 1)
    return out.reshape(*lead, out.shape[-1])


def gaunt_chain_fused_pallas(
    xs,
    Ls,
    Lout: int | None = None,
    *,
    entries: tuple | None = None,
    out_entry: str = "sh",
    block_b: int | None = None,
    block_g: int | None = None,
    interpret: bool | None = None,
    dtype: str | None = None,
    gate=None,
):
    """n-way fused chain Gaunt product — ONE `pallas_call`.

    xs      : per-operand arrays; entry 'sh' is packed SH [..., (L_i+1)^2],
              entry 'grid' is the Fourier-resident half grid
              [..., 2L_i+1, L_i+1] (complex — it enters the kernel as its
              real-stacked form and skips the SH sampling matmul).
    Lout    : exit degree (default sum(Ls)); out_entry 'sh' returns packed SH
              [..., (Lout+1)^2], 'grid' the resident half product grid.
    block_b : row-block size; block_g: sample-axis block (multiple of 128)
              — large product grids accumulate across grid blocks in VMEM.
              Defaults double under bf16 storage (half the bytes per block).
    dtype   : storage dtype ('float32'|'bfloat16'|'float64'); None infers
              from the operands (bf16 only when ALL operands are bf16).
              Operands are cast to it once at entry; accumulation is always
              >= f32 and the output comes back at the accumulation dtype.
    gate    : optional (gs, gb) pair of per-row scalars (each broadcastable
              to the operands' leading shape): the fused pointwise stage
              applies ``v <- v*gs + gb`` to the VMEM-resident product values
              before projection — `gate_apply` in its affine form, for free
              inside the same single `pallas_call` (DESIGN.md §6.5).

    float64 storage exists only under x64 and is interpret-only (TPUs have
    no f64).  Differentiable to any order via the collocation JVP (extended
    with dgs/dgb when gated).
    """
    Ls = tuple(int(L) for L in Ls)
    Lout = sum(Ls) if Lout is None else int(Lout)
    entries = ("sh",) * len(Ls) if entries is None else tuple(entries)
    if len(xs) != len(Ls) or len(entries) != len(Ls):
        raise ValueError(f"chain kernel got {len(xs)} operands / "
                         f"{len(entries)} entries for degrees {Ls}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sdt = _storage_dtype(xs, dtype)
    if sdt == "float64":
        interpret = True  # f64 is interpret-only: no accelerator lowers it
    bf16 = sdt == "bfloat16"
    if block_b is None:
        block_b = 512 if bf16 else 256
    if block_g is None:
        block_g = 1024 if bf16 else 512
    flat, lead, B = _chain_prepare(xs, Ls, entries)
    # clamp the row block to the batch, quantized to powers of two: tiny
    # batches avoid 50x zero-row padding, while the quantization bounds the
    # per-config `_chain_runner` cache at ~6 entries (8..block_b) even for
    # callers with ragged eager batch sizes.  bf16 sublane tiles are 16 rows
    # (f32: 8), so the bf16 floor is one full tile.
    eff_b = 16 if bf16 else 8
    while eff_b < min(block_b, B):
        eff_b *= 2
    block_b = min(block_b, eff_b)
    block_g = max(128, (block_g // 128) * 128)
    run, dout = _chain_runner(Ls, Lout, entries, out_entry, block_b, block_g,
                              bool(interpret), sdt, gate is not None)
    _STATS["chain_pallas_calls"] += 1
    _STATS["interpret_calls"] += bool(interpret)
    Bp = -(-B // block_b) * block_b
    st_dt = jnp.dtype(sdt)
    flat = [jnp.zeros((Bp, a.shape[-1]), st_dt).at[:B].set(a.astype(st_dt))
            for a in flat]
    if gate is not None:
        acc_dt = jnp.float64 if sdt == "float64" else jnp.float32
        pads = [jnp.zeros((Bp, 1), acc_dt).at[:B].set(
                    jnp.broadcast_to(g, lead).reshape(B, 1).astype(acc_dt))
                for g in gate]
        out = run(tuple(flat), *pads)[:B]
    else:
        out = run(tuple(flat))[:B]
    return _chain_finish(out, lead, sum(Ls), out_entry)


def gaunt_chain_fused_xla(
    xs,
    Ls,
    Lout: int | None = None,
    *,
    entries: tuple | None = None,
    out_entry: str = "sh",
    dtype: str | None = None,
    gate=None,
):
    """The chain collocation math as plain jnp (XLA) — the same matrices,
    no Pallas.  Grad/vmap/dtype support come for free; off-TPU this is the
    fast realization of the chain kernel (interpret mode never is).

    Same storage rule as the Pallas runner: operands and T_i at the storage
    dtype, >= f32 accumulation via ``preferred_element_type``, P and the
    output at the accumulation dtype.  ``gate=(gs, gb)`` applies the same
    fused pointwise stage as the Pallas runner (``v <- v*gs + gb`` on the
    product values before projection).
    """
    from repro.core.constants import chain_matrices

    Ls = tuple(int(L) for L in Ls)
    Lout = sum(Ls) if Lout is None else int(Lout)
    entries = ("sh",) * len(Ls) if entries is None else tuple(entries)
    sdt = _storage_dtype(xs, dtype)
    st_dt = jnp.dtype(sdt)
    acc_dt = jnp.float64 if sdt == "float64" else jnp.float32
    acc_np = "float64" if sdt == "float64" else "float32"
    Ts, _ = chain_matrices(Ls, Lout, entries, out_entry, dtype=sdt)
    _, P = chain_matrices(Ls, Lout, entries, out_entry, dtype=acc_np)
    flat, lead, B = _chain_prepare(xs, Ls, entries)
    v = jnp.dot(flat[0].astype(st_dt), jnp.asarray(Ts[0]),
                preferred_element_type=acc_dt)
    for a, T in zip(flat[1:], Ts[1:]):
        v = v * jnp.dot(a.astype(st_dt), jnp.asarray(T),
                        preferred_element_type=acc_dt)
    if gate is not None:
        gs, gb = (jnp.broadcast_to(g, lead).reshape(B, 1).astype(acc_dt)
                  for g in gate)
        v = v * gs + gb
    out = v @ jnp.asarray(P)
    return _chain_finish(out, lead, sum(Ls), out_entry)


def gaunt_fused_pallas(
    x1,
    x2,
    L1: int,
    L2: int,
    Lout: int | None = None,
    block_b: int | None = None,
    interpret: bool | None = None,
    dtype: str | None = None,
):
    """Fused Gaunt TP.  x1 [..., d1], x2 [..., d2] -> [..., dout].

    Leading dims are flattened into a row-block grid; T1/T2/P stay fully
    VMEM-resident per block (they are tiny: L=8 -> T 81x1156 f32 = 375 KiB).

    ``dtype`` is the storage dtype (operands + T1/T2; None infers from the
    inputs); the MXU accumulates at f32 and P/the output stay f32.  The
    default row block doubles under bf16 storage.
    """
    from repro.core.constants import chain_matrices
    from repro.core.irreps import num_coeffs

    Lout = L1 + L2 if Lout is None else Lout
    sdt = _storage_dtype((x1, x2), dtype)
    if sdt == "float64":
        sdt = "float32"  # the pairwise kernel is f32/bf16-storage only
    st_dt = jnp.dtype(sdt)
    if block_b is None:
        block_b = 512 if sdt == "bfloat16" else 256
    (T1, T2), _ = chain_matrices((L1, L2), Lout, ("sh", "sh"), "sh", dtype=sdt)
    _, P = chain_matrices((L1, L2), Lout, ("sh", "sh"), "sh", dtype="float32")
    T1, T2, P = (jnp.asarray(a) for a in (T1, T2, P))
    batch = x1.shape[:-1]
    B = int(np.prod(batch)) if batch else 1
    d1, d2, dout = num_coeffs(L1), num_coeffs(L2), num_coeffs(Lout)
    Bp = ((B + block_b - 1) // block_b) * block_b
    a1 = jnp.zeros((Bp, d1), st_dt).at[:B].set(x1.reshape(B, d1).astype(st_dt))
    a2 = jnp.zeros((Bp, d2), st_dt).at[:B].set(x2.reshape(B, d2).astype(st_dt))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G = T1.shape[1]
    _STATS["pairwise_pallas_calls"] += 1
    _STATS["interpret_calls"] += bool(interpret)
    out = pl.pallas_call(
        _kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, d1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d2), lambda i: (i, 0)),
            pl.BlockSpec((d1, G), lambda i: (0, 0)),
            pl.BlockSpec((d2, G), lambda i: (0, 0)),
            pl.BlockSpec((G, dout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, dout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, dout), jnp.float32),
        interpret=interpret,
        name="gaunt_pairwise_fused",
    )(a1, a2, T1, T2, P)
    return out[:B].reshape(*batch, dout)
