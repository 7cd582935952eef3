"""Mamba-2 SSD (state-space duality) — chunked scan, TPU-friendly.

Per head (headdim P, state N, scalar A < 0):
    h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T        h in R^{P x N}
    y_t = h_t C_t + D x_t

Chunked (la = cumsum(A dt) within chunk, all exponents <= 0):
    intra:  M[i,j] = exp(la_i - la_j) dt_j (C_i . B_j)   (j <= i);  Y = M X
    inter:  y_i += exp(la_i) (h_0 C_i)
    state:  h' = exp(la_C) h_0 + sum_j exp(la_C - la_j) dt_j x_j B_j^T

`mamba2_ssd_chunked` is the pure-jnp scan; `mamba2_ssd_pallas` the Pallas TPU
kernel (grid (B*H, T/C), VMEM-resident h across the sequential chunk axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba2_ssd_chunked", "mamba2_ssd_pallas"]


def mamba2_ssd_chunked(x, dt, A, B, C, D, chunk: int = 64, return_state: bool = False):
    """x [Bt,T,H,P]; dt [Bt,T,H]; A [H]; B,C [Bt,T,G,N]; D [H] -> y like x.

    With return_state, also returns final h [Bt,H,P,N]."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hpg = H // G
    Ck = min(chunk, T)
    assert T % Ck == 0
    n = T // Ck

    # broadcast groups to heads, fold (Bt, H) -> R rows
    Bh = jnp.repeat(B, hpg, axis=2)
    Ch = jnp.repeat(C, hpg, axis=2)

    def to_r(a, d):
        return (
            a.astype(jnp.float32)
            .transpose(0, 2, 1, 3)
            .reshape(Bt * H, n, Ck, d)
            .transpose(1, 0, 2, 3)
        )

    xs = to_r(x, P)
    Bs = to_r(Bh, N)
    Cs = to_r(Ch, N)
    dts = (
        dt.astype(jnp.float32).transpose(0, 2, 1).reshape(Bt * H, n, Ck).transpose(1, 0, 2)
    )
    A_r = jnp.tile(A.astype(jnp.float32), (Bt,))  # [Bt*H]

    def step(h, xs_):
        xc, Bc, Cc, dtc = xs_  # [R,C,P], [R,C,N], [R,C,N], [R,C]
        la = jnp.cumsum(A_r[:, None] * dtc, axis=1)  # [R,C] (<= 0, decreasing)
        ii = jnp.arange(Ck)[:, None]
        jj = jnp.arange(Ck)[None, :]
        diff = la[:, :, None] - la[:, None, :]  # [R,i,j]
        Mexp = jnp.exp(jnp.where((ii >= jj)[None], diff, -jnp.inf))
        M = Mexp * jnp.einsum("rin,rjn->rij", Cc, Bc) * dtc[:, None, :]
        y = jnp.einsum("rij,rjp->rip", M, xc)
        y = y + jnp.exp(la)[..., None] * jnp.einsum("rpn,rin->rip", h, Cc)
        w = jnp.exp(la[:, -1:] - la)[..., None] * dtc[..., None]  # [R,C,1->N]
        h = jnp.exp(la[:, -1])[:, None, None] * h + jnp.einsum(
            "rjp,rjn->rpn", xc * w[..., :1], Bc
        )
        return h, y

    h0 = jnp.zeros((Bt * H, P, N), dtype=jnp.float32)
    # checkpoint the chunk body (see wkv6.py — §Perf H9)
    h_fin, ys = jax.lax.scan(jax.checkpoint(step, prevent_cse=False),
                             h0, (xs, Bs, Cs, dts))
    y = ys.transpose(1, 0, 2, 3).reshape(Bt, H, T, P).transpose(0, 2, 1, 3)
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    if return_state:
        return y, h_fin.reshape(Bt, H, P, N)
    return y


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, h_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0]  # [C, P]
    Bc = b_ref[0]  # [C, N]
    Cc = c_ref[0]
    dt = dt_ref[0]  # [1, C] row
    A = a_ref[0]  # [1, 1]
    h = h_ref[...]  # [P, N]
    Ck = x.shape[0]
    la = jnp.cumsum(A[0, 0] * dt[0], axis=0)  # [C]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Ck, Ck), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Ck, Ck), 1)
    diff = la[:, None] - la[None, :]
    Mexp = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))
    M = Mexp * jnp.dot(Cc, Bc.T, preferred_element_type=jnp.float32) * dt[0][None, :]
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)
    y = y + jnp.exp(la)[:, None] * jnp.dot(Cc, h.T, preferred_element_type=jnp.float32)
    y_ref[0] = y
    w = (jnp.exp(la[-1] - la) * dt[0])[:, None]
    h_ref[...] = jnp.exp(la[-1]) * h + jnp.dot(
        (x * w).T, Bc, preferred_element_type=jnp.float32
    )


def mamba2_ssd_pallas(x, dt, A, B, C, D, chunk: int = 64, interpret: bool | None = None):
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hpg = H // G
    Ck = min(chunk, T)
    assert T % Ck == 0
    n = T // Ck
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R = Bt * H
    xs = x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(R, T, P)
    Bs = jnp.repeat(B, hpg, axis=2).astype(jnp.float32).transpose(0, 2, 1, 3).reshape(R, T, N)
    Cs = jnp.repeat(C, hpg, axis=2).astype(jnp.float32).transpose(0, 2, 1, 3).reshape(R, T, N)
    dts = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(R, 1, T)
    A_r = jnp.tile(A.astype(jnp.float32), (Bt,)).reshape(R, 1, 1)
    y = pl.pallas_call(
        _ssd_kernel,
        grid=(R, n),
        in_specs=[
            pl.BlockSpec((1, Ck, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Ck, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Ck, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Ck), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, 1), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Ck, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((R, T, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(xs, Bs, Cs, dts, A_r)
    y = y.reshape(Bt, H, T, P).transpose(0, 2, 1, 3)
    return y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
