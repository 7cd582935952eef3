"""RWKV6 (Finch) WKV recurrence — chunked, numerically stable, TPU-friendly.

Recurrence (per batch, head; K/V head dims):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)

Chunked form (chunk C, lw = cumsum log w within chunk, lw_0 = 0):
    intra:  A[i,j] = sum_k r[i,k] k[j,k] exp(lw[i-1,k] - lw[j,k])   (j < i)
            + diag(sum_k r[i,k] u[k] k[i,k])
    inter:  o += (r ⊙ exp(lw_prev)) @ S_chunk_start
    state:  S' = diag(exp(lw_C)) S + (k ⊙ exp(lw_C - lw))^T V

Every exponent is masked to <= 0 before exp — no overflow for any data-
dependent decay (tested against the naive recurrence oracle in fp32).

`wkv6_chunked` is the pure-jnp scan (used inside scanned model layers);
`wkv6_pallas` is the Pallas TPU kernel: grid (B*H, T/C) with the sequential
chunk axis carrying S in a VMEM scratch accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_chunked", "wkv6_pallas"]


def wkv6_chunked(r, k, v, w, u, chunk: int = 64, return_state: bool = False):
    """r,k,w [B,T,H,K]; v [B,T,H,V]; u [H,K] -> o [B,T,H,V] (fp32 inside).

    With return_state, also returns the final S [B,H,K,V] (prefill -> decode
    handoff)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    assert T % C == 0, f"T={T} not divisible by chunk={C}"
    n = T // C

    def to_bh(x, d):
        # [B,T,H,d] -> [n, B*H, C, d]
        x = x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, T, d)
        return x.reshape(B * H, n, C, d).transpose(1, 0, 2, 3)

    rs, ks, ws = to_bh(r, K), to_bh(k, K), to_bh(w, K)
    vs = to_bh(v, V)
    u_full = jnp.tile(u.astype(jnp.float32), (B, 1)).reshape(B * H, K)

    def step(S, xs):
        rc, kc, vc, wc = xs
        # u is per-head; fold into einsum via per-row u
        C_ = rc.shape[1]
        logw = jnp.log(jnp.clip(wc, 1e-12, 1.0))
        lw = jnp.cumsum(logw, axis=1)
        lw_prev = jnp.pad(lw[:, :-1], ((0, 0), (1, 0), (0, 0)))
        diff = lw_prev[:, :, None, :] - lw[:, None, :, :]
        mask = (jnp.arange(C_)[:, None] > jnp.arange(C_)[None, :])[None, :, :, None]
        E = jnp.exp(jnp.where(mask, diff, -jnp.inf))
        A = jnp.einsum("bik,bjk,bijk->bij", rc, kc, E)
        Adiag = jnp.einsum("bik,bk,bik->bi", rc, u_full, kc)
        o = jnp.einsum("bij,bjv->biv", A, vc) + Adiag[..., None] * vc
        o = o + jnp.einsum("bik,bkv->biv", rc * jnp.exp(lw_prev), S)
        k_t = kc * jnp.exp(lw[:, -1:, :] - lw)
        S = jnp.exp(lw[:, -1, :])[..., None] * S + jnp.einsum("bik,biv->bkv", k_t, vc)
        return S, o

    S0 = jnp.zeros((B * H, K, V), dtype=jnp.float32)
    # checkpoint the chunk body: backward recomputes the O(C^2 K) intra-chunk
    # tensors instead of saving them per iteration (§Perf H9)
    S_fin, os = jax.lax.scan(jax.checkpoint(step, prevent_cse=False),
                             S0, (rs, ks, vs, ws))
    # os [n, BH, C, V] -> [B, T, H, V]
    o = os.transpose(1, 0, 2, 3).reshape(B, H, T, V).transpose(0, 2, 1, 3)
    if return_state:
        return o, S_fin.reshape(B, H, K, V)
    return o


# ----------------------------------------------------------------------
# Pallas kernel
# ----------------------------------------------------------------------


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, S_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        S_ref[...] = jnp.zeros_like(S_ref)

    r = r_ref[0]  # [C, K]
    k = k_ref[0]
    v = v_ref[0]
    w = w_ref[0]
    u = u_ref[0]  # [1, K] (head-broadcast row)
    C = r.shape[0]
    S = S_ref[...]
    logw = jnp.log(jnp.clip(w, 1e-12, 1.0))
    lw = jnp.cumsum(logw, axis=0)
    lw_prev = jnp.concatenate([jnp.zeros_like(lw[:1]), lw[:-1]], axis=0)
    diff = lw_prev[:, None, :] - lw[None, :, :]  # [i, j, K]
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    E = jnp.exp(jnp.where((ii > jj)[..., None], diff, -jnp.inf))
    A = jnp.einsum("ik,jk,ijk->ij", r, k, E)
    Adiag = jnp.sum(r * u * k, axis=-1)  # [C]
    o = jnp.dot(A, v, preferred_element_type=jnp.float32) + Adiag[:, None] * v
    o = o + jnp.dot(r * jnp.exp(lw_prev), S, preferred_element_type=jnp.float32)
    o_ref[0] = o
    k_t = k * jnp.exp(lw[-1:, :] - lw)
    S_ref[...] = jnp.exp(lw[-1])[:, None] * S + jnp.dot(
        k_t.T, v, preferred_element_type=jnp.float32
    )


def wkv6_pallas(r, k, v, w, u, chunk: int = 64, interpret: bool | None = None):
    """Pallas WKV6: grid (B*H, T/C); S carried in VMEM scratch across chunks."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    assert T % C == 0
    n = T // C
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def to_bh(x, d):
        return x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, T, d)

    rs, ks, ws, vs = to_bh(r, K), to_bh(k, K), to_bh(w, K), to_bh(v, V)
    u_rows = jnp.tile(u.astype(jnp.float32), (B, 1)).reshape(B * H, 1, K)

    out = pl.pallas_call(
        _wkv6_kernel,
        grid=(B * H, n),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, V), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(rs, ks, vs, ws, u_rows)
    return out.reshape(B, H, T, V).transpose(0, 2, 1, 3)
