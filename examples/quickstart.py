"""Quickstart: the Gaunt Tensor Product as a drop-in equivariant primitive.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.cg import cg_full_tensor_product, gaunt_einsum_reference
from repro.core.conv import EquivariantConv
from repro.core.gaunt import GauntTensorProduct
from repro.core.irreps import num_coeffs
from repro.core.manybody import manybody_selfmix
from repro.core.so3 import wigner_D_real_packed
from repro.kernels.ops import gaunt_tp_fused_xla


def main():
    enable_compile_cache()
    L = 4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, num_coeffs(L))), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, num_coeffs(L))), jnp.float32)

    # 1) full Gaunt tensor product, three equivalent realizations
    tp = GauntTensorProduct(L, L)           # paper's FFT pipeline
    out_fft = tp(x, y)
    out_fused = gaunt_tp_fused_xla(x, y, L, L)   # TPU-native fused form
    out_ref = gaunt_einsum_reference(x, y, L, L)  # dense oracle
    print("max |fft - ref|   =", float(jnp.abs(out_fft - out_ref).max()))
    print("max |fused - ref| =", float(jnp.abs(out_fused - out_ref).max()))

    # 2) O(3) equivariance
    D_in = jnp.asarray(wigner_D_real_packed(L, 0.3, 1.1, -0.7), jnp.float32)
    D_out = jnp.asarray(wigner_D_real_packed(2 * L, 0.3, 1.1, -0.7), jnp.float32)
    lhs = out_ref @ D_out.T
    rhs = gaunt_einsum_reference(x @ D_in.T, y @ D_in.T, L, L)
    print("equivariance error =", float(jnp.abs(lhs - rhs).max()))

    # 3) equivariant convolution with the eSCN-sparsity fast path
    r = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    conv = EquivariantConv(L, L, L, method="escn")
    print("escn conv out:", conv(x, r).shape)

    # 4) many-body products (MACE-style B_nu features)
    B3 = manybody_selfmix(x, L, nu=3, Lout=L)
    print("3-body selfmix out:", B3.shape)

    # 5) the speedup story (jit-compiled timings on this machine)
    cg = jax.jit(lambda a, b: cg_full_tensor_product(a, b, L, L, L))
    fast = jax.jit(lambda a, b: gaunt_tp_fused_xla(a, b, L, L, L))
    for f, name in ((cg, "CG (e3nn-style)"), (fast, "Gaunt fused")):
        jax.block_until_ready(f(x, y))
        t0 = time.perf_counter()
        for _ in range(20):
            jax.block_until_ready(f(x, y))
        print(f"{name:>18}: {(time.perf_counter() - t0) / 20 * 1e6:8.1f} us/call")


if __name__ == "__main__":
    main()
