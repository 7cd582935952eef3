"""The Gaunt collocation kernels and the served eSCN conv compile for a
TPU v5e.

Each test lowers a kernel with interpret mode off and compiles it for one
chip of a *described* ``v5e:2x2`` topology: the TPU compiler installed with
JAX refuses what the chip would refuse (unaligned tiles, VMEM overruns)
without a chip attached.  Nothing runs.  The topology is described inside a
fixture, never at import, so test workers that never get this file never
load the TPU library.  Shapes are the serving preset's selfmix key: a
64-atom slot of 64 channels gives 4096 rows of degree-2 irreps.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.irreps import num_coeffs
from repro.kernels.gaunt_fused import gaunt_chain_fused_pallas, gaunt_fused_pallas

ROWS = 64 * 64


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one, so
    the persistent cache stays off while this file compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _named_kernel(hlo: str, name: str) -> bool:
    """The kernel's custom call carries its stable name, which a trace
    shows as the op's name."""
    return re.search(rf"%{name}[.\d]* = \S+ custom-call\(", hlo) is not None


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chain_kernel_compiles_for_v5e(one_chip, gated, dtype):
    L, nu = 2, 3
    x = jax.ShapeDtypeStruct((ROWS, num_coeffs(L)), jnp.dtype(dtype),
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)

    def run(x, gs, gb):
        return gaunt_chain_fused_pallas(
            [x] * nu, (L,) * nu, L, interpret=False, dtype=dtype,
            gate=(gs, gb) if gated else None)

    hlo = _hlo(run, x, g, g)
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "gaunt_chain_fused")


def test_pairwise_kernel_compiles_for_v5e(one_chip):
    L1, L2, Lout = 2, 3, 4
    x1 = jax.ShapeDtypeStruct((ROWS, num_coeffs(L1)), jnp.float32,
                              sharding=one_chip)
    x2 = jax.ShapeDtypeStruct((ROWS, num_coeffs(L2)), jnp.float32,
                              sharding=one_chip)

    def run(a, b):
        return gaunt_fused_pallas(a, b, L1, L2, Lout, interpret=False)

    hlo = _hlo(run, x1, x2)
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "gaunt_pairwise_fused")


def test_escn_conv_compiles_without_complex_for_v5e(one_chip):
    """The force path of the served conv (the MD cell's key: L=1 features,
    L_edge=3 filter, [atoms, atoms, channels] rows) compiles for v5e as real
    arithmetic: the aligned filter's Gaunt coupling is one real matmul, so
    no complex64 value may appear in the forward or the backward."""
    from repro.core.conv import EquivariantConv

    conv = EquivariantConv(1, 3, 1, method="escn")
    n, C = 128, 128
    x = jax.ShapeDtypeStruct((n, n, C, num_coeffs(1)), jnp.float32,
                             sharding=one_chip)
    r = jax.ShapeDtypeStruct((n, n, 1, 3), jnp.float32, sharding=one_chip)

    def energy(x, r):
        return jnp.sum(conv(x, r) ** 2)

    lowered = jax.jit(jax.value_and_grad(energy, argnums=(0, 1))).lower(x, r)
    assert "f32[" in lowered.compile().as_text()
    # the TPU compiler splits complex ops into real ones, so the guard reads
    # the program as traced, before the compiler's passes
    assert "c64[" not in lowered.as_text(dialect="hlo")
