"""The paper's primary contribution: Gaunt Tensor Products in JAX.

Public API:
    GauntEngine / plan      unified plan/dispatch layer over all backends
    plan_chain / ChainPlan  whole chained products, Fourier-resident interior
    autotune_cache          persistent per-host measured-selection cache
                            (fingerprinted JSON + offline warm-up CLI)
    Rep                     basis-tagged activations (sh | fourier residency)
    GauntTensorProduct      full O(L^3) tensor product (FFT / direct / packed)
    EquivariantConv         x (x) Y(rhat) with the eSCN-sparsity fast path
    manybody_gaunt_product  nu-fold products (divide-and-conquer chain)
    cg_full_tensor_product  the e3nn-style O(L^6) baseline
    gaunt_einsum_reference  dense real-Gaunt oracle
"""
from .cg import cg_full_tensor_product, gaunt_einsum_reference  # noqa: F401
from .conv import EquivariantConv  # noqa: F401
from .engine import (  # noqa: F401
    ChainPlan,
    GauntEngine,
    GauntPlan,
    available_backends,
    get_engine,
    plan,
    plan_chain,
)
from .gaunt import GauntTensorProduct, expand_degree_weights  # noqa: F401
from .irreps import Irreps, num_coeffs  # noqa: F401
from .manybody import manybody_gaunt_product, manybody_selfmix  # noqa: F401
from .rep import Rep, conversion_stats, reset_conversion_stats  # noqa: F401
