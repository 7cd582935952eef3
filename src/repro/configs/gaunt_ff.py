"""The paper's own model configs: Gaunt-accelerated equivariant networks."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class EquivariantConfig:
    name: str
    kind: str  # mace | segnn
    L: int = 2           # max feature degree
    L_edge: int = 2      # SH filter degree
    channels: int = 64
    n_layers: int = 2
    n_species: int = 8
    nu: int = 3          # many-body order (MACE)
    cutoff: float = 5.0
    n_radial: int = 8
    tp_impl: str = "gaunt"  # gaunt | cg | gaunt_fused
    conv_impl: str = "escn"  # escn | general
    hidden: int = 128
    # batched-execution knob (engine.plan_batch, DESIGN.md §5); donation is
    # NOT a config knob — model loops reuse operand buffers across layers,
    # so donating them is only safe for callers that own buffer lifetimes
    shard_data: bool = False       # shard rows over the activation mesh's data axes
    # basis-residency knob (DESIGN.md §6): keep layer-constant operands (the
    # edge SH filter / eSCN Wigner blocks) Fourier-resident across the layer
    # stack and run chained products through engine.plan_chain.  Composes
    # with shard_data (resident grids shard like SH rows).  Off only for A/B
    # debugging — the resident path is numerically identical up to dtype
    # roundoff.
    fourier_resident: bool = True
    # chain-backend policy (DESIGN.md §6.4): 'heuristic' keeps the resident
    # spectral tree; 'measure' folds the model's chained products into the
    # engine's measured autotuner, which may collapse a whole chain into the
    # n-way collocation kernel (one dispatch, zero conversions).  Measurement
    # only runs outside jit: a forward traced before any eager call stays on
    # 'tree' for its chain keys — run one eager forward (or serve warmup(),
    # which seeds the keys) before jitting to engage the measured picks.
    chain_tune: str = "heuristic"
    # storage precision for the Gaunt products (DESIGN.md §3.6): the SH
    # operands/constants of every engine plan the model builds are stored at
    # this dtype; accumulation and the resident complex grids stay >= f32.
    # 'float32' (default) | 'bfloat16' | 'auto' ('auto' + chain_tune=
    # 'measure' lets the engine time both precisions per workload and keep
    # bf16 only where it wins).  Activations between plans (mixes, gates)
    # follow the plan output dtype via jnp promotion.
    compute_dtype: str = "float32"
    # persistent autotune cache file (DESIGN.md §4.5): serve warmup() points
    # the engine at this path so measured selections (backends, chain
    # flavors, dtype winners) load from disk instead of
    # re-timing — a warm host boots with zero timing runs.  None (default)
    # falls back to $REPRO_AUTOTUNE_CACHE, else persistence stays off.
    # Pre-populate with `python -m repro.core.autotune_cache --cache <path>`.
    autotune_cache: str | None = None
    # grid-resident equivariant gates (DESIGN.md §6.5): where the layer gate
    # runs.  'off' (default) applies gate_apply on SH coefficients between
    # chain exits; 'on' keeps the gate on the resident grid — MACE fuses the
    # affine gate g*f + beta*Y00 into the selfmix chain (pointwise stage in
    # the collocation kernel; the layer reorders to gate-before-mb_mix, an
    # equally expressive reparameterization), SEGNN evaluates the gate on the
    # S^2 quadrature grid.  'auto' asks the engine's measured gate policy
    # (engine.select_gate, keyed like chain plans) per workload; requires
    # chain_tune='measure', else it resolves to 'off'.
    grid_gate: str = "off"
    # serve-time slot buckets (DESIGN.md §10.2): ((max_atoms, n_slots), ...)
    # size-bucketed pools for EquivariantServeEngine — each bucket compiles
    # its own step at its own padded shape and seeds its own warmup/autotune
    # keys, so small molecules stop padding to the deployment maximum.  None
    # (default) keeps the engine's single fixed-max_atoms bucket; the
    # engine's explicit ``buckets=`` argument overrides this knob.  See
    # serve/pools.py `default_buckets` for the small/medium/large ladder.
    serve_buckets: tuple[tuple[int, int], ...] | None = None


@dataclasses.dataclass(frozen=True)
class EquiformerV2Config:
    """EquiformerV2 (Liao, Shuaibi, Zitnick, Smidt, ICLR 2024) with the Gaunt
    Selfmix layer of Luo et al. (ICLR 2024, section 5) in every block.  The
    defaults are the OC20 S2EF-2M model ``equiformer_v2_N@12_L@6_M@2`` of the
    public Open Catalyst configs; `repro.models.equiformer_v2` holds the
    equations.  The model runs on a k-nearest-neighbour graph: a serving
    pool builds it on the host from ``max_neighbors`` and ``max_radius``."""
    name: str = "eqv2-l6m2-selfmix"
    n_blocks: int = 12
    lmax: int = 6
    mmax: int = 2
    sphere_channels: int = 128
    attn_hidden_channels: int = 64
    num_heads: int = 8
    attn_alpha_channels: int = 64
    attn_value_channels: int = 16
    ffn_hidden_channels: int = 128
    edge_channels: int = 128
    max_neighbors: int = 20
    max_radius: float = 12.0
    num_distance_basis: int = 600   # Gaussians on [0, max_radius]
    distance_width: float = 2.0     # Gaussian width, in basis spacings
    max_num_elements: int = 90
    # the S^2 grid of the nonlinearities: Gauss-Legendre in cos(theta) times
    # a uniform phi grid (`core.fourier.s2quad_angles`)
    grid_theta: int = 18
    grid_phi: int = 18
    avg_degree: float = 23.395238876342773   # edge-degree embedding scale
    avg_num_nodes: float = 77.81317          # energy scale

    @property
    def n_species(self) -> int:
        return self.max_num_elements


gaunt_mace_ff = EquivariantConfig(
    name="gaunt-mace-ff", kind="mace", L=2, L_edge=3, channels=64, n_layers=2, nu=3
)
gaunt_segnn_nbody = EquivariantConfig(
    name="gaunt-segnn-nbody", kind="segnn", L=1, L_edge=1, channels=32, n_layers=4
)
# every width cut down, for the CPU tests (EquiformerV2Config() itself holds
# the published OC20 settings)
equiformer_v2_tiny = EquiformerV2Config(
    name="eqv2-tiny", n_blocks=2, lmax=2, mmax=1, sphere_channels=8,
    attn_hidden_channels=4, num_heads=2, attn_alpha_channels=4,
    attn_value_channels=2, ffn_hidden_channels=8, edge_channels=8,
    max_neighbors=4, max_radius=6.0, num_distance_basis=16,
    max_num_elements=10, grid_theta=6, grid_phi=6)
