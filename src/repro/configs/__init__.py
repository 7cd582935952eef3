"""Assigned architecture configs (exact public-literature sizes) + the paper's
own equivariant model configs.  One file per arch; importing this package
registers everything."""
from repro.configs.dbrx_132b import dbrx_132b
from repro.configs.qwen2_moe_a2p7b import qwen2_moe_a2p7b
from repro.configs.qwen15_32b import qwen15_32b
from repro.configs.qwen2_0p5b import qwen2_0p5b
from repro.configs.stablelm_3b import stablelm_3b
from repro.configs.gemma_2b import gemma_2b
from repro.configs.zamba2_2p7b import zamba2_2p7b
from repro.configs.rwkv6_3b import rwkv6_3b
from repro.configs.whisper_base import whisper_base
from repro.configs.qwen2_vl_72b import qwen2_vl_72b
from repro.configs.gaunt_ff import (equiformer_v2_tiny, gaunt_mace_ff,
                                    gaunt_segnn_nbody)

ALL_LM_ARCHS = [
    "dbrx-132b", "qwen2-moe-a2.7b", "qwen1.5-32b", "qwen2-0.5b",
    "stablelm-3b", "gemma-2b", "zamba2-2.7b", "whisper-base",
    "qwen2-vl-72b", "rwkv6-3b",
]

# archs with sub-quadratic decode (run long_500k); the rest skip it (DESIGN.md)
SUBQUADRATIC = {"zamba2-2.7b", "rwkv6-3b"}
