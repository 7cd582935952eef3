"""The system under test for configurations of the ``eqv2`` family:
`repro.models.equiformer_v2.EquiformerV2`, built from the configuration file
and run on one device at the matmul precision that the file states.  It is
served on a neighbour graph that the serving pools build on the host.
"""
from __future__ import annotations

import contextlib

__all__ = ["program_config", "build", "deployed"]


def program_config(config: dict):
    """The program's own config object for a configuration file; the S^2
    grid is the file's ``model.grid``."""
    from repro.configs.gaunt_ff import EquiformerV2Config

    m = dict(config["model"])
    grid = m.pop("grid")
    if (grid["theta"], grid["phi"]) != ("gauss_legendre", "uniform"):
        raise ValueError(f"the program has no grid {grid}")
    return EquiformerV2Config(name=config["name"], grid_theta=grid["n_theta"],
                              grid_phi=grid["n_phi"], **m, **config["program"])


def build(config: dict):
    """The served model: ``energy_graph(params, species, pos, mask, nbr,
    nbr_mask)``."""
    from repro.models.equiformer_v2 import EquiformerV2

    return EquiformerV2(program_config(config))


@contextlib.contextmanager
def deployed(config: dict, devices):
    """Run what the block builds at the configuration's matmul precision.
    Refuses a deployment of more than one chip or with a mesh: the family
    has no sharded path."""
    import jax

    dep = config["deployment"]
    if dep["chips"] != 1 or dep.get("mesh"):
        raise ValueError(f"eqv2 runs on one chip, not {dep}")
    if not devices:
        raise ValueError("no device to deploy on")
    with jax.default_matmul_precision(config["precision"]["matmul"]):
        yield None
