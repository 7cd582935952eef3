"""The system under test for configurations of the ``mace`` family:
`repro.models.equivariant.MaceGaunt`, built from the configuration file and
run on the devices and at the matmul precision that the file states.

A configuration file names its family (``"family": "mace"``); the harness
finds this module by that name, and the plain reference, the weights and
the FLOP count in ``bench/configs/<family>.py``.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

__all__ = ["program_config", "build", "deployed"]


def program_config(config: dict):
    """The program's own config object for a configuration file."""
    from repro.configs.gaunt_ff import EquivariantConfig

    m, prog = config["model"], config["program"]
    return EquivariantConfig(
        name=config["name"], kind="mace", L=m["L"], L_edge=m["L_edge"],
        channels=m["channels"], n_layers=m["n_layers"],
        n_species=m["n_species"], nu=m["nu"], cutoff=m["cutoff"],
        n_radial=m["n_radial"], hidden=m["hidden"], **prog)


def build(config: dict):
    """The served and trained model: ``energy_masked(params, species, pos,
    mask)`` and ``loss(params, batch)``."""
    from repro.models.equivariant import MaceGaunt

    return MaceGaunt(program_config(config))


@contextlib.contextmanager
def deployed(config: dict, devices):
    """Run what the block builds as the configuration's ``deployment`` and
    ``precision`` say: the matmul precision, and where ``deployment.mesh``
    names axes ({"data": 4}), an activation mesh of that shape over the
    first devices, registered for the block.  Refuses a configuration whose
    mesh does not cover its chips, and one that shards its rows
    (``program.shard_data``) without a mesh."""
    import jax

    dep, prog = config["deployment"], config["program"]
    axes = dep.get("mesh") or {}
    size = math.prod(axes.values()) if axes else 1
    if axes and size != dep["chips"]:
        raise ValueError(f"mesh {axes} does not cover the deployment's "
                         f"{dep['chips']} chips")
    if len(devices) < dep["chips"]:
        raise ValueError(f"the deployment asks for {dep['chips']} chips, "
                         f"{len(devices)} are there")
    if prog.get("shard_data") and not axes:
        raise ValueError("program.shard_data needs deployment.mesh")
    with jax.default_matmul_precision(config["precision"]["matmul"]):
        if not axes:
            yield None
            return
        from jax.sharding import Mesh

        from repro.distributed.sharding import set_activation_mesh

        mesh = Mesh(np.asarray(devices[:size]).reshape(tuple(axes.values())),
                    tuple(axes))
        set_activation_mesh(mesh)
        try:
            yield mesh
        finally:
            set_activation_mesh(None)
