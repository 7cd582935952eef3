"""The served MACE step carries a named scope on every stage.

A profiler trace charges each device op to a model layer by the ``op_name``
path that XLA keeps in the op's metadata: ``mace.edge``, ``mace.radial``,
``mace.conv``, ``mace.chain``, ``mace.mix_gate`` and ``mace.readout`` in
the forward pass, and ``transpose(jvp(mace.<layer>))`` in the force
backward.  These tests compile a serving step of a tiny `MaceGaunt` on the
CPU and read the compiled program's text, so a refactor that drops a scope
fails here and not in a trace.
"""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.gaunt_ff import EquivariantConfig
from repro.models.equivariant import MaceGaunt
from repro.serve.pools import BucketSpec, SlotPool

LAYERS = ("edge", "radial", "conv", "chain", "mix_gate", "readout")
# one HLO instruction of the kinds a device spends its time in
HEAVY = re.compile(r"^\s*(?:ROOT )?%?\S+ = \S+ "
                   r"(fusion|dot|convolution|reduce)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"mace\.(\w+)")

# the benchmark cells' program at a tiny width: spectral Gaunt products,
# eSCN convolution, a Fourier-resident edge filter, nu=3 chains, 2 layers
TINY = EquivariantConfig(
    name="tiny", kind="mace", L=2, L_edge=3, channels=4, n_layers=2,
    n_species=4, nu=3, cutoff=5.0, n_radial=8, hidden=16, tp_impl="gaunt",
    conv_impl="escn", chain_tune="heuristic", fourier_resident=True,
    grid_gate="off", compute_dtype="float32", shard_data=False)


def _step_text(cfg) -> str:
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = SlotPool(model, params, BucketSpec(6, 2))
    args = (jnp.asarray(pool.species), jnp.asarray(pool.pos),
            jnp.asarray(pool.mask))
    return pool._step_fn.lower(params, *args).compile().as_text()


def _heavy_paths(text: str) -> list:
    """The op_name path of each heavy instruction ('' where it has none)."""
    out = []
    for line in text.split("\n"):
        if HEAVY.match(line):
            m = OP_NAME.search(line)
            out.append(m.group(1) if m else "")
    return out


@pytest.fixture(scope="module")
def step_text():
    return _step_text(TINY)


def test_every_forward_scope_and_the_backward_are_in_the_step(step_text):
    paths = _heavy_paths(step_text)
    forward = collections.Counter()
    backward = collections.Counter()
    for p in paths:
        scopes = SCOPE.findall(p)
        if scopes:
            (backward if "transpose(" in p else forward)[scopes[-1]] += 1
    assert set(forward) == set(LAYERS), forward
    # the force backward of the layers that depend on the positions
    assert {"edge", "radial", "conv", "chain"} <= set(backward), backward


def test_few_heavy_ops_lack_a_model_scope(step_text):
    paths = _heavy_paths(step_text)
    assert len(paths) > 100
    unscoped = [p for p in paths if "mace." not in p]
    assert len(unscoped) <= 0.05 * len(paths), collections.Counter(unscoped)


def test_scopes_reach_through_the_engines_nested_jits(step_text):
    """The engine's batched plans and chains run as inner `jax.jit`s; their
    ops keep the model's scope in front of the inner jit's name."""
    nested = [p for p in _heavy_paths(step_text)
              if p.count("jit(") > 1]
    assert nested, "no op of an inner jit in the step"
    assert all("mace." in p for p in nested)


def test_grid_gate_runs_under_the_chain_scope():
    """With the grid-resident gate the gate is a stage of the chain, and the
    mix after it is still scoped."""
    text = _step_text(dataclasses.replace(TINY, grid_gate="on", n_layers=1))
    forward = {SCOPE.findall(p)[-1] for p in _heavy_paths(text)
               if "mace." in p and "transpose(" not in p}
    assert set(LAYERS) == forward
