"""End-to-end behaviour tests for the paper's system: the Gaunt Tensor
Product primitive wired through a real training run, the fault-tolerance
path, and the multi-device dry-run contract (on a small host mesh)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TrainConfig
from repro.configs.gaunt_ff import gaunt_mace_ff
from repro.data import lj_dataset
from repro.models.equivariant import MaceGaunt
from repro.train import train_loop


def test_force_field_end_to_end_with_restart(tmp_path):
    """Train the paper-side model, stop it mid-run, resume from the
    checkpoint, and verify the final model is E(3)-sound."""
    cfg = dataclasses.replace(gaunt_mace_ff, channels=8, L=1, L_edge=1,
                              n_layers=1, nu=2, n_radial=4, hidden=16)
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = lj_dataset(12, n_atoms=6, n_species=4, seed=0)

    class Batches:
        step = 0

        def state(self):
            return {"step": self.step}

        def restore(self, s):
            self.step = int(s["step"])

        def next_batch(self):
            rng = np.random.default_rng((7, self.step))
            idx = rng.choice(12, 6, replace=False)
            self.step += 1
            return {k: v[idx] for k, v in data.items()}

    def loss_fn(p, batch):
        loss = model.loss(p, batch)
        return loss, {}

    # phase 1: run 8 steps, checkpoint at 4 and 8
    t1 = TrainConfig(lr=2e-3, warmup_steps=2, total_steps=8, checkpoint_every=4,
                     log_every=4, grad_clip=10.0)
    train_loop(loss_fn, params, Batches(), t1, ckpt_dir=str(tmp_path))
    # phase 2 ("restart after preemption"): extend to 14 steps
    t2 = dataclasses.replace(t1, total_steps=14)
    b2 = Batches()
    state, hist = train_loop(loss_fn, params, b2, t2, ckpt_dir=str(tmp_path))
    assert state.step == 14
    assert b2.step == 14  # data pipeline resumed, not replayed
    # E(3) soundness of the final model
    from repro.core.so3 import rotation_matrix_zyz

    R = jnp.asarray(rotation_matrix_zyz(0.4, 1.0, -0.2), jnp.float32)
    s0 = jnp.asarray(data["species"][0])
    p0 = jnp.asarray(data["pos"][0])
    e1 = model.energy(state.params, s0, p0)
    e2 = model.energy(state.params, s0, p0 @ R.T)
    np.testing.assert_allclose(float(e1), float(e2), rtol=1e-4, atol=1e-3)


def test_dryrun_tiny_cell_subprocess():
    """The dry-run contract end-to-end (subprocess so the 8-device XLA flag
    does not leak into this process)."""
    code = (
        "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8';"
        "import repro.launch.dryrun as D;"
        "import repro.launch.mesh as M, jax;"
        "M.make_production_mesh = lambda multi_pod=False: jax.make_mesh("
        "(2,2,2) if multi_pod else (4,2), ('pod','data','model') if multi_pod"
        " else ('data','model'),"
        "axis_types=M._auto(3 if multi_pod else 2));"
        # dryrun binds the name at import — patch its reference too
        "D.make_production_mesh = M.make_production_mesh;"
        "r1 = D.dryrun_cell('qwen2-0.5b','train_4k', False, tiny=True);"
        "r2 = D.dryrun_cell('qwen2-0.5b','decode_32k', True, tiny=True);"
        "assert r1['status']=='ok' and r2['status']=='ok', (r1, r2);"
        "assert r1['cost']['flops_per_device'] > 0;"
        "print('DRYRUN_OK')"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    assert "DRYRUN_OK" in out.stdout, out.stderr[-2000:]


def test_train_supervisor_never_loads_jax():
    """The --supervise parent starts the worker that takes the chip, so it
    must not hold a backend itself: importing the launcher loads no JAX."""
    code = ("import sys, repro.launch.train;"
            "assert 'jax' not in sys.modules, 'launcher imported jax';"
            "print('NO_JAX_OK')")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH="src"), timeout=300,
    )
    assert "NO_JAX_OK" in out.stdout, out.stderr[-2000:]


def test_gaunt_primitive_in_training_matches_cg_class():
    """Sanity-check claim (paper Fig 1e): swapping CG -> Gaunt
    parameterization preserves trainability on the same task/seed."""
    from repro.configs.gaunt_ff import gaunt_segnn_nbody
    from repro.data import nbody_dataset
    from repro.models.equivariant import SegnnNBody

    data = nbody_dataset(6, horizon=150, seed=3)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    finals = {}
    for impl in ("gaunt", "cg"):
        cfg = dataclasses.replace(gaunt_segnn_nbody, tp_impl=impl, channels=8,
                                  n_layers=1, n_radial=4)
        m = SegnnNBody(cfg)
        p = m.init(jax.random.PRNGKey(5))
        g = jax.jit(jax.grad(m.loss))
        for _ in range(5):
            p = jax.tree.map(lambda a, b: a - 1e-2 * b, p, g(p, batch))
        finals[impl] = float(m.loss(p, batch))
    # same accuracy class: within 2x of each other after identical budgets
    ratio = finals["gaunt"] / max(finals["cg"], 1e-9)
    assert 0.5 < ratio < 2.0, finals
