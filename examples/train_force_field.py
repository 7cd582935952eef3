"""End-to-end driver (the paper's kind): train a Gaunt-MACE force field on
synthetic Lennard-Jones clusters for a few hundred steps, with the full
training substrate (AdamW + cosine, checkpointing, resume).

    PYTHONPATH=src python examples/train_force_field.py --steps 300
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import TrainConfig
from repro.configs.gaunt_ff import gaunt_mace_ff
from repro.data import lj_dataset
from repro.models.equivariant import MaceGaunt
from repro.train import train_loop


class LJBatches:
    """Resumable batch iterator over a fixed synthetic dataset."""

    def __init__(self, n=128, batch=16, seed=0):
        self.data = lj_dataset(n, n_atoms=8, n_species=4, seed=seed)
        self.n, self.batch, self.step = n, batch, 0

    def state(self):
        return {"step": self.step}

    def restore(self, s):
        self.step = int(s["step"])

    def next_batch(self):
        rng = np.random.default_rng((1234, self.step))
        idx = rng.choice(self.n, self.batch, replace=False)
        self.step += 1
        return {k: v[idx] for k, v in self.data.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--ckpt", default="/tmp/gaunt_mace_ckpt")
    ap.add_argument("--channels", type=int, default=16)
    ap.add_argument("--L", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(gaunt_mace_ff, channels=args.channels, L=args.L,
                              L_edge=2, n_layers=1, nu=2)
    model = MaceGaunt(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model: {cfg.name}  params={n_params:,}")

    tcfg = TrainConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps,
                       checkpoint_every=100, log_every=10, grad_clip=10.0)

    def loss_fn(p, batch):
        loss = model.loss(p, batch)
        return loss, {"mse": loss}

    state, hist = train_loop(loss_fn, params, LJBatches(), tcfg, ckpt_dir=args.ckpt,
                             hooks={"log": lambda m: print(
                                 f"step {m['step']:4d}  loss {m['loss']:.4f}")})
    print(f"final loss: {hist[-1]['loss']:.4f}  (start {hist[0]['loss']:.4f})")
    # quick validation: energy invariance of the trained model
    from repro.core.so3 import rotation_matrix_zyz

    d = lj_dataset(1, n_atoms=8, n_species=4, seed=99)
    R = jnp.asarray(rotation_matrix_zyz(0.5, 1.0, -0.3), jnp.float32)
    s, pos = jnp.asarray(d["species"][0]), jnp.asarray(d["pos"][0])
    e1 = model.energy(state.params, s, pos)
    e2 = model.energy(state.params, s, pos @ R.T)
    print(f"rotation invariance: E={float(e1):.5f} vs {float(e2):.5f}")


if __name__ == "__main__":
    main()
