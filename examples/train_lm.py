"""Train a ~100M-param LM (reduced qwen2 family) on the synthetic Markov
corpus for a few hundred steps with the production train loop.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --dim 512
(defaults are CPU-sized; --dim 768 --layers 12 gives ~100M params)
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import TrainConfig, get_config
from repro.data import LMTokenPipeline
from repro.models import build_model
from repro.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced(
        d_model=args.dim, n_layers=args.layers, n_heads=max(4, args.dim // 64),
        n_kv_heads=max(2, args.dim // 128), head_dim=64, d_ff=args.dim * 4,
        vocab=args.vocab, attn_chunk=args.seq, max_seq=args.seq * 2,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.1f}M")

    pipe = LMTokenPipeline(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    tcfg = TrainConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps,
                       checkpoint_every=100, log_every=10)
    state, hist = train_loop(model.loss, params, pipe, tcfg, ckpt_dir=args.ckpt,
                             hooks={"log": lambda m: print(
                                 f"step {m['step']:4d}  loss {m['loss']:.4f}  "
                                 f"ce {m['ce']:.4f}")})
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
