"""Serving launcher: batched decode over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --requests 8 --slots 4
"""
from __future__ import annotations

import argparse
import time

import jax


def main():
    from repro.config import get_config
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=args.slots, max_len=args.max_len)
    reqs = [Request(prompt=[(11 * i + j) % cfg.vocab for j in range(5)],
                    max_new_tokens=args.max_new, temperature=args.temperature, rid=i)
            for i in range(args.requests)]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    tokens = sum(len(r.output) for r in reqs)
    print(f"[serve] {len(reqs)} requests, {tokens} tokens, {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
