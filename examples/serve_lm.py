"""Serve a small LM with batched requests through the continuous-batching
engine (any of the 10 archs; reduced config on CPU).

    PYTHONPATH=src python examples/serve_lm.py --arch qwen2-0.5b --requests 6
"""
import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.config import get_config
from repro.models import build_model
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=args.slots, max_len=128)

    reqs = [
        Request(prompt=[(7 * i + j) % cfg.vocab for j in range(4 + i % 3)],
                max_new_tokens=args.max_new, temperature=args.temperature, rid=i)
        for i in range(args.requests)
    ]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(r.output) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt={r.prompt} -> {r.output}")
    print(f"{total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s, {args.slots} slots, arch={args.arch})")


if __name__ == "__main__":
    main()
