"""Serving example: batched greedy decoding with the ServeEngine
(+ optional int8 KV cache, the production decode configuration).

Run:  PYTHONPATH=src python examples/serve_lm.py [--int8-kv]
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import ServeEngine
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", help="smoke-config arch id")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if args.int8_kv:
        cfg = dataclasses.replace(cfg, kv_quant_decode=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    engine = ServeEngine(model, params, max_len=128)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, rng.integers(3, 9)).tolist()
               for _ in range(args.batch)]
    t0 = time.time()
    outs = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    total_new = args.batch * args.max_new
    print(f"arch={cfg.name} int8_kv={args.int8_kv}")
    for i, o in enumerate(outs):
        print(f"req{i}: prompt={o[:len(prompts[i])]} -> {o[len(prompts[i]):]}")
    print(f"{total_new} tokens in {dt:.2f}s = {total_new / dt:.1f} tok/s (batched)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
