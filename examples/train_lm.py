"""End-to-end training driver: DDF data pipeline -> LM trainer.

The pipeline stages (dedup / filter / length-sort / rebalance) are the
paper's parallel patterns; the trainer is the framework's pjit path with
checkpointing + the straggler watchdog.

Run (tiny, CPU-friendly):
  PYTHONPATH=src python examples/train_lm.py --steps 200
Run the ~100M-param preset (same code; sized for a real accelerator):
  PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DDFContext
from repro.data.pipeline import TokenPipeline
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.train.checkpoint import save
from repro.train.elastic import StepGuard
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import TrainHParams, init_train_state, make_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh

PRESETS = {
    # ~1M params: fast on this CPU container
    "tiny": ModelConfig(name="tiny-lm", family="dense", n_layers=4, d_model=128,
                        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=512,
                        vocab_size=2048, norm="rmsnorm", mlp="swiglu"),
    # ~100M params: the task-spec example config (runs identically; sized
    # for accelerators)
    "100m": ModelConfig(name="lm-100m", family="dense", n_layers=12, d_model=768,
                        n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048,
                        vocab_size=32000, norm="rmsnorm", mlp="swiglu"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    model = build_model(cfg)

    # ---- DDF data pipeline (the paper's technique as the data path) -------
    mesh = make_host_mesh()
    ctx = DDFContext(mesh=mesh, axes=("data",))
    pipe = TokenPipeline(ctx, n_docs=4000, vocab=cfg.vocab_size,
                         seq_len=args.seq, batch=args.batch)
    print(f"pipeline: {pipe.n_docs} docs after dedup+filter, "
          f"{pipe.total_tokens} tokens budget")

    # ---- trainer ------------------------------------------------------------
    hp = TrainHParams(opt=AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps))
    step = jax.jit(make_train_step(model, hp), donate_argnums=(0,))
    state = init_train_state(model, jax.random.key(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state["params"]))
    print(f"model: {cfg.name}, {n_params:,} params")

    guard = StepGuard(args.ckpt_dir)
    t0 = time.time()
    for i, batch in zip(range(args.steps), pipe):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = guard.step(i, step, state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            toks = args.batch * args.seq * (i + 1)
            print(f"step {i:4d}  loss={float(metrics['loss']):.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.2f}  "
                  f"tok/s={toks / (time.time() - t0):.0f}")
        if i and i % args.ckpt_every == 0:
            save(args.ckpt_dir, i, state)
    save(args.ckpt_dir, args.steps, state)
    print(f"done in {time.time() - t0:.1f}s; final checkpoint at "
          f"{args.ckpt_dir}/step_{args.steps:08d} "
          f"(emergency saves: {guard.emergency_saves})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
