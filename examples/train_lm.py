"""Train a small LM end-to-end with CARD-deduplicated checkpointing.

Reduced granite-8b (llama-style) by default; `--params 100m` builds a
~100M-parameter variant (slow on 1 CPU core — a few hundred steps take a
while; reduce --steps accordingly).

    PYTHONPATH=src python examples/train_lm.py --steps 60
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import optim
from repro.api import enable_compile_cache
from repro.checkpoint import DedupCheckpointStore
from repro.configs import get_config
from repro.data import TokenPipeline, TokenPipelineConfig
from repro.models import make_model
from repro.train import make_train_step
from repro.train.step import init_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--params", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("granite-8b").reduced()
    if args.params == "100m":
        cfg = dataclasses.replace(cfg, num_layers=8, d_model=768,
                                  num_heads=12, num_kv_heads=4, d_ff=2048,
                                  vocab_size=32000)
    model = make_model(cfg)
    print(f"model: {cfg.name} ~{cfg.param_count()/1e6:.1f}M params")

    tx = optim.adamw(optim.cosine_schedule(3e-3, 20, args.steps),
                     weight_decay=0.1, max_grad_norm=1.0)
    state = init_state(model.init(jax.random.PRNGKey(0)), tx)
    step_fn = jax.jit(make_train_step(model, tx))
    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, global_batch=args.batch, seq_len=args.seq))

    store = DedupCheckpointStore()
    losses = []
    t0 = time.time()
    for step in range(args.steps):
        state, metrics = step_fn(state, pipe.batch(step))
        losses.append(float(metrics["loss"]))
        if step % 10 == 0:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        if (step + 1) % args.checkpoint_every == 0:
            s = store.save(jax.device_get(state.params), step + 1)
            print(f"  [ckpt] step {step+1}: store DCR {s.dcr:.2f} "
                  f"({s.bytes_stored >> 20} MiB for {s.bytes_in >> 20} MiB raw)",
                  flush=True)
    assert np.mean(losses[-10:]) < np.mean(losses[:10]), "loss must decrease"
    print(f"done: loss {np.mean(losses[:5]):.3f} -> {np.mean(losses[-5:]):.3f}; "
          f"checkpoint store DCR {store.stats.dcr:.2f}")


if __name__ == "__main__":
    main()
