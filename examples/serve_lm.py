"""Batched autoregressive serving with KV caches: prefill a batch of
prompts token-by-token, then decode continuations, reporting tokens/s.

    PYTHONPATH=src python examples/serve_lm.py --batch 4 --prompt-len 16 --gen 24
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import enable_compile_cache
from repro.configs import get_config
from repro.models import make_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dec = jax.jit(model.decode_step)

    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    cache = model.init_cache(args.batch, args.prompt_len + args.gen)

    t0 = time.time()
    logits = None
    for i in range(args.prompt_len):          # prefill via the decode path
        logits, cache = dec(params, prompts[:, i:i + 1], cache)
    prefill_s = time.time() - t0

    out = []
    t0 = time.time()
    tok = jnp.argmax(logits, axis=-1)[:, None]
    for i in range(args.gen):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = dec(params, tok, cache)
        if args.temperature > 0:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(k, logits / args.temperature)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None]
    gen_s = time.time() - t0

    toks = np.stack(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prefill: {args.prompt_len} steps in {prefill_s:.2f}s")
    print(f"decode:  {args.gen} steps in {gen_s:.2f}s "
          f"({args.batch*args.gen/max(gen_s,1e-9):.1f} tok/s)")
    print("sample token ids:", toks[0][:12].tolist())
    assert np.isfinite(np.asarray(logits, np.float32)).all()


if __name__ == "__main__":
    main()
