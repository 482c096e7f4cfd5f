"""End-to-end serving driver (the paper's kind is inference, so the
end-to-end example serves a small model with batched requests).

Trains a small LM briefly on the synthetic permutation task so generation is
meaningfully non-random, then serves BATCHED requests through prefill +
greedy decode, in fp32 and int8 weight-only (the paper's quantization at LLM
scale), comparing outputs and throughput.

  PYTHONPATH=src python examples/serve_llm.py [--steps 60]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as M
from repro.optim import adamw
from repro.serve.engine import ServeSession
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config("stablelm-3b").reduced()
    print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model})")

    # -- short training run on the synthetic next-token task --------------
    data = SyntheticLM(DataConfig(cfg.vocab_size, 32, 8, seed=0))
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32,
                           max_seq=256)
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=5,
                                total_steps=args.steps)
    opt_state = adamw.init(params)
    step = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
    for s in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        params, opt_state, m = step(params, opt_state, batch)
        if s % 20 == 0 or s == args.steps - 1:
            print(f"  train step {s:3d} loss {float(m['loss']):.3f}")

    # -- batched serving ---------------------------------------------------
    rng = np.random.default_rng(1)
    prompts = data.batch(10_000)["tokens"][:args.batch, :16]

    for quantized in (False, True):
        sess = ServeSession(cfg, params, max_seq=256, quantized=quantized)
        t0 = time.time()
        out = sess.generate(prompts, args.max_new)
        dt = time.time() - t0
        toks = args.batch * args.max_new
        # quality: fraction of generated tokens following the synthetic
        # permutation rule (0.9 is the Bayes ceiling at 10% noise)
        follow = float(np.mean(
            data.perm[out[:, :-1].ravel()] == out[:, 1:].ravel()))
        tag = "int8" if quantized else "fp32"
        print(f"[{tag}] {toks} tokens in {dt:.2f}s ({toks/dt:6.1f} tok/s)  "
              f"rule-following {follow:.2f}")
        if not quantized:
            ref = out
    agree = float(np.mean(ref == out))
    print(f"int8 vs fp32 token agreement: {agree:.2f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
