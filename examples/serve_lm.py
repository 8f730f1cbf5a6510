"""Serve a small model with batched requests: prefill + autoregressive
decode through the KV-cache runtime (ring caches for windowed archs).

    PYTHONPATH=src python examples/serve_lm.py [--arch h2o-danube-1.8b]
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.train.serve import generate
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.vision is not None:
        batch["img_embeds"] = jax.random.normal(
            key, (args.batch, cfg.vision.n_img_tokens,
                  cfg.vision.embed_dim))
    if cfg.is_encdec:
        batch["enc_frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder.src_len, cfg.d_model))

    t0 = time.perf_counter()
    out = generate(model, params, batch, n_steps=args.new_tokens, key=key,
                   temperature=args.temperature, top_k=40)
    dt = time.perf_counter() - t0
    tok_s = args.batch * args.new_tokens / dt
    print(f"arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} new={args.new_tokens}")
    print(f"generated ids[0]: {out[0].tolist()}")
    print(f"{dt:.2f}s end-to-end ({tok_s:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
