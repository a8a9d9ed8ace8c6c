"""Batched serving example: prefill a batch of prompts through a reduced
arch (any of the 10 assigned, --arch selectable) and decode with the KV
cache / recurrent-state path.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-4b
    PYTHONPATH=src python examples/serve_lm.py --arch falcon-mamba-7b
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import list_configs, reduced_config
from repro.models.factory import build_model
from repro.serve.loop import generate
from repro.sharding.rules import init_from_defs
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b",
                    choices=[a for a in list_configs() if a != "paper-logreg"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    bundle = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = init_from_defs(key, bundle.param_defs)

    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["enc_feats"] = np.ones(
            (args.batch, cfg.encoder_seq, cfg.encoder_feature_dim), np.float32)
    if cfg.family == "vlm":
        batch["image_embeds"] = np.ones(
            (args.batch, cfg.num_image_tokens, cfg.image_embed_dim), np.float32)

    cache_len = args.prompt_len + args.new_tokens
    t0 = time.perf_counter()
    out = generate(bundle, params, batch, args.new_tokens, cache_len)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} family={cfg.family}")
    print(f"generated {out.shape[0]}x{out.shape[1]} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s, includes compile)")
    print("first rows:", np.asarray(out)[:2, :10])


if __name__ == "__main__":
    enable_compile_cache()
    main()
