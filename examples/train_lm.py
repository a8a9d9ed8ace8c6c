"""End-to-end driver: train a ~100M-param LM with the AsySVRG optimizer for
a few hundred steps on synthetic data, with checkpointing enabled; compares
against the plain-SGD baseline (the Hogwild!-equivalent compute).

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--small]

--small shrinks to a CPU-friendly ~1M model (used by CI/smoke).
"""
import argparse


from repro.config import ModelConfig, SVRGConfig, TrainConfig
from repro.data.synthetic_lm import SyntheticLMDataset
from repro.models.factory import build_model
from repro.train.loop import train
from repro.utils.compile_cache import enable_compile_cache


def model_cfg(small: bool) -> ModelConfig:
    if small:
        return ModelConfig(
            name="lm-small", family="dense", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=512, dtype="float32", param_dtype="float32",
            remat="none", tie_embeddings=True)
    # ~100M params: 12L x 768 (gpt2-small scale), llama-style blocks
    return ModelConfig(
        name="lm-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
        vocab_size=32768, dtype="float32", param_dtype="float32",
        remat="none", tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = model_cfg(args.small)
    bundle = build_model(cfg)
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch)

    for opt in ("svrg", "sgd"):
        print(f"\n=== optimizer: {opt} ===")
        tcfg = TrainConfig(
            steps=args.steps, optimizer=opt, learning_rate=0.3,
            warmup_steps=10, schedule="cosine", grad_clip=1.0,
            checkpoint_dir=(args.checkpoint_dir + "_" + opt),
            checkpoint_every=100, log_every=25,
            svrg=SVRGConfig(snapshot_every=50, snapshot_batches=4),
        )
        losses = []
        train(bundle, tcfg, ds.batch_at,
              hooks=lambda s, m: losses.append(m["loss"]))
        print(f"{opt}: first={losses[0]:.4f} last={losses[-1]:.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
