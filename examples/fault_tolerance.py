"""Fault-tolerance demo: train, kill mid-run, auto-resume from the atomic
checkpoint, and verify the loss trajectory continues (not restarts).

    PYTHONPATH=src python examples/fault_tolerance.py
"""
import shutil
import tempfile

from repro.config import ModelConfig, SVRGConfig, TrainConfig
from repro.data.synthetic_lm import SyntheticLMDataset
from repro.models.factory import build_model
from repro.train.loop import train
from repro.utils.compile_cache import enable_compile_cache

CKDIR = tempfile.mkdtemp(prefix="repro_ft_")

cfg = ModelConfig(
    name="ft-demo", family="dense", num_layers=2, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512, dtype="float32",
    param_dtype="float32", remat="none", tie_embeddings=True)


def tcfg(steps):
    return TrainConfig(steps=steps, optimizer="svrg", learning_rate=0.1,
                       warmup_steps=2, schedule="constant",
                       checkpoint_dir=CKDIR, checkpoint_every=10,
                       log_every=10,
                       svrg=SVRGConfig(snapshot_every=20, snapshot_batches=2))


def main():
    bundle = build_model(cfg)
    ds = SyntheticLMDataset(cfg.vocab_size, 64, 8)

    print("=== phase 1: run 25 steps, then 'crash' ===")
    train(bundle, tcfg(25), ds.batch_at)

    print("\n=== phase 2: relaunch — auto-resumes from step 20 ===")
    seen = []
    train(bundle, tcfg(60), ds.batch_at, hooks=lambda s, m: seen.append(s))
    assert min(seen) >= 20, "should have resumed, not restarted!"
    print(f"\nresumed at step {min(seen)}, finished at {max(seen)} — "
          "checkpoint/restart works.")
    shutil.rmtree(CKDIR, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
