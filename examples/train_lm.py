"""End-to-end training example: full stack — xMem admission gate,
synthetic data, checkpointing + resume, emergency save.

Default is a CPU-sized model for a quick demo; ``--model-100m`` selects a
~100M-parameter config (a few hundred steps is feasible on a real
accelerator; on this 1-core CPU box expect ~seconds/step).

  PYTHONPATH=src python examples/train_lm.py --steps 100
  PYTHONPATH=src python examples/train_lm.py --model-100m --steps 300
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs.base import (AttentionConfig, ModelConfig,  # noqa: E402
                                smoke_shape)
from repro.launch.train import train_loop                      # noqa: E402
from repro.train import TrainPolicy                            # noqa: E402

MODEL_100M = ModelConfig(
    name="demo-100m", family="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=4, d_ff=2048, vocab=32768,
    attention=AttentionConfig(),
)

MODEL_DEMO = ModelConfig(
    name="demo-8m", family="dense", n_layers=4, d_model=256,
    n_heads=8, n_kv_heads=4, d_ff=768, vocab=8192,
    attention=AttentionConfig(),
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--model-100m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = MODEL_100M if args.model_100m else MODEL_DEMO
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params)")
    shape = smoke_shape(seq_len=args.seq, global_batch=args.batch)
    res = train_loop(cfg, shape,
                     TrainPolicy(optimizer="adamw", learning_rate=3e-4),
                     steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=50)
    print(f"final loss: {res.loss:.4f}")


if __name__ == "__main__":
    main()
