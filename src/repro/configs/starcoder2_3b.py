"""StarCoder2-3B — GQA kv=2, RoPE. [arXiv:2402.19173; hf]

``CHIP`` / ``CHIP_SHAPE`` are one TPU v5e chip's share of a starcoder2-3b
training deployment (AdamW as in ``TrainPolicy()``, seq 4096 as in
``TRAIN_4K``). Every published width is kept: d_model 3072, 24 query /
2 KV heads x 128, d_ff 12288, vocab 49152. Reduced keys:

* ``n_layers`` 30 -> 4: the other 26 layers sit on further chips as
  pipeline stages, so this chip holds 4 layers plus the embedding and
  the head (0.84 B parameters; bf16 weights + f32 Adam moments are
  7.79 GiB of the chip's 16);
* global batch 256 -> 2: one data-parallel replica's share; its
  activations take most of the remaining memory (the step compiles to
  12.83 GiB for a v5e).
"""
import dataclasses

from .base import AttentionConfig, ModelConfig, ShapeSpec

FULL = ModelConfig(
    name="starcoder2-3b", family="dense", n_layers=30, d_model=3072,
    n_heads=24, n_kv_heads=2, d_ff=12288, vocab=49152, head_dim=128,
    attention=AttentionConfig(),
)

CHIP = dataclasses.replace(FULL, n_layers=4)
CHIP_SHAPE = ShapeSpec("train_4k_chip", 4_096, 2, "train")

SMOKE = ModelConfig(
    name="starcoder2-smoke", family="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
)
