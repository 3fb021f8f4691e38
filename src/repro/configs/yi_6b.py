"""yi-6b [arXiv:2403.04652; hf 01-ai/Yi-6B config.json] — llama-arch GQA kv=4."""
import dataclasses

from .base import ArchConfig

CONFIG = ArchConfig(
    name="yi-6b", family="dense",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=4, d_ff=11_008,
    vocab_size=64_000, mlp="swiglu", rope_theta=5_000_000.0,
    citation="arXiv:2403.04652",
)

#: one TPU v5e chip (16 GB): every width as published, depth cut to 8 of 32
#: layers.  All Yi layers are alike, so 8 still hold whole periods.  At full
#: depth the bf16 weights alone (6.06 B parameters, 12.1 GB) leave no room
#: for the transient copy ``unstack_layers`` makes while packing them.
ONE_CHIP = dataclasses.replace(CONFIG, n_layers=8)
