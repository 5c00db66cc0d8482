"""SmolLM-360M — llama-arch small dense GQA. [hf:HuggingFaceTB/SmolLM-360M].

32L d_model=960 15H (GQA kv=5) d_ff=2560 vocab=49152, rope_theta=10000
(the published config.json).
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="smollm-360m",
    family="dense",
    num_layers=32,
    d_model=960,
    num_heads=15,
    num_kv_heads=5,
    d_ff=2560,
    vocab_size=49152,
    head_dim=64,
    tie_embeddings=True,
    rope_theta=10000.0,
    source="hf:HuggingFaceTB/SmolLM-360M",
))
