"""DeepSeek-LLM-67B — dense llama-style GQA (64 query heads over 8 KV
heads). [arXiv:2401.02954]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-67b",
    arch_type="dense",
    n_layers=95,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    d_ff=22016,
    vocab_size=102400,
    attention="full",
    rope="rope",
    citation="arXiv:2401.02954",
)
