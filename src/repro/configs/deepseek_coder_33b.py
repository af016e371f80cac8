"""DeepSeek-Coder 33B [arXiv:2401.14196; hf deepseek-ai/deepseek-coder-33b-base
config.json] — llama-arch: 62L d_model=7168 56H GQA(kv=8) d_ff=19200
vocab=32256, RoPE theta 1e5 with linear scaling x4, RMSNorm eps 1e-6."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-coder-33b",
    family="dense",
    n_layers=62,
    d_model=7168,
    n_heads=56,
    n_kv_heads=8,
    head_dim=128,
    d_ff=19200,
    vocab_size=32256,
    norm_eps=1e-6,
    rope_theta=1e5,
    rope_scaling=4.0,
)
