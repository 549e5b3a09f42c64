"""Minitron-8B [arXiv:2407.14679, the 8B row; Hugging Face
``nvidia/Minitron-8B-Base`` config.json, ``model_type: nemotron``] —
width-pruned Nemotron-4 15B.

32 layers, hidden 4096, 48 query heads of 128 (q width 6144), GQA with
8 KV heads, squared-ReLU MLP of 16,384 with no biases (no linear layer
has one), vocabulary 256,000, 4,096 positions, RoPE theta 10,000 on the
first half of each head (``partial_rotary_factor`` 0.5), LayerNorm1p
with eps 1e-5, untied embeddings: 8,271,699,968 parameters.

LayerNorm1p scales by ``1 + gamma`` with gamma starting at 0; the
system's LayerNorm with its scale starting at 1 is the same function.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="minitron-8b",
    family="dense",
    num_layers=32,
    d_model=4096,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    d_ff=16384,
    vocab_size=256_000,
    mlp_type="relu2",
    mlp_bias=False,
    norm_type="layer",
    norm_eps=1e-5,
    tie_embeddings=False,
    rope_theta=10_000.0,
    rope_fraction=0.5,
    max_seq_len=4096,
    decode_window=8192,
    source="arXiv:2407.14679 (Minitron-8B); nvidia/Minitron-8B-Base config.json",
)

# the published 6:1 query-to-KV grouping and half rotary, at a CPU size
SMOKE = CONFIG.replace(num_layers=2, d_model=128, num_heads=6, num_kv_heads=1,
                       head_dim=32, d_ff=256, vocab_size=512,
                       param_dtype="float32", compute_dtype="float32")
