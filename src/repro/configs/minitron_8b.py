"""minitron-8b [hf:nvidia/Minitron-8B-Base, arXiv:2407.14679] (Nemotron-4
15B pruned in width): 32L d=4096 48H (GQA kv=8) head 128 d_ff=16384
vocab=256000; LayerNorm1p with a bias, ungated squared-ReLU MLP, rotary
on the first half of each head, untied head."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="minitron-8b", family="dense",
    num_layers=32, d_model=4096, num_heads=48, num_kv_heads=8,
    d_ff=16384, vocab_size=256000, head_dim=128, act="relu2",
    norm="layernorm1p", gated_mlp=False, rotary_frac=0.5,
))
