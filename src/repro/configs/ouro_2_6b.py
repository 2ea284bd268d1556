"""Ouro-2.6B [arXiv:2510.25741] — a looped LM (LoopLM): 48 dense
layers run ``total_ut_steps`` = 4 times per forward with shared weights.

Published sizes (huggingface.co/ByteDance/Ouro-2.6B, ``config.json``):
hidden 2048, 16 heads with 16 KV heads of 128, SwiGLU intermediate
5632, RMSNorm (eps 1e-6), RoPE theta 1e6, vocab 49152, untied
embeddings, no biases.  Each layer uses sandwich norms
(``h + N2(attn(N1(h)))``, then ``h + N4(mlp(N3(h)))``); a per-step
early-exit gate sits on the final-normed state, and with
``early_exit_threshold`` 1 every step runs.
"""
from .base import ArchConfig

ARCH = ArchConfig(
    name="ouro-2.6b", family="dense",
    n_layers=48, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=5632, vocab=49152, head_dim=128,
    norm="rmsnorm", act="swiglu", rope_theta=1_000_000.0,
    tie_embeddings=False,
    loop_steps=4, norm_placement="sandwich",
    exit_gate=True, early_exit_threshold=1.0,
    notes="LoopLM: 4 weight-shared passes of the 48-layer stack, sandwich "
          "RMSNorm, per-step exit gate",
)
